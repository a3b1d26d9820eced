"""Operations and bytes that a dense decoder's serving dispatches require,
computed from logical shapes only.

Counts depend on sequence lengths, head counts and head width, never on
how a kernel lays the work out (padding, packed lanes, padded batch rows
or chunk columns are not counted), so a layout change cannot inflate a
share of a peak.  A multiply-add counts as two operations.

``model`` is the ``model`` block of a configuration file
(``configs/<name>.json``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

GATED = ("silu", "gelu_gated")
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["n_heads"]


def layer_matmul_params(model: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, h, hkv, hd, f = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                        head_dim(model), model["d_ff"])
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = (3 if model["activation"] in GATED else 2) * d * f
    return attn + mlp


def lm_head_params(model: dict) -> int:
    return model["d_model"] * model["vocab_size"]


@dataclass
class DispatchWork:
    """What one dispatch requires: the model's operations, and the paged
    attention kernel's operations and bytes, summed over layers."""

    tokens: int = 0  # query tokens computed for real rows
    model_flops: float = 0.0
    attn_flops: float = 0.0
    attn_bytes: float = 0.0

    def attn_least_s(self, matmul_flops_per_s: float, bytes_per_s: float) -> float:
        """Least time the attention kernel could take on a chip with these
        peaks: the larger of the compute and the memory bound."""
        return max(self.attn_flops / matmul_flops_per_s, self.attn_bytes / bytes_per_s)


def extend_work(model: dict, dtype: str, offsets, lengths, completes) -> DispatchWork:
    """A chunk-extend (prefill) dispatch: row ``i`` appends ``lengths[i]``
    tokens at cache positions ``offsets[i] ..``; token ``t`` attends
    ``offsets[i] + t + 1`` positions.  ``completes[i]`` marks a row whose
    prompt ends in this chunk: only its last token's logits are used."""
    off = np.asarray(offsets, np.float64)
    n = np.asarray(lengths, np.float64)
    keep = n > 0
    off, n = off[keep], n[keep]
    done = np.asarray(completes, bool)[keep]
    L, h, hkv, hd = model["n_layers"], model["n_heads"], model["n_kv_heads"], head_dim(model)
    b = DTYPE_BYTES[dtype]
    ctx_sum = float(np.sum(n * off + n * (n + 1) / 2))  # sum over tokens of attended positions
    attn_flops = L * 4.0 * h * hd * ctx_sum
    # K and V of every attended position once per row, the queries read, the outputs written
    attn_bytes = L * float(np.sum((off + n) * hkv * hd * 2 * b + 2 * n * h * hd * b))
    model_flops = (2.0 * L * layer_matmul_params(model) * float(n.sum()) + attn_flops
                   + 2.0 * lm_head_params(model) * float(done.sum()))
    return DispatchWork(int(n.sum()), model_flops, attn_flops, attn_bytes)


def decode_work(model: dict, dtype: str, positions) -> DispatchWork:
    """A decode dispatch over the rows that decode: a row at cache position
    ``p`` writes one token there and attends ``p + 1`` positions."""
    ctx = np.asarray(positions, np.float64) + 1
    L, h, hkv, hd = model["n_layers"], model["n_heads"], model["n_kv_heads"], head_dim(model)
    b = DTYPE_BYTES[dtype]
    rows = len(ctx)
    attn_flops = L * 4.0 * h * hd * float(ctx.sum())
    attn_bytes = L * float(np.sum(ctx * hkv * hd * 2 * b + 2 * h * hd * b))
    model_flops = (rows * 2.0 * (L * layer_matmul_params(model) + lm_head_params(model))
                   + attn_flops)
    return DispatchWork(rows, model_flops, attn_flops, attn_bytes)

"""Plain reference of a dense decoder-only transformer, in float32 at
``highest`` matmul precision.

It follows the configuration file's ``model`` block and imports nothing
of the program under test: token embeddings plus, where
``max_position_embeddings`` is set, a learned table of positions;
pre-norm blocks of RMSNorm or LayerNorm (``norm``), attention over
``n_kv_heads`` shared key/value heads with optionally biased q/k/v
projections (``qkv_bias``) and, where positions are not learned, rotary
positions (the half-split rotation), and a feed-forward block (gated
SiLU, or ungated GELU in its tanh form); then a final norm and an untied
output head.  No
cache, no batching, no kernels: one sequence, every position, layer by
layer, with causal attention computed in blocks of queries so that it
fits beside the weights.

Weights are named by path (``weight_spec``) and made by
``chipbench.weights`` from the run's seed, the same function that makes
the program's weights; the reference makes its own copy.

This file is the one home of what the harness knows about the dense
family.  Besides ``weight_spec`` and ``Reference`` it exports:

- ``weight_scale(path, shape, model)``: the rule ``chipbench.weights.make``
  draws every leaf by, the program's and the reference's alike;
- ``extend_work`` and ``decode_work``: the operations and bytes a prefill
  or a decode dispatch requires (``chipbench.flops.DispatchWork``), which
  the recorder counts for ``mfu`` and the paged kernel's roofline;
- ``tiny(model)``: the model keys a CPU test sets to hold a configuration
  of this family at tiny widths (``TINY``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import DTYPE_BYTES, DispatchWork

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
ROW_BLOCK = 256
NORMS = ("ln1", "ln2", "ln_f")
BIAS = 0.1
GATED = ("silu", "gelu_gated")
# tiny widths for CPU tests; the key/value head count keeps its kind (``tiny``)
TINY = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128, vocab_size=2048)


def tiny(model: dict) -> dict:
    """``TINY``, with one shared key/value head kept one (MQA stays MQA)."""
    return dict(TINY, n_kv_heads=1 if model["n_kv_heads"] == 1 else 4)


def weight_spec(model: dict) -> dict:
    """``{path: shape}`` of every weight, layers stacked on a leading axis."""
    L, d, f, v = model["n_layers"], model["d_model"], model["d_ff"], model["vocab_size"]
    h, hkv, hd = model["n_heads"], model["n_kv_heads"], head_dim(model)
    spec = {
        ("embed",): (v, d),
        ("layers", "ln1", "w"): (L, d),
        ("layers", "attn", "wq"): (L, d, h * hd),
        ("layers", "attn", "wk"): (L, d, hkv * hd),
        ("layers", "attn", "wv"): (L, d, hkv * hd),
        ("layers", "attn", "wo"): (L, h * hd, d),
        ("layers", "ln2", "w"): (L, d),
        ("layers", "mlp", "wi"): (L, d, f),
        ("layers", "mlp", "wo"): (L, f, d),
        ("ln_f", "w"): (d,),
        ("lm_head",): (d, v),
    }
    if model["activation"] == "silu":
        spec[("layers", "mlp", "wg")] = (L, d, f)
    if model["norm"] == "layernorm":
        for norm in (("layers", "ln1"), ("layers", "ln2")):
            spec[norm + ("b",)] = (L, d)
        spec[("ln_f", "b")] = (d,)
    if model["qkv_bias"]:
        spec[("layers", "attn", "bq")] = (L, h * hd)
        spec[("layers", "attn", "bk")] = (L, hkv * hd)
        spec[("layers", "attn", "bv")] = (L, hkv * hd)
    if model["max_position_embeddings"]:
        spec[("pos",)] = (model["max_position_embeddings"], d)
    return spec


def weight_scale(path: tuple, shape: tuple, model: dict):
    """Standard deviation of the leaf at ``path``; ``"ones"`` for a norm's
    gain; ``None`` for a leaf this family does not have.

    Scales follow the usual initialisation of such models: embeddings and
    learned positions at 0.02, projections at ``1 / sqrt(fan_in)``, and
    the two projections that write back into the residual stream further
    by ``1 / sqrt(2 n_layers)``.  Biases (a LayerNorm's, the q/k/v
    projections') are drawn at 0.1, not zeros, so that the comparison
    sees a program that drops one."""
    name, parent = path[-1], (path[-2] if len(path) > 1 else "")
    if parent in NORMS:
        return {"w": "ones", "b": BIAS}[name]
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    resid = 1.0 / math.sqrt(2 * model["n_layers"])
    if path in (("embed",), ("pos",)):
        return 0.02
    if path == ("lm_head",):
        return 1.0 / math.sqrt(fan_in)
    if parent == "attn" and name in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(fan_in)
    if parent == "attn" and name in ("bq", "bk", "bv"):
        return BIAS
    if parent in ("attn", "mlp") and name == "wo":
        return resid / math.sqrt(fan_in)
    if parent == "mlp" and name in ("wi", "wg"):
        return 1.0 / math.sqrt(fan_in)
    return None


# Operations and bytes of the serving dispatches, from logical shapes
# only: sequence lengths, head counts and head width, never how a kernel
# lays the work out (padding, packed lanes, padded batch rows or chunk
# columns are not counted), so a layout change cannot inflate a share of
# a peak.  A multiply-add counts as two operations.


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


def _norm(x, p, m):
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m["norm_eps"]) * p["w"] + p["b"]
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + m["norm_eps"]) * p["w"]
    raise ValueError(f"norm {m['norm']!r} is not in the reference")


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@partial(jax.jit, static_argnames=("model",))
def _layer(x, layers, i, *, model):
    """One pre-norm decoder block over a whole padded sequence ``x (S, d)``."""
    m = dict(model)
    S = x.shape[0]
    h, hkv, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    g = h // hkv
    w = jax.tree.map(lambda a: a[i], layers)
    pos = jnp.arange(S)
    hn = _norm(x, w["ln1"], m)
    a = w["attn"]
    q, k, v = _mm(hn, a["wq"]), _mm(hn, a["wk"]), _mm(hn, a["wv"])
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k, v = q.reshape(S, h, hd), k.reshape(S, hkv, hd), v.reshape(S, hkv, hd)
    if not m["max_position_embeddings"]:
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    kh = jnp.repeat(k, g, axis=1)  # query head j reads key/value head j // g
    vh = jnp.repeat(v, g, axis=1)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, axis=0)  # (Qb, h, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, kh, precision=HIGHEST) / math.sqrt(hd)
        qpos = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vh, precision=HIGHEST)

    att = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK)).reshape(S, h * hd)
    x = x + _mm(att, w["attn"]["wo"])
    hn = _norm(x, w["ln2"], m)
    if m["activation"] == "silu":
        ff = jax.nn.silu(_mm(hn, w["mlp"]["wi"])) * _mm(hn, w["mlp"]["wg"])
    elif m["activation"] == "gelu":
        ff = _gelu_tanh(_mm(hn, w["mlp"]["wi"]))
    else:
        raise ValueError(f"activation {m['activation']!r} is not in the reference")
    return x + _mm(ff, w["mlp"]["wo"])


@partial(jax.jit, static_argnames=("model",))
def _head_gaps(x, rows, targets, ln_f, lm_head, *, model):
    """At each chosen row, how far the target's logit lies below the best."""
    lg = _mm(_norm(x[rows], ln_f, dict(model)), lm_head)
    return lg.max(axis=-1) - jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]


class Reference:
    """Logits of the reference model at chosen positions of one sequence."""

    def __init__(self, model: dict, weights: dict, max_len: int):
        if model.get("family", "dense") != "dense" or model.get("sliding_window", 0):
            raise ValueError("the dense reference runs dense full-attention models only")
        self.model = tuple(sorted((k, v) for k, v in model.items() if not isinstance(v, (list, dict))))
        self.w = weights
        self.S = -(-max_len // Q_BLOCK) * Q_BLOCK

    def gaps_at(self, tokens, positions, targets) -> np.ndarray:
        """``(len(positions),)`` gaps: entry ``j`` is how far the logit of
        ``targets[j]``, as the token after ``tokens[positions[j]]``, lies
        below the best logit there."""
        n = len(tokens)
        if n > self.S:
            raise ValueError(f"sequence of {n} tokens exceeds the reference length {self.S}")
        ids = np.zeros(self.S, np.int32)
        ids[:n] = tokens
        x = self.w["embed"][jnp.asarray(ids)]
        if dict(self.model)["max_position_embeddings"]:
            x = x + self.w["pos"][: self.S]
        for i in range(dict(self.model)["n_layers"]):
            x = _layer(x, self.w["layers"], i, model=self.model)
        out = []
        positions = np.asarray(positions, np.int32)
        targets = np.asarray(targets, np.int32)
        for s in range(0, len(positions), ROW_BLOCK):
            rows, want = np.zeros(ROW_BLOCK, np.int32), np.zeros(ROW_BLOCK, np.int32)
            chunk = positions[s: s + ROW_BLOCK]
            rows[: len(chunk)], want[: len(chunk)] = chunk, targets[s: s + ROW_BLOCK]
            gap = _head_gaps(x, jnp.asarray(rows), jnp.asarray(want), self.w["ln_f"],
                             self.w["lm_head"], model=self.model)
            out.append(np.asarray(gap)[: len(chunk)])
        return np.concatenate(out, axis=0)

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
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
ROW_BLOCK = 256


def weight_spec(model: dict) -> dict:
    """``{path: shape}`` of every weight, layers stacked on a leading axis."""
    L, d, f, v = model["n_layers"], model["d_model"], model["d_ff"], model["vocab_size"]
    h, hkv = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
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
    h, hkv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // h
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

"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a per-token latent ``c_kv`` of rank ``kv_lora_rank``
plus a single shared RoPE key of ``rope_head_dim``; queries go through
their own low-rank path.  The decode cache stores only
``(kv_lora_rank + rope_head_dim)`` per token — the paper's 93% KV-cache
reduction — and attention against the cache is computed in latent space
by *absorbing* ``k_up`` into the query (so the cache is never expanded to
per-head keys at decode time).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_norm, apply_rope, dense_init, norm_init
from repro.sharding.logical import shard

Params = Dict[str, jax.Array]


def mla_init(key, cfg: ArchConfig, dtype, depth_scale: float) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    qk = cfg.nope_head_dim
    qr = cfg.rope_head_dim
    v = cfg.v_head_dim
    ks = jax.random.split(key, 7)
    return {
        "q_down": dense_init(ks[0], d, cfg.q_lora_rank, dtype),
        "q_norm": norm_init(cfg.q_lora_rank, cfg.norm, dtype),
        "q_up": dense_init(ks[1], cfg.q_lora_rank, h * (qk + qr), dtype),
        "kv_down": dense_init(ks[2], d, cfg.kv_lora_rank + qr, dtype),
        "kv_norm": norm_init(cfg.kv_lora_rank, cfg.norm, dtype),
        "k_up": dense_init(ks[3], cfg.kv_lora_rank, h * qk, dtype),
        "v_up": dense_init(ks[4], cfg.kv_lora_rank, h * v, dtype),
        "wo": dense_init(ks[5], h * v, d, dtype, scale=depth_scale),
    }


def _project_q(p: Params, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    b, s, _ = x.shape
    h, qk, qr = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    q = apply_norm(p["q_norm"], x @ p["q_down"], cfg.norm, cfg.norm_eps) @ p["q_up"]
    q = q.reshape(b, s, h, qk + qr)
    q_nope, q_rope = q[..., :qk], q[..., qk:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _compress_kv(p: Params, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    """Returns (c_kv (b,s,r), k_rope (b,s,qr)) — exactly what decode caches."""
    kv = x @ p["kv_down"]
    c_kv, k_rope = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank :]
    c_kv = apply_norm(p["kv_norm"], c_kv, cfg.norm, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def apply_mla(
    p: Params,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence (train / prefill) MLA with causal masking."""
    b, s, _ = x.shape
    h, qk, qr, vd = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]

    q_nope, q_rope = _project_q(p, x, cfg, positions)
    c_kv, k_rope = _compress_kv(p, x, cfg, positions)

    k_nope = (c_kv @ p["k_up"]).reshape(b, s, h, qk)
    v = (c_kv @ p["v_up"]).reshape(b, s, h, vd)
    k_nope = shard(k_nope, "batch", "seq", "heads", "head_dim")
    v = shard(v, "batch", "seq", "heads", "head_dim")

    scale = 1.0 / math.sqrt(qk + qr)
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * scale
    logits = shard(logits, "batch", "heads", None, "kv_seq")
    qpos = positions[:, None, :, None]
    kpos = positions[:, None, None, :]
    logits = jnp.where(kpos <= qpos, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = out.reshape(b, s, h * vd)
    return shard(out @ p["wo"], "batch", "seq", "embed")


def mla_decode_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Dict[str, jax.Array]:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype),
    }


def apply_mla_extend(
    p: Params,
    x: jax.Array,  # (b, T, d) chunk of new tokens
    cache: Dict[str, jax.Array],
    positions: jax.Array,  # (b, T) absolute cache positions of the chunk
    cfg: ArchConfig,
    *,
    valid: Optional[jax.Array] = None,  # (b, T) real (non-padded) tokens
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked-prefill MLA: T new tokens per row against the compressed
    cache in one shot (absorbed form, same math as ``apply_mla_decode``).

    Right-padded tokens (``valid`` False) have their writes redirected
    out of bounds, where JAX drops them — the cache only ever receives
    real tokens, and the ``slot <= q_pos`` mask supplies causality.
    """
    b, T, _ = x.shape
    h, qk, qr, vd, r = (
        cfg.n_heads,
        cfg.nope_head_dim,
        cfg.rope_head_dim,
        cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    q_nope, q_rope = _project_q(p, x, cfg, positions)  # (b,T,h,*)
    c_new, kr_new = _compress_kv(p, x, cfg, positions)  # (b,T,r), (b,T,qr)

    rows = jnp.arange(b)[:, None]
    t_cache = cache["c_kv"].shape[1]
    write = positions
    if valid is not None:
        write = jnp.where(valid, write, t_cache)  # out of bounds -> dropped
    c_kv = cache["c_kv"].at[rows, write].set(c_new.astype(cache["c_kv"].dtype))
    k_rope = cache["k_rope"].at[rows, write].set(kr_new.astype(cache["k_rope"].dtype))
    c_kv = shard(c_kv, "cache_batch", "kv_seq", None)
    k_rope = shard(k_rope, "cache_batch", "kv_seq", None)

    k_up = p["k_up"].reshape(r, h, qk)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, k_up)

    scale = 1.0 / math.sqrt(qk + qr)
    t = c_kv.shape[1]
    logits = (
        jnp.einsum("bqhr,btr->bhqt", q_lat, c_kv, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,btd->bhqt", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * scale
    mask = jnp.arange(t)[None, None, None, :] <= positions[:, None, :, None]  # (b,1,T,t)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)

    ctx = jnp.einsum("bhqt,btr->bqhr", probs, c_kv)
    v_up = p["v_up"].reshape(r, h, vd)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, v_up).reshape(b, T, h * vd)
    return shard(out @ p["wo"], "batch", "seq", "embed"), {"c_kv": c_kv, "k_rope": k_rope}


def mla_page_width(cfg: ArchConfig) -> int:
    """Columns per paged-cache slot: latent ``c_kv`` + shared RoPE key."""
    return cfg.kv_lora_rank + cfg.rope_head_dim


def apply_mla_paged(
    p: Params,
    x: jax.Array,  # (b, T, d) — T = 1 is decode, T > 1 chunk-extend
    kv_pages: jax.Array,  # (n_pages, ps, r + qr) — this layer's page pool
    page_table: jax.Array,  # (b, P) int32; entries >= n_pages = unallocated
    positions: jax.Array,  # (b, T) absolute cache positions
    cfg: ArchConfig,
    *,
    impl: str = "jnp",
    valid: Optional[jax.Array] = None,  # (b, T) real (non-padded) tokens
) -> Tuple[jax.Array, jax.Array]:
    """MLA decode / chunk-extend against a *paged* compressed cache.

    Each cache slot stores ``concat(c_kv, k_rope)``; the absorbed-form
    score ``q_lat . c_kv + q_rope . k_rope`` is a single dot against that
    concatenated slot, so the paged flash kernel serves MLA as its
    ``Hkv = 1`` case with values read from the first ``kv_lora_rank``
    columns of the shared page (``v_width``).  The jnp fallback keeps the
    two score terms as separate einsums so it is numerically identical to
    the dense ``apply_mla_decode`` path (token parity with ``cache_mode=
    'dense'``).  Writes for padded/parked rows go to the out-of-bounds
    page sentinel and are dropped.
    """
    b, T, _ = x.shape
    h, qk, qr, vd, r = (
        cfg.n_heads,
        cfg.nope_head_dim,
        cfg.rope_head_dim,
        cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    q_nope, q_rope = _project_q(p, x, cfg, positions)  # (b,T,h,*)
    c_new, kr_new = _compress_kv(p, x, cfg, positions)  # (b,T,r), (b,T,qr)
    kv_new = jnp.concatenate([c_new, kr_new], axis=-1)  # (b,T,r+qr)

    n_pages, ps = kv_pages.shape[0], kv_pages.shape[1]
    P = page_table.shape[1]
    rows = jnp.arange(b)[:, None]
    wp = page_table[rows, jnp.minimum(positions // ps, P - 1)]  # (b,T)
    if valid is not None:
        wp = jnp.where(valid, wp, n_pages)  # out of bounds -> dropped
    kv_pages = kv_pages.at[wp, positions % ps].set(kv_new.astype(kv_pages.dtype))

    k_up = p["k_up"].reshape(r, h, qk)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, k_up)  # (b,T,h,r)
    scale = 1.0 / math.sqrt(qk + qr)

    if impl == "kernel":
        from repro.kernels import ops as kops

        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # (b,T,h,r+qr)
        pool = kv_pages[:, None]  # (n_pages, 1, ps, r+qr): Hkv=1, no copy
        ctx = kops.paged_attention(
            q_eff, pool, pool,
            page_table, positions[:, 0], scale=scale, v_width=r,
        )  # (b,T,h,r)
    else:
        safe = jnp.minimum(page_table, n_pages - 1)
        kv_full = kv_pages[safe].reshape(b, P * ps, r + qr)
        logits = (
            jnp.einsum("bqhr,btr->bhqt", q_lat, kv_full[..., :r],
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,btd->bhqt", q_rope, kv_full[..., r:],
                         preferred_element_type=jnp.float32)
        ) * scale
        kv_pos = jnp.arange(P * ps)[None, None, None, :]
        alloc = jnp.repeat(page_table < n_pages, ps, axis=1)  # (b, P*ps)
        mask = jnp.logical_and(
            kv_pos <= positions[:, None, :, None], alloc[:, None, None, :]
        )
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqt,btr->bqhr", probs, kv_full[..., :r])

    v_up = p["v_up"].reshape(r, h, vd)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, v_up).reshape(b, T, h * vd)
    return shard(out @ p["wo"], "batch", "seq", "embed"), kv_pages


def apply_mla_decode(
    p: Params,
    x: jax.Array,  # (b, 1, d)
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # () current position
    cfg: ArchConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step against the compressed cache (absorbed form).

    score(t) = q_nope·(c_kv[t] K_up)  + q_rope·k_rope[t]
             = (q_nope K_upᵀ)·c_kv[t] + q_rope·k_rope[t]     # absorb k_up
    out      = softmax·(c_kv V_up)    = (softmax·c_kv) V_up  # absorb v_up
    """
    b = x.shape[0]
    h, qk, qr, vd, r = (
        cfg.n_heads,
        cfg.nope_head_dim,
        cfg.rope_head_dim,
        cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    pos = jnp.asarray(pos, jnp.int32)
    uniform_pos = pos.ndim == 0
    pos_v = jnp.broadcast_to(pos, (b,))
    positions = pos_v[:, None]
    q_nope, q_rope = _project_q(p, x, cfg, positions)  # (b,1,h,*)
    c_new, kr_new = _compress_kv(p, x, cfg, positions)  # (b,1,r), (b,1,qr)

    if uniform_pos:  # scalar write partitions cleanly (see transformer.py)
        c_kv = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos, 0)
        )
        k_rope = jax.lax.dynamic_update_slice(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos, 0)
        )
    else:
        rows = jnp.arange(b)
        c_kv = cache["c_kv"].at[rows, pos_v].set(c_new[:, 0].astype(cache["c_kv"].dtype))
        k_rope = cache["k_rope"].at[rows, pos_v].set(kr_new[:, 0].astype(cache["k_rope"].dtype))
    c_kv = shard(c_kv, "cache_batch", "kv_seq", None)
    k_rope = shard(k_rope, "cache_batch", "kv_seq", None)

    # absorb k_up into q: (b,1,h,qk) @ (r, h, qk) -> (b,h,r)
    k_up = p["k_up"].reshape(r, h, qk)
    q_lat = jnp.einsum("bqhd,rhd->bhr", q_nope, k_up)  # q=1 squeezed

    scale = 1.0 / math.sqrt(qk + qr)
    t = c_kv.shape[1]
    logits = (
        jnp.einsum("bhr,btr->bht", q_lat, c_kv, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,btd->bht", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * scale
    mask = jnp.arange(t)[None, None, :] <= pos_v[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)

    # absorbed value path: (b,h,t)·(b,t,r) -> (b,h,r), then V_up
    ctx = jnp.einsum("bht,btr->bhr", probs, c_kv)
    v_up = p["v_up"].reshape(r, h, vd)
    out = jnp.einsum("bhr,rhd->bhd", ctx, v_up).reshape(b, 1, h * vd)
    return shard(out @ p["wo"], "batch", "seq", "embed"), {"c_kv": c_kv, "k_rope": k_rope}

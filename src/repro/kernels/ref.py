"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_reference(
    q: jax.Array,  # (BH, Sq, hd)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Direct softmax attention with causal/sliding-window masking."""
    bh, sq, hd = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
    if window > 0:
        mask = jnp.logical_and(mask, kp > qp - window)
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_reference(
    q: jax.Array,  # (B, T, H, dk)
    k_pages: jax.Array,  # (n_pages, Hkv / r, page_size, r * dk) — head-major pool
    v_pages: jax.Array,  # (n_pages, Hkv / r, page_size, r * dv)
    page_table: jax.Array,  # (B, P) int32; entries >= n_pages = unallocated
    offsets: jax.Array,  # (B,)
    *,
    scale: float = 0.0,
    softcap: float = 0.0,
    v_width: int = 0,
) -> jax.Array:
    """Gather-then-softmax oracle for the paged flash kernel.

    Dense materialization of exactly what the kernel computes: pages are
    gathered through the (clamped) page table into a contiguous logical
    cache, unallocated pages and future positions are masked, and rows
    with zero attendable positions return zeros (matching the kernel's
    all-pages-skipped writeback).
    """
    n_pages, hg, ps, width = k_pages.shape
    b, T, h, dk = q.shape
    P = page_table.shape[1]
    r = width // dk
    hkv = hg * r
    g = h // hkv
    if not scale:
        scale = 1.0 / math.sqrt(dk)
    safe = jnp.minimum(page_table, n_pages - 1)

    def logical(pages):  # (B, P, Hkv/r, ps, r*d) -> (B, P*ps, Hkv, d)
        x = pages[safe]
        x = x.reshape(b, P, hg, ps, r, -1).transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(b, P * ps, hkv, -1).astype(jnp.float32)

    k = logical(k_pages)
    v = logical(v_pages)
    if v_width:
        v = v[..., :v_width]
    qg = q.reshape(b, T, hkv, g, dk).astype(jnp.float32)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = offsets[:, None] + jnp.arange(T)[None, :]  # (b, T)
    kv_pos = jnp.arange(P * ps)[None, :]  # (1, P*ps)
    alloc = jnp.repeat(page_table < n_pages, ps, axis=1)  # (b, P*ps)
    mask = jnp.logical_and(
        kv_pos[:, None] <= q_pos[..., None], alloc[:, None, :]
    )  # (b, T, P*ps)
    mask_b = mask[:, None, None]  # (b,1,1,T,t)
    s = jnp.where(mask_b, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows/queries with nothing attendable: zeros, not a uniform average
    any_valid = jnp.any(mask_b, axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, v)
    return out.reshape(b, T, h, -1).astype(q.dtype)


def ssd_reference(x, dt, A, B, C, chunk):
    """Full chunked-SSD oracle (shared with the model path)."""
    from repro.models.ssm import ssd_chunked

    return ssd_chunked(x, dt, A, B, C, chunk)


def ssd_chunk_reference(x, dA, dt, B, C):
    """Within-chunk term + per-chunk states (the kernel's exact contract).

    x (b,nc,q,h,p); dA/dt (b,nc,q,h); B/C (b,nc,q,n) ->
    (Y_diag (b,nc,q,h,p) fp32, states (b,nc,h,p,n) fp32)
    """
    cum = jnp.cumsum(dA.astype(jnp.float32), axis=2)  # (b,nc,q,h)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,q,q,h)
    q = x.shape[2]
    tri = (jnp.arange(q)[None, :] <= jnp.arange(q)[:, None])[None, None, :, :, None]
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcin,bcjn->bcij", C.astype(jnp.float32), B.astype(jnp.float32))
    M = scores[..., None] * L * dt[:, :, None, :, :]
    y = jnp.einsum("bcijh,bcjhp->bcihp", M, x.astype(jnp.float32))
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dt
    s = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", B.astype(jnp.float32), w, x.astype(jnp.float32))
    return y, s


def rmsnorm_reference(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------- registry
# Op-name -> plain-jnp oracle for every public entry point in
# ``kernels.ops``.  dslint R6 checks this stays total: a kernel without
# a registered oracle (and a parity test exercising it) cannot ship.
# ``paged_verify`` shares ``paged_attention``'s oracle by design — the
# verify primitive IS the chunk-extend case (T = k + 1).
ORACLES = {
    "flash_attention": attention_reference,
    "paged_attention": paged_attention_reference,
    "paged_verify": paged_attention_reference,
    "ssd": ssd_reference,
    "rmsnorm": rmsnorm_reference,
}

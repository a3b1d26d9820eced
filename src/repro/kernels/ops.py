"""Jit-ready wrappers around the Pallas kernels.

On TPU the kernels are compiled by Mosaic.  ``interpret`` defaults to
True on any other backend: interpret mode runs the kernel bodies as
plain JAX ops, and exists for the CPU tests only (it says nothing about
whether Mosaic accepts a kernel, nor about its speed).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.flash_decode import paged_flash_attention_folded
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_chunk_pallas


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention(
    q: jax.Array,  # (B, S, H, hd) — model layout
    k: jax.Array,  # (B, S, H, hd) (kv already repeated to H)
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, s, h, hd = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], hd)  # noqa: E731
    out = flash_attention_bhsd(
        fold(q),
        fold(k),
        fold(v),
        causal=causal,
        window=sliding_window,
        block_q=block_q,
        block_k=block_k,
        interpret=_default_interpret(interpret),
    )
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def paged_attention(
    q: jax.Array,  # (B, T, H, dk) — model layout, RoPE already applied
    k_pages: jax.Array,  # (n_pages, Hkv / r, page_size, r * dk) — head-major pool
    v_pages: jax.Array,  # (n_pages, Hkv / r, page_size, r * dv) (or MLA's shared page)
    page_table: jax.Array,  # (B, P) int32; entries >= n_pages = unallocated
    offsets: jax.Array,  # (B,) absolute position of each row's first token
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    v_width: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:  # (B, T, H, dv)
    """Flash-decode / chunk-extend against a paged KV cache.

    Grouped-query layout: KV heads are NOT repeated; the kernel processes
    one (row, kv head group) pair per grid step with all its query heads
    folded group-major into the query block.  A pool row holds ``r``
    heads side by side (``flash_decode.heads_per_row``; ``r`` is read off
    the pool's width): each query is zero outside its own head's lanes,
    so the kernel's full-width dot scores exactly that head, and each
    head keeps its own lanes of the output.  ``T = 1`` is decode,
    ``T > 1`` the chunk-extend used by fused prefill.  MLA's absorbed
    form is the ``Hkv = 1`` case (``v_width`` selects the latent columns
    of the shared KV page).  Query ``t`` of row ``b`` sits at absolute
    position ``offsets[b] + t``; the engine's allocate-on-write invariant
    makes the causal mask exact (see ``flash_decode``).
    """
    b, T, h, dk = q.shape
    hg = k_pages.shape[1]
    r = k_pages.shape[-1] // dk
    g = h // (hg * r)
    # (B, T, Hg, r, G, dk) -> (B, Hg, r, G*T, dk), then zero-fill to r*dk lanes
    qf = q.reshape(b, T, hg, r, g, dk).transpose(0, 2, 3, 4, 1, 5).reshape(b, hg, r, g * T, dk)
    own = jnp.eye(r, dtype=bool)[:, None, :, None]  # (r, 1, r, 1): head j -> lanes j
    qf = jnp.where(own, qf[:, :, :, :, None, :], 0).reshape(b, hg, r * g * T, r * dk)
    out = paged_flash_attention_folded(
        qf,
        k_pages,
        v_pages,
        page_table,
        offsets,
        tokens_per_row=T,
        scale=scale if scale is not None else 1.0 / math.sqrt(dk),
        softcap=softcap,
        v_width=v_width,
        interpret=_default_interpret(interpret),
    )  # (B, Hg, r*G*T, r*dv)
    dv = out.shape[-1] // r
    out = jnp.diagonal(out.reshape(b, hg, r, g * T, r, dv), axis1=2, axis2=4)
    # (B, Hg, G*T, dv, r) -> (B, T, Hg, r, G, dv)
    out = out.reshape(b, hg, g, T, dv, r).transpose(0, 3, 1, 5, 2, 4)
    return out.reshape(b, T, h, dv)


def paged_verify(
    q: jax.Array,  # (B, k+1, H, dk) — last accepted token + k draft tokens
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    offsets: jax.Array,  # (B,) each row's write frontier (slot.pos)
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    v_width: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Speculative-verify attention: score ``k + 1`` candidate positions
    per row in one kernel launch.

    This IS :func:`paged_attention`'s chunk-extend case (``T = k + 1``)
    — the verify primitive needs nothing the extend kernel does not
    already provide.  Query ``t`` sits at absolute position
    ``offsets[b] + t`` and the kernel's causal mask (``kv_pos <=
    q_pos``) scopes each draft's attention to the accepted history plus
    the drafts before it, which is exactly the conditioning sequential
    decode would have used — so per-position logits, and therefore the
    engine's accept/reject decisions, are byte-identical to ``k + 1``
    single-token decode launches.  Rejected positions need no kernel-
    side cleanup: their K/V lands past the rewound write frontier where
    this same mask excludes it from every later query.

    Kept as a named entry so call sites (and the jnp fallback parity
    test) can say *verify* and mean it; the dispatch is shared."""
    return paged_attention(
        q, k_pages, v_pages, page_table, offsets,
        scale=scale, softcap=softcap, v_width=v_width, interpret=interpret,
    )


def ssd(
    x: jax.Array,  # (b, l, h, p)
    dt: jax.Array,  # (b, l, h)  (post-softplus)
    A: jax.Array,  # (h,) negative
    B: jax.Array,  # (b, l, n)
    C: jax.Array,  # (b, l, n)
    *,
    chunk: int = 128,
    block_h: int = 8,
    initial_state: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD: Pallas within-chunk kernel + jnp inter-chunk glue.

    Same contract as `repro.models.ssm.ssd_chunked`.
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    if l % chunk:
        raise ValueError(f"seq {l} !% chunk {chunk}")
    nc = l // chunk
    if h % block_h:
        block_h = h  # degrade to one head block

    xr = x.reshape(b, nc, chunk, h, p)
    dtr = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    dAr = (dt * A).reshape(b, nc, chunk, h).astype(jnp.float32)
    Br = B.reshape(b, nc, chunk, n)
    Cr = C.reshape(b, nc, chunk, n)

    y_diag, states = ssd_chunk_pallas(
        xr, dAr, dtr, Br, Cr, block_h=block_h, interpret=_default_interpret(interpret)
    )

    # inter-chunk recurrence (cheap, O(nc) scan over (b,h,p,n) states)
    cum = jnp.cumsum(dAr, axis=2)  # (b,nc,q,h)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (b,nc,h)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry

    xs = (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
    final, prev = jax.lax.scan(scan_fn, initial_state.astype(jnp.float32), xs)
    prev = prev.transpose(1, 0, 2, 3, 4)  # (b,nc,h,p,n) state entering each chunk

    decay_out = jnp.exp(cum)  # (b,nc,q,h)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cr.astype(jnp.float32), prev, decay_out)
    y = (y_diag + y_off).reshape(b, l, h, p).astype(x.dtype)
    return y, final


def rmsnorm(
    x: jax.Array,  # (..., d)
    w: jax.Array,  # (d,)
    *,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, shape[-1])
    br = block_rows
    while rows % br:
        br //= 2
    out = rmsnorm_pallas(
        x2, w, eps=eps, block_rows=max(br, 1), interpret=_default_interpret(interpret)
    )
    return out.reshape(shape)

"""Paged flash-decode / chunk-extend attention as a Pallas TPU kernel.

The serving engine's paged KV cache stores tokens in fixed-size *pages*
drawn from a shared pool; a per-slot *page table* maps each row's logical
page index to a physical page id.  This kernel attends a chunk of queries
``q[B, T]`` (``T = 1`` is plain flash-decode; ``T > 1`` is chunk-extend,
used both for fused prefill and as the speculative-decoding *verify*
primitive — ``ops.paged_verify`` scores the last accepted token plus
``k`` drafts per row in one ``T = k + 1`` launch) against that paged
cache **through the page table**,
without ever gathering the pages into a dense ``(B, max_len)`` cache and
without materializing a ``(B, H, T, max_len)`` score tensor.

TPU mapping (same sequential-grid trick as ``flash_attention.py``): the
page table and per-row query offsets are *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``), so each KV ``BlockSpec`` index map
dereferences ``page_table[b, j]`` to DMA the right physical page for
grid step ``(b, kv_head, j)``.  The pool is stored head-major (see the
layout contract below), so one grid step's KV block is one contiguous
``(page_size, width)`` tile whose last two dims are whole array dims, as
Mosaic's tiling asks.  The last grid dimension walks a row's
logical pages in order; the online-softmax state ``(m, l, acc)`` lives
in VMEM scratch and carries across steps.  Pages whose logical positions
lie entirely after the row's last query — including unallocated pages,
whose table entries hold the out-of-bounds sentinel ``>= n_pages`` — are
skipped with ``pl.when`` (their DMA index is clamped in bounds, their
compute never runs).

Masking: query ``i`` of a row (grouped-query fold, see below) sits at
absolute position ``offset[b] + i % T``; cache slot ``o`` of logical
page ``j`` holds position ``j * page_size + o``.  The causal mask
``kv_pos <= q_pos`` is exact because the engine's allocator guarantees
every logical position ``< offset + T`` is backed by an allocated,
written page (allocate-on-write), and everything at or beyond the write
frontier is masked.

Layout contract (GQA without repeating KV): a KV pool is head-major,
``(n_pages, Hkv / r, page_size, r * hd)``: each row holds one position
of ``r = heads_per_row(Hkv, hd)`` heads side by side, so that a row
fills the 128 lanes of a TPU vector register where the head count
allows.  (With ``hd = 64`` unpacked rows would be half padding, and XLA
then stores the pool with pages as its minor dim, which the kernel can
only read after a relayout copy of the whole layer pool.)  This kernel
sees one head group per grid step: queries folded *group-major* to
``(B, Hkv / r, QL, r * hd)`` — ``ops.paged_attention`` zero-fills each
query outside its own head's lanes, so a full-width dot scores exactly
that head, and keeps each head's own lanes of the output.  MLA's
absorbed decode is the ``Hkv = 1`` case with ``hd = kv_lora_rank +
rope_head_dim`` and values read from the first ``v_width`` columns of
the (shared) KV page.

Rows with no attendable positions (e.g. parked slots whose pages were
freed) produce zeros, not NaNs.  The kernel is compiled by Mosaic for
TPU v5e (``tests/test_tpu_compile.py`` compiles it for a described chip)
and validated on CPU in interpret mode against
``ref.paged_attention_reference``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # width of a TPU vector register row


def heads_per_row(n_kv_heads: int, head_dim: int) -> int:
    """KV heads packed side by side in one pool row: as many as fill a
    vector register row, and a divisor of ``n_kv_heads``."""
    return math.gcd(n_kv_heads, max(1, LANES // head_dim))


def gather_pages(pages: jax.Array, page_table: jax.Array, head_dim: int) -> jax.Array:
    """The rows' logical caches ``(B, P * page_size, Hkv, head_dim)`` from a
    head-major pool ``(n_pages, Hkv / r, page_size, r * head_dim)``, through
    the page table (sentinel entries clamped to a real page; callers mask
    them).  Gathers only the rows' own pages."""
    n_pages, hg, ps, width = pages.shape
    r = width // head_dim
    b, P = page_table.shape
    x = pages[jnp.minimum(page_table, n_pages - 1)]  # (B, P, Hkv/r, ps, r*hd)
    x = x.reshape(b, P, hg, ps, r, head_dim).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, P * ps, hg * r, head_dim)


def write_pages(pages: jax.Array, page_ids: jax.Array, slots: jax.Array,
                x: jax.Array) -> jax.Array:
    """Write ``x[..., Hkv, hd]`` at ``(page_ids, slots)`` of a head-major
    pool.  Each update is one whole pool row, the pool's minor dim, so XLA
    keeps the pool in the layout the kernel reads.  Out-of-bounds page ids
    (the unallocated sentinel) are dropped."""
    hg = pages.shape[1]
    rows = x.reshape(x.shape[:-2] + (hg, -1))  # r heads side by side per row
    idx = (page_ids[..., None], jnp.arange(hg), slots[..., None])
    return pages.at[idx].set(rows.astype(pages.dtype))


def _paged_kernel(
    # scalar-prefetch refs
    table_ref,  # (B, P) int32 physical page per (row, logical page)
    off_ref,  # (B,) int32 absolute position of each row's first query
    # blocked operands
    q_ref,  # (1, 1, QL, dk)
    k_ref,  # (1, 1, page_size, dk)
    v_ref,  # (1, 1, page_size, dv_store)
    o_ref,  # (1, 1, QL, dv)
    # scratch
    m_scr,  # (QL, 1) f32
    l_scr,  # (QL, 1) f32
    acc_scr,  # (QL, dv) f32
    *,
    scale: float,
    softcap: float,
    page_size: int,
    tokens_per_row: int,
    n_pages: int,
    pages_per_slot: int,
    v_width: int,
    ql: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # page is attendable iff some logical slot precedes the last query AND
    # the table entry is real (sentinel >= n_pages marks unallocated /
    # freed pages, which the allocator invariant puts past the frontier)
    last_q = off_ref[b] + tokens_per_row - 1
    run = jnp.logical_and(j * page_size <= last_q, table_ref[b, j] < n_pages)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (QL, dk)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, dk)
        v = v_ref[0, 0, :, :v_width].astype(jnp.float32)  # (ps, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (QL, ps)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = off_ref[b] + (
            jax.lax.broadcasted_iota(jnp.int32, (ql, page_size), 0) % tokens_per_row
        )
        kv_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (ql, page_size), 1
        )
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(j == pages_per_slot - 1)
    def _writeback():
        # rows with zero attendable positions (all pages skipped) keep
        # l == 0 and write zeros instead of dividing 0/0 into NaNs
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_flash_attention_folded(
    q: jax.Array,  # (B, Hg, QL, dk) group-major fold, QL a multiple of T
    k_pages: jax.Array,  # (n_pages, Hg, page_size, dk)
    v_pages: jax.Array,  # (n_pages, Hg, page_size, dv_store)
    page_table: jax.Array,  # (B, P) int32; entries >= n_pages = unallocated
    offsets: jax.Array,  # (B,) int32 absolute position of first query token
    *,
    tokens_per_row: int,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    v_width: int = 0,
    interpret: bool = False,
) -> jax.Array:  # (B, Hg, QL, dv)
    b, hkv, ql, dk = q.shape
    n_pages, page_size = k_pages.shape[0], k_pages.shape[2]
    pages_per_slot = page_table.shape[1]
    dv_store = v_pages.shape[-1]
    dv = v_width or dv_store
    if ql % tokens_per_row:
        raise ValueError(f"QL {ql} must fold a whole group count x T {tokens_per_row}")
    if scale is None:
        scale = 1.0 / math.sqrt(dk)

    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        softcap=softcap,
        page_size=page_size,
        tokens_per_row=tokens_per_row,
        n_pages=n_pages,
        pages_per_slot=pages_per_slot,
        v_width=dv,
        ql=ql,
    )

    def page_map(bb, hh, jj, table, off):
        # clamp the sentinel in bounds: skipped pages still DMA *something*
        return (jnp.minimum(table[bb, jj], n_pages - 1), hh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages_per_slot),
        in_specs=[
            pl.BlockSpec((1, 1, ql, dk), lambda bb, hh, jj, table, off: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk), page_map),
            pl.BlockSpec((1, 1, page_size, dv_store), page_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, ql, dv), lambda bb, hh, jj, table, off: (bb, hh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((ql, 1), jnp.float32),
            pltpu.VMEM((ql, 1), jnp.float32),
            pltpu.VMEM((ql, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, ql, dv), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(
        page_table.astype(jnp.int32),
        offsets.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
    )

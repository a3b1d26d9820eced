"""Unified model builder: every assigned architecture family behind one API.

    model = Model(cfg, runtime)
    params = model.init(rng)                       # or jax.eval_shape for dry-run
    loss, metrics = model.loss(params, batch)      # train forward (causal LM)
    cache = model.init_cache(batch, max_len)       # decode cache pytree
    logits, cache = model.decode_step(params, cache, tokens, pos)
    cache, last_logits = model.prefill(params, tokens)

Families: dense (llama/nemotron/qwen/granite), moe (mixtral/deepseek+MLA),
ssm (mamba2), hybrid (zamba2 = mamba2 + shared attention block), audio
(whisper enc-dec, stub frontend), vlm (internvl2, stub frontend).

Layer stacks lower as ``jax.lax.scan`` over stacked parameters so the
512-device dry-run compiles in seconds; ``runtime.unroll_layers`` unrolls
instead (used by the roofline per-layer probe).  The serving programs'
loops also unroll where the stack is float32 (``_unrolls_layers``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.flash_decode import gather_pages, heads_per_row, write_pages
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    apply_rope,
    attn_init,
    attention_scores,
    dense_init,
    embed_init,
    gqa_attention,
    mlp_init,
    norm_init,
    qkv_project,
    repeat_kv,
)
from repro.sharding.logical import shard

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelRuntime:
    """Execution knobs, orthogonal to the architecture."""

    dtype: Any = jnp.float32
    attn_impl: str = "auto"  # auto | direct | chunked | kernel
    attn_chunk: int = 1024
    # paged-cache attention: "kernel" = Pallas flash-decode/chunk-extend
    # through the page table (compiled by Mosaic on TPU; interpret mode,
    # elsewhere, is for the CPU tests only), "jnp" = gather the pages and
    # reuse the dense attention path (the CPU-fast reference)
    paged_attn_impl: str = "auto"  # auto | kernel | jnp
    moe_strategy: str = "capacity"
    use_ssd_kernel: bool = False
    remat: bool = False
    # 0 = checkpoint every layer; k>1 = checkpoint every k layers
    # (sqrt-remat: residuals = L/k boundaries + k inner during recompute)
    remat_segment: int = 0
    unroll_layers: bool = False
    logit_dtype: Any = jnp.float32

    def resolve_attn(self, seq_len: int) -> str:
        if self.attn_impl != "auto":
            return self.attn_impl
        return "chunked" if seq_len > 4096 else "direct"

    def resolve_paged_attn(self) -> str:
        if self.paged_attn_impl != "auto":
            return self.paged_attn_impl
        return "kernel" if jax.default_backend() == "tpu" else "jnp"


# ============================================================ chunked attention
def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    sliding_window: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention over kv chunks (pure-jnp flash pattern).

    q,k,v: (B, S, H, hd) with equal q/kv length (prefill/train).  Memory is
    O(B·H·chunk²) instead of O(B·H·S²).  FLOPs equal the full rectangle
    (masked) — same count XLA produces for direct attention; the Pallas
    kernel is the path that skips masked tiles on TPU.
    """
    b, s, h, hd = q.shape
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    nq = s // chunk
    scale = 1.0 / math.sqrt(hd)
    qs = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 3, 2, 4)  # (nq,b,h,c,hd)
    ks = k.reshape(b, nq, chunk, h, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(b, nq, chunk, h, hd).transpose(1, 0, 3, 2, 4)

    def q_step(_, qi_idx):
        qi, iq = qi_idx
        q_pos = iq * chunk + jnp.arange(chunk)

        def kv_step(carry, kj_idx):
            m, l, acc = carry
            kj, vj, jk = kj_idx
            k_pos = jk * chunk + jnp.arange(chunk)
            logits = (
                jnp.einsum("bhqd,bhkd->bhqk", qi, kj, preferred_element_type=jnp.float32)
                * scale
            )
            mask = jnp.ones((chunk, chunk), bool)
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
            if sliding_window > 0:
                mask = jnp.logical_and(mask, k_pos[None, :] > q_pos[:, None] - sliding_window)
            logits = jnp.where(mask[None, None], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(qi.dtype), vj, preferred_element_type=jnp.float32
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, jnp.arange(nq)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))  # (nq,b,h,c,hd)
    return outs.transpose(1, 0, 3, 2, 4).reshape(b, s, h, hd)


# ================================================================= blocks
def _attn_forward(
    p: Params,
    x: jax.Array,
    cfg: ArchConfig,
    rt: ModelRuntime,
    *,
    positions: jax.Array,
    causal: bool = True,
    n_heads: Optional[int] = None,
    n_kv: Optional[int] = None,
    head_dim: Optional[int] = None,
    use_rope: bool = True,
) -> jax.Array:
    h = n_heads or cfg.n_heads
    hkv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    q, k, v = qkv_project(p, x, h, hkv, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    kr, vr = repeat_kv(k, h), repeat_kv(v, h)
    impl = rt.resolve_attn(x.shape[1])
    if impl == "kernel":
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, kr, vr, causal=causal, sliding_window=cfg.sliding_window)
    elif impl == "chunked":
        out = chunked_attention(
            q, kr, vr, causal=causal, sliding_window=cfg.sliding_window,
            chunk=_pick_chunk(x.shape[1], rt.attn_chunk),
        )
    else:
        out = attention_scores(
            q, kr, vr, causal=causal, sliding_window=cfg.sliding_window,
            q_positions=positions, kv_positions=positions,
            logit_softcap=cfg.attn_logit_softcap,
        )
    out = out.reshape(x.shape[0], x.shape[1], h * hd)
    return shard(out @ p["wo"], "batch", "residual_seq", "embed")


def _attn_extend(
    p: Params,
    x: jax.Array,  # (b,T,d)
    cache_k: jax.Array,  # (b,t,hkv,hd)
    cache_v: jax.Array,
    positions: jax.Array,  # (b,T) absolute positions of the chunk tokens
    cfg: ArchConfig,
    *,
    n_heads: Optional[int] = None,
    n_kv: Optional[int] = None,
    head_dim: Optional[int] = None,
    use_rope: bool = True,
    valid: Optional[jax.Array] = None,  # (b,T) real (non-padded) tokens
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill attention: append T tokens per row to the KV cache
    in one shot and attend each query against the full cache.

    Right-padded tokens (``valid`` False, including whole rows that are
    not ingesting this dispatch) have their writes redirected out of
    bounds — JAX drops out-of-bounds scatter updates — so the cache only
    ever receives real tokens.  Causality then falls out of the
    ``kv_pos <= q_pos`` position mask.  A rolling sliding-window cache
    (t < max position) additionally mislabels wrapped slots via the
    chunk-end reconstruction below, so callers gate those out.
    """
    h = n_heads or cfg.n_heads
    hkv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    b, T, _ = x.shape
    q, k, v = qkv_project(p, x, h, hkv, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    t = cache_k.shape[1]
    write = jnp.mod(positions, t)  # (b,T)
    if valid is not None:
        write = jnp.where(valid, write, t)  # out-of-bounds -> update dropped
    rows = jnp.arange(b)[:, None]
    cache_k = cache_k.at[rows, write].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[rows, write].set(v.astype(cache_v.dtype))
    cache_k = shard(cache_k, "cache_batch", "kv_seq", "kv_heads", "head_dim")
    cache_v = shard(cache_v, "cache_batch", "kv_seq", "kv_heads", "head_dim")
    # absolute position held by each cache slot, referenced to the chunk end
    last = positions[:, -1]
    slots = jnp.arange(t)
    kv_pos = last[:, None] - jnp.mod(last[:, None] - slots[None, :], t)  # (b,t)
    kv_mask = kv_pos >= 0
    out = gqa_attention(
        q, cache_k, cache_v,
        q_positions=positions, kv_positions=kv_pos,
        sliding_window=cfg.sliding_window, kv_mask=kv_mask,
        logit_softcap=cfg.attn_logit_softcap,
    )
    out = out.reshape(b, T, h * hd)
    out = shard(out, "act_batch", "seq", "act_heads")
    return shard(out @ p["wo"], "batch", "seq", "embed"), cache_k, cache_v


def _attn_decode(
    p: Params,
    x: jax.Array,  # (b,1,d)
    cache_k: jax.Array,  # (b,T,hkv,hd)
    cache_v: jax.Array,
    pos: jax.Array,
    cfg: ArchConfig,
    *,
    n_heads: Optional[int] = None,
    n_kv: Optional[int] = None,
    head_dim: Optional[int] = None,
    use_rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    h = n_heads or cfg.n_heads
    hkv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    uniform_pos = pos.ndim == 0  # all rows at the same depth (serving cells)
    pos_v = jnp.broadcast_to(pos, (b,))
    positions = pos_v[:, None]
    q, k, v = qkv_project(p, x, h, hkv, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # rolling cache: slot = pos % T.  For full caches (T > pos) this is the
    # identity; for sliding-window caches (T = window+1 padded) it wraps.
    t = cache_k.shape[1]
    if uniform_pos:
        # scalar-position write: dynamic-update-slice partitions cleanly
        # over a kv_seq-sharded cache; the per-row scatter below makes
        # GSPMD all-gather cache shards (measured 79 GB/step for
        # nemotron decode_32k — EXPERIMENTS §Perf iteration 3.1)
        write0 = jnp.mod(pos, t)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, write0, 0, 0)
        )
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, write0, 0, 0)
        )
    else:
        write = jnp.mod(pos_v, t)  # (b,) continuous batching: ragged rows
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, write].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, write].set(v[:, 0].astype(cache_v.dtype))
    cache_k = shard(cache_k, "cache_batch", "kv_seq", "kv_heads", "head_dim")
    cache_v = shard(cache_v, "cache_batch", "kv_seq", "kv_heads", "head_dim")
    # absolute position held by each slot (most recent write <= its row pos)
    slots = jnp.arange(t)
    kv_pos = pos_v[:, None] - jnp.mod(pos_v[:, None] - slots[None, :], t)  # (b,t)
    kv_mask = kv_pos >= 0
    # grouped attention: never materialize repeated KV heads against a
    # long cache (12x HBM blow-up for nemotron's 96/8 grouping)
    out = gqa_attention(
        q, cache_k, cache_v,
        q_positions=positions, kv_positions=kv_pos,
        sliding_window=cfg.sliding_window, kv_mask=kv_mask,
        logit_softcap=cfg.attn_logit_softcap,
    )
    out = out.reshape(b, 1, h * hd)
    # wo is row-parallel over 'model': constrain the contraction input so
    # the decode matmul is partial + psum rather than a weight gather
    out = shard(out, "act_batch", "seq", "act_heads")
    return shard(out @ p["wo"], "batch", "seq", "embed"), cache_k, cache_v


# ------------------------------------------------------- paged KV attention
def _paged_gqa(
    q: jax.Array,  # (b, T, H, hd)
    k_pages: jax.Array,  # (n_pages, Hkv/r, ps, r*hd) — head-major
    v_pages: jax.Array,
    page_table: jax.Array,  # (b, P) int32; >= n_pages = unallocated
    q_positions: jax.Array,  # (b, T)
    cfg: ArchConfig,
    rt: ModelRuntime,
) -> jax.Array:
    """Attention against the paged cache: Pallas flash kernel through the
    page table, or the jnp fallback (gather pages -> dense ``gqa_attention``,
    byte-identical math to the dense cache path so paged and dense engines
    stay token-parity).

    Page-table entries are pure indirection: several rows may alias the
    same physical page (shared-prefix stitching), which is transparent to
    both read paths.  The serving engine guarantees writes never target an
    aliased page (copy-on-write privatizes it first), so reads here always
    see immutable shared content."""
    if rt.resolve_paged_attn() == "kernel":
        from repro.kernels import ops as kops

        return kops.paged_attention(
            q, k_pages, v_pages, page_table, q_positions[:, 0],
            softcap=cfg.attn_logit_softcap,
        )
    n_pages, ps = k_pages.shape[0], k_pages.shape[2]
    hd = q.shape[-1]
    b = q.shape[0]
    P = page_table.shape[1]
    kf = gather_pages(k_pages, page_table, hd)
    vf = gather_pages(v_pages, page_table, hd)
    kv_pos = jnp.broadcast_to(jnp.arange(P * ps, dtype=jnp.int32)[None], (b, P * ps))
    # unallocated pages gather garbage from the clamped physical page; the
    # allocator keeps them past every query's frontier, but masking them
    # also keeps padded prefill rows finite
    kv_mask = jnp.repeat(page_table < n_pages, ps, axis=1)
    return gqa_attention(
        q, kf, vf,
        q_positions=q_positions, kv_positions=kv_pos, kv_mask=kv_mask,
        logit_softcap=cfg.attn_logit_softcap,
    )


def _attn_decode_paged(
    p: Params,
    x: jax.Array,  # (b,1,d)
    k_pages: jax.Array,  # (n_pages, hkv/r, ps, r*hd) — this layer's page pool
    v_pages: jax.Array,
    page_table: jax.Array,  # (b, P)
    pos: jax.Array,  # (b,) per-row cache positions
    cfg: ArchConfig,
    rt: ModelRuntime,
    *,
    use_rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token per row against the paged cache.

    The new K/V lands at physical slot ``(page_table[b, pos//ps], pos%ps)``;
    rows whose table entry is the out-of-bounds sentinel (parked slots —
    their pages were freed) have the scatter dropped by JAX, so a dead row
    can never write into a page now owned by someone else."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b = x.shape[0]
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos_v[:, None]
    q, k, v = qkv_project(p, x, h, hkv, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    ps = k_pages.shape[2]
    P = page_table.shape[1]
    rows = jnp.arange(b)
    phys = page_table[rows, jnp.minimum(pos_v // ps, P - 1)]  # (b,) OOB = dropped
    k_pages = write_pages(k_pages, phys, pos_v % ps, k[:, 0])
    v_pages = write_pages(v_pages, phys, pos_v % ps, v[:, 0])
    out = _paged_gqa(q, k_pages, v_pages, page_table, positions, cfg, rt)
    out = out.reshape(b, 1, h * hd)
    out = shard(out, "act_batch", "seq", "act_heads")
    return shard(out @ p["wo"], "batch", "seq", "embed"), k_pages, v_pages


def _attn_extend_paged(
    p: Params,
    x: jax.Array,  # (b,T,d)
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,  # (b,T) absolute positions of the chunk tokens
    cfg: ArchConfig,
    rt: ModelRuntime,
    *,
    use_rope: bool = True,
    valid: Optional[jax.Array] = None,  # (b,T) real (non-padded) tokens
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunk-extend against the paged cache: append T tokens per row and
    attend each query through the page table.  Padded tokens write to the
    out-of-bounds page sentinel (dropped); their garbage outputs are
    discarded by the caller's last-valid-token gather.

    ``positions`` may start at any page-aligned (or, after a shared-prefix
    full hit, mid-page copy-on-write) offset: RoPE uses the absolute
    positions and earlier pages — possibly written by a *different* row
    that shares the prefix — are visible through the table, so prefill can
    resume mid-sequence from the first divergent chunk."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, T, _ = x.shape
    q, k, v = qkv_project(p, x, h, hkv, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    n_pages, ps = k_pages.shape[0], k_pages.shape[2]
    P = page_table.shape[1]
    rows = jnp.arange(b)[:, None]
    wp = page_table[rows, jnp.minimum(positions // ps, P - 1)]  # (b,T)
    if valid is not None:
        wp = jnp.where(valid, wp, n_pages)  # out of bounds -> dropped
    k_pages = write_pages(k_pages, wp, positions % ps, k)
    v_pages = write_pages(v_pages, wp, positions % ps, v)
    out = _paged_gqa(q, k_pages, v_pages, page_table, positions, cfg, rt)
    out = out.reshape(b, T, h * hd)
    out = shard(out, "act_batch", "seq", "act_heads")
    return shard(out @ p["wo"], "batch", "seq", "embed"), k_pages, v_pages


# -------------------------------------------------- per-family layer init/apply
def _layer_init(key, cfg: ArchConfig, dtype, dense_layer: bool) -> Params:
    """One decoder layer.  ``dense_layer``: MoE archs keep the first
    ``first_k_dense`` layers dense."""
    depth_scale = 1.0 / math.sqrt(2 * cfg.n_layers)
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": norm_init(cfg.d_model, cfg.norm, dtype)}
    if cfg.family == "ssm":
        p["mixer"] = ssm_mod.mamba2_init(ks[0], cfg, dtype, depth_scale)
        return p
    if cfg.family == "hybrid":
        p["mixer"] = ssm_mod.mamba2_init(ks[0], cfg, dtype, depth_scale)
        return p
    # attention families
    if cfg.use_mla:
        p["attn"] = mla_mod.mla_init(ks[0], cfg, dtype, depth_scale)
    else:
        p["attn"] = attn_init(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            dtype, depth_scale, qkv_bias=cfg.qkv_bias,
        )
    p["ln2"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if cfg.family == "moe" and not dense_layer:
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype, depth_scale)
    else:
        f = cfg.d_ff if cfg.d_ff else cfg.moe_d_ff
        p["mlp"] = mlp_init(ks[1], cfg.d_model, f, cfg.activation, dtype, depth_scale)
    return p


def _layer_apply(
    p: Params, x: jax.Array, cfg: ArchConfig, rt: ModelRuntime, positions: jax.Array
) -> jax.Array:
    h = x + _mixer_apply(p, x, cfg, rt, positions)
    if "ln2" not in p:
        return h
    hn = apply_norm(p["ln2"], h, cfg.norm, cfg.norm_eps)
    if "moe" in p:
        return h + moe_mod.apply_moe(p["moe"], hn, cfg, rt.moe_strategy)
    return h + apply_mlp(p["mlp"], hn, cfg.activation)


def _mixer_apply(p, x, cfg, rt, positions):
    xn = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    if "mixer" in p:
        return ssm_mod.apply_mamba2(p["mixer"], xn, cfg, use_kernel=rt.use_ssd_kernel)
    if cfg.use_mla:
        return mla_mod.apply_mla(p["attn"], xn, cfg, positions=positions)
    # archs with learned absolute positions (whisper) do not use RoPE
    return _attn_forward(
        p["attn"], xn, cfg, rt, positions=positions,
        use_rope=not cfg.max_position_embeddings,
    )


# ------------------------------------------------------- zamba2 shared block
def _shared_block_init(key, cfg: ArchConfig, dtype) -> Params:
    """Shared transformer block at width 2·d_model (zamba2)."""
    d2 = 2 * cfg.d_model
    hd = d2 // cfg.n_heads
    depth_scale = 1.0 / math.sqrt(2 * cfg.n_layers)
    ks = jax.random.split(key, 5)
    n_inv = cfg.n_layers // cfg.shared_attn_every
    p: Params = {
        "ln1": norm_init(d2, cfg.norm, dtype),
        "attn": attn_init(ks[0], d2, cfg.n_heads, cfg.n_kv_heads, hd, dtype, depth_scale),
        "ln2": norm_init(d2, cfg.norm, dtype),
        "mlp": mlp_init(ks[1], d2, cfg.d_ff, cfg.activation, dtype, depth_scale),
        "down": dense_init(ks[2], d2, cfg.d_model, dtype, scale=depth_scale),
    }
    if cfg.shared_attn_lora_rank:
        r = cfg.shared_attn_lora_rank
        p["lora_a"] = (
            jax.random.normal(ks[3], (n_inv, d2, r), jnp.float32) * (1.0 / math.sqrt(d2))
        ).astype(dtype)
        p["lora_b"] = jnp.zeros((n_inv, r, cfg.n_heads * hd), dtype)
    return p


def _shared_block_apply(
    p: Params,
    h: jax.Array,
    x0: jax.Array,
    inv: int,
    cfg: ArchConfig,
    rt: ModelRuntime,
    positions: jax.Array,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    pos: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
):
    """Returns delta to add to h (and updated kv cache when decoding)."""
    d2h = jnp.concatenate([h, x0], axis=-1)
    xn = apply_norm(p["ln1"], d2h, cfg.norm, cfg.norm_eps)
    attn_p = dict(p["attn"])
    if "lora_a" in p:
        la, lb = p["lora_a"][inv], p["lora_b"][inv]
        attn_p = dict(attn_p)
        attn_p["wq"] = attn_p["wq"] + (la @ lb).astype(attn_p["wq"].dtype)
    hd = 2 * cfg.d_model // cfg.n_heads
    if cache is None:
        a = _attn_forward(
            attn_p, xn, cfg, rt, positions=positions,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
        )
        new_cache = None
    elif pos is not None:
        a, ck, cv = _attn_decode(
            attn_p, xn, cache[0], cache[1], pos, cfg,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
        )
        new_cache = (ck, cv)
    else:
        # chunked prefill: T tokens per row against the shared-block cache
        a, ck, cv = _attn_extend(
            attn_p, xn, cache[0], cache[1], positions, cfg,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd, valid=valid,
        )
        new_cache = (ck, cv)
    y = d2h + a
    yn = apply_norm(p["ln2"], y, cfg.norm, cfg.norm_eps)
    y = y + apply_mlp(p["mlp"], yn, cfg.activation)
    return y @ p["down"], new_cache


# ================================================================== Model
class Model:
    def __init__(self, cfg: ArchConfig, runtime: Optional[ModelRuntime] = None):
        self.cfg = cfg
        self.rt = runtime or ModelRuntime()

    # ------------------------------------------------------------- init
    def init(self, rng: jax.Array) -> Params:
        cfg, dtype = self.cfg, self.rt.dtype
        keys = jax.random.split(rng, 8)
        params: Params = {"embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype)}

        n_dense = cfg.first_k_dense if cfg.family == "moe" else 0
        layer_keys = jax.random.split(keys[1], cfg.n_layers)
        if n_dense:
            # heterogeneous stack: leading dense layers kept separate
            params["dense_layers"] = _stack_init(
                layer_keys[:n_dense], lambda k: _layer_init(k, cfg, dtype, dense_layer=True)
            )
            params["layers"] = _stack_init(
                layer_keys[n_dense:], lambda k: _layer_init(k, cfg, dtype, dense_layer=False)
            )
        else:
            params["layers"] = _stack_init(
                layer_keys, lambda k: _layer_init(k, cfg, dtype, dense_layer=False)
            )

        if cfg.family == "hybrid":
            params["shared"] = _shared_block_init(keys[2], cfg, dtype)
        if cfg.is_encoder_decoder:
            enc_keys = jax.random.split(keys[3], cfg.n_encoder_layers)
            params["encoder"] = {
                "layers": _stack_init(enc_keys, lambda k: _layer_init(k, cfg, dtype, True)),
                "ln_f": norm_init(cfg.d_model, cfg.norm, dtype),
                "pos": embed_init(keys[4], cfg.encoder_seq, cfg.d_model, dtype),
            }
            params["cross"] = _stack_init(
                jax.random.split(keys[5], cfg.n_layers),
                lambda k: {
                    "ln": norm_init(cfg.d_model, cfg.norm, dtype),
                    "attn": attn_init(
                        k, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                        dtype, 1.0 / math.sqrt(2 * cfg.n_layers),
                    ),
                },
            )
        if cfg.max_position_embeddings:
            params["pos"] = embed_init(keys[6], cfg.max_position_embeddings, cfg.d_model, dtype)
        params["ln_f"] = norm_init(cfg.d_model, cfg.norm, dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[7], cfg.d_model, cfg.padded_vocab, dtype)
        return params


    # ---------------------------------------------------------------- scan
    def _maybe_scan(self, body_fn, carry, xs):
        """``lax.scan(body_fn, carry, xs)`` over stacked layers, or the same
        loop unrolled: when :func:`_unrolls_layers` says so for ``xs``, or
        always with ``runtime.unroll_layers`` (roofline probes need
        entry-visible costs).

        Unrolled, each layer's outputs are written at its index into a
        stacked buffer, as the scan writes its own, so a cache pool is
        updated slab by slab and never rebuilt by a stack."""
        if not (self.rt.unroll_layers or _unrolls_layers(xs)):
            return jax.lax.scan(body_fn, carry, xs)
        n = _stack_len(xs)
        ys = None
        for i in range(n):
            carry, y = body_fn(carry, jax.tree.map(lambda a: a[i], xs))
            if i == 0:
                ys = jax.tree.map(lambda a: jax.lax.empty((n,) + a.shape, a.dtype), y)
            ys = jax.tree.map(
                lambda buf, a: jax.lax.dynamic_update_index_in_dim(buf, a, i, 0), ys, y)
        return carry, ys

    # ----------------------------------------------------------- embeddings
    def _embed(self, params: Params, tokens: jax.Array, offset: int = 0) -> jax.Array:
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.max_position_embeddings:
            s = tokens.shape[1]
            x = x + jax.lax.dynamic_slice_in_dim(params["pos"], offset, s, axis=0)[None]
        return shard(x.astype(self.rt.dtype), "batch", "seq", "embed")

    def _logits(self, params: Params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = apply_norm(params["ln_f"], h, cfg.norm, cfg.norm_eps)
        h = shard(h, "act_batch", "seq", "act_embed")
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (h @ w).astype(self.rt.logit_dtype)
        return shard(logits, "batch", "seq", "vocab")

    # ----------------------------------------------------------- backbone
    def _run_layers(
        self, params: Params, x: jax.Array, positions: jax.Array
    ) -> jax.Array:
        cfg, rt = self.cfg, self.rt

        def body_fn(h, layer_p):
            out = _layer_apply(layer_p, h, cfg, rt, positions)
            return out, None

        if rt.remat:
            body_fn = jax.checkpoint(body_fn)  # noqa: F821 - jax.checkpoint is jax.remat

        if cfg.family == "hybrid":
            return self._run_hybrid(params, x, positions)

        h = x
        for group in ("dense_layers", "layers"):
            if group not in params:
                continue
            stacked = params[group]
            n = _stack_len(stacked)
            seg = rt.remat_segment
            if rt.unroll_layers:
                for i in range(n):
                    h, _ = body_fn(h, jax.tree.map(lambda a: a[i], stacked))
            elif rt.remat and seg > 1 and n % seg == 0:
                # segmented (sqrt) remat: only segment-boundary activations
                # persist; per-layer residuals materialize transiently while
                # a segment is being recomputed for its backward
                inner_body = lambda hh, lp: (_layer_apply(lp, hh, cfg, rt, positions), None)  # noqa: E731

                def seg_body(hh, seg_params):
                    hh, _ = jax.lax.scan(inner_body, hh, seg_params)
                    return hh, None

                seg_body = jax.checkpoint(seg_body)
                stacked_seg = jax.tree.map(
                    lambda a: a.reshape((n // seg, seg) + a.shape[1:]), stacked
                )
                h, _ = jax.lax.scan(seg_body, h, stacked_seg)
            else:
                h, _ = jax.lax.scan(body_fn, h, stacked)
        return h

    def _run_hybrid(self, params: Params, x: jax.Array, positions: jax.Array) -> jax.Array:
        """zamba2: segments of SSM layers with a shared attn block between."""
        cfg, rt = self.cfg, self.rt
        every = cfg.shared_attn_every
        n_inv = cfg.n_layers // every

        def body_fn(h, layer_p):
            return _layer_apply(layer_p, h, cfg, rt, positions), None

        if rt.remat:
            body_fn = jax.checkpoint(body_fn)
        h, x0 = x, x
        for inv in range(n_inv):
            delta, _ = _shared_block_apply(params["shared"], h, x0, inv, cfg, rt, positions)
            h = h + delta
            seg = jax.tree.map(lambda a: a[inv * every : (inv + 1) * every], params["layers"])
            if rt.unroll_layers:
                for i in range(every):
                    h, _ = body_fn(h, jax.tree.map(lambda a: a[i], seg))
            else:
                h, _ = jax.lax.scan(body_fn, h, seg)
        # trailing layers not covered by full segments
        rem = cfg.n_layers - n_inv * every
        if rem:
            seg = jax.tree.map(lambda a: a[n_inv * every :], params["layers"])
            h, _ = jax.lax.scan(body_fn, h, seg)
        return h

    # ------------------------------------------------------------ encoder
    def _encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg, rt = self.cfg, self.rt
        x = frames.astype(rt.dtype) + params["encoder"]["pos"][None, : frames.shape[1]]
        positions = jnp.broadcast_to(jnp.arange(frames.shape[1])[None], frames.shape[:2])

        def body_fn(h, layer_p):
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a = _attn_forward(
                layer_p["attn"], hn, cfg, rt, positions=positions, causal=False, use_rope=False
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            return h + apply_mlp(layer_p["mlp"], hn, cfg.activation), None

        h, _ = self._maybe_scan(body_fn, x, params["encoder"]["layers"])
        return apply_norm(params["encoder"]["ln_f"], h, cfg.norm, cfg.norm_eps)

    def _run_decoder_with_cross(
        self, params: Params, x: jax.Array, enc: jax.Array, positions: jax.Array
    ) -> jax.Array:
        cfg, rt = self.cfg, self.rt
        enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None], enc.shape[:2])

        def body_fn(h, ps):
            layer_p, cross_p = ps
            h = h + _mixer_apply(layer_p, h, cfg, rt, positions)
            # cross attention
            hn = apply_norm(cross_p["ln"], h, cfg.norm, cfg.norm_eps)
            q, _, _ = qkv_project(
                cross_p["attn"], hn, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
            )
            _, k, v = qkv_project(
                cross_p["attn"], enc, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
            )
            a = attention_scores(
                q, repeat_kv(k, cfg.n_heads), repeat_kv(v, cfg.n_heads),
                causal=False, q_positions=positions, kv_positions=enc_pos,
            )
            a = a.reshape(h.shape[0], h.shape[1], -1) @ cross_p["attn"]["wo"]
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            return h + apply_mlp(layer_p["mlp"], hn, cfg.activation), None

        h, _ = self._maybe_scan(body_fn, x, (params["layers"], params["cross"]))
        return h

    # ------------------------------------------------------------- forward
    def forward(
        self,
        params: Params,
        tokens: jax.Array,
        *,
        frames: Optional[jax.Array] = None,
        patches: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Full-sequence causal forward -> logits (B, S_total, Vp)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.n_vision_tokens and patches is not None:
            x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        if cfg.is_encoder_decoder:
            enc = self._encode(params, frames)
            h = self._run_decoder_with_cross(params, x, enc, positions)
        else:
            h = self._run_layers(params, x, positions)
        return self._logits(params, h)

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
        """Next-token cross-entropy.  batch: tokens, labels, (frames|patches)."""
        cfg = self.cfg
        logits = self.forward(
            params, batch["tokens"], frames=batch.get("frames"), patches=batch.get("patches")
        )
        labels = batch["labels"]
        if cfg.n_vision_tokens and "patches" in batch:
            logits = logits[:, cfg.n_vision_tokens :]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss, {"loss": loss, "tokens": jnp.sum(mask)}

    # ------------------------------------------------------------ decode
    @property
    def supports_paged_cache(self) -> bool:
        """Can this architecture's decode cache be paged?

        SSM/hybrid state is O(1) per slot (nothing to page), encoder-
        decoder carries a static cross cache, and rolling sliding-window
        caches already bound memory by the window (and their slot->position
        reconstruction is incompatible with page indirection)."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid") or cfg.is_encoder_decoder:
            return False
        return cfg.sliding_window == 0

    def init_cache(
        self,
        batch: int,
        max_len: int,
        dtype=None,
        *,
        paged: bool = False,
        page_size: int = 16,
        n_pages: Optional[int] = None,
    ) -> Params:
        """Decode cache pytree.

        ``paged=True`` replaces the per-slot dense ``max_len`` reservation
        with a shared pool of ``n_pages`` fixed-size pages plus a per-slot
        ``page_table`` (``(batch, max_len/page_size)`` int32).  K/V pools
        are head-major, ``(L, n_pages, Hkv/r, page_size, r*hd)`` with
        ``r = heads_per_row(Hkv, hd)`` heads side by side in a row — the
        layout the Pallas paged kernel reads (``kernels/flash_decode.py``);
        the MLA latent pool is ``(L, n_pages, page_size, width)``.  Table
        entries hold the out-of-bounds sentinel ``n_pages`` until the
        owner (the serving engine's allocator) backs them with a physical
        page; cache memory then scales with tokens actually resident
        instead of ``batch * max_len`` worst case.
        """
        cfg = self.cfg
        dtype = dtype or self.rt.dtype
        L = cfg.n_layers
        hd = cfg.resolved_head_dim
        cache: Params = {}
        if paged:
            if not self.supports_paged_cache:
                raise ValueError(
                    f"paged cache unsupported for arch {cfg.name!r} "
                    "(ssm/hybrid state, enc-dec cross cache, or rolling "
                    "sliding-window cache)"
                )
            ps = int(page_size)
            pages_per_slot = -(-max_len // ps)
            pool = batch * pages_per_slot if n_pages is None else int(n_pages)
            cache["page_table"] = jnp.full((batch, pages_per_slot), pool, jnp.int32)
            if cfg.use_mla:
                width = cfg.kv_lora_rank + cfg.rope_head_dim
                cache["kv_pages"] = jnp.zeros((L, pool, ps, width), dtype)
            else:
                r = heads_per_row(cfg.n_kv_heads, hd)
                shape = (L, pool, cfg.n_kv_heads // r, ps, r * hd)
                cache["k_pages"] = jnp.zeros(shape, dtype)
                cache["v_pages"] = jnp.zeros(shape, dtype)
            return cache
        if cfg.family == "ssm":
            cache["state"] = _stack_states(ssm_mod.mamba2_decode_state(cfg, batch, dtype), L)
        elif cfg.family == "hybrid":
            cache["state"] = _stack_states(ssm_mod.mamba2_decode_state(cfg, batch, dtype), L)
            n_inv = L // cfg.shared_attn_every
            hd2 = 2 * cfg.d_model // cfg.n_heads
            cache["shared_k"] = jnp.zeros((n_inv, batch, max_len, cfg.n_kv_heads, hd2), dtype)
            cache["shared_v"] = jnp.zeros((n_inv, batch, max_len, cfg.n_kv_heads, hd2), dtype)
        elif cfg.use_mla:
            cache["c_kv"] = jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype)
            cache["k_rope"] = jnp.zeros((L, batch, max_len, cfg.rope_head_dim), dtype)
        else:
            window = cfg.sliding_window or 0
            t = max_len if window == 0 else min(max_len, _pad128(window + 1))
            cache["k"] = jnp.zeros((L, batch, t, cfg.n_kv_heads, hd), dtype)
            cache["v"] = jnp.zeros((L, batch, t, cfg.n_kv_heads, hd), dtype)
            if cfg.is_encoder_decoder:
                cache["cross_k"] = jnp.zeros(
                    (L, batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype
                )
                cache["cross_v"] = jnp.zeros(
                    (L, batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype
                )
        return cache

    def decode_step(
        self, params: Params, cache: Params, tokens: jax.Array, pos: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """One token for every sequence in the batch.

        tokens: (B, 1); pos: scalar or (B,) per-row cache positions (rows
        may be at different depths — continuous batching)."""
        cfg, rt = self.cfg, self.rt
        pos = jnp.asarray(pos, jnp.int32)  # scalar (uniform) or (B,) per-row
        x = self._embed_decode(params, tokens, pos)
        if "page_table" in cache:
            if cfg.use_mla:
                return self._decode_mla_paged(params, cache, x, pos)
            return self._decode_attn_paged(params, cache, x, pos)
        if cfg.family in ("ssm", "hybrid"):
            return self._decode_ssm(params, cache, x, pos)
        if cfg.use_mla:
            return self._decode_mla(params, cache, x, pos)
        return self._decode_attn(params, cache, x, pos)

    def _embed_decode(self, params, tokens, pos):
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.max_position_embeddings:
            pos_v = jnp.broadcast_to(pos, (tokens.shape[0],))
            x = x + params["pos"][pos_v][:, None, :]
        return x.astype(self.rt.dtype)

    def _decode_attn(self, params, cache, x, pos):
        cfg, rt = self.cfg, self.rt

        def body_fn(h, xs):
            layer_p, ck, cv, extra = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, ck, cv = _attn_decode(
                layer_p["attn"], hn, ck, cv, pos, cfg,
                use_rope=not cfg.max_position_embeddings,
            )
            h = h + a
            if cfg.is_encoder_decoder:
                cross_p, xk, xv = extra
                hn = apply_norm(cross_p["ln"], h, cfg.norm, cfg.norm_eps)
                q, _, _ = qkv_project(
                    cross_p["attn"], hn, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
                )
                enc_t = xk.shape[1]
                a = attention_scores(
                    q, repeat_kv(xk, cfg.n_heads), repeat_kv(xv, cfg.n_heads), causal=False
                )
                a = a.reshape(h.shape[0], 1, -1) @ cross_p["attn"]["wo"]
                h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            if "moe" in layer_p:
                h = h + moe_mod.apply_moe(layer_p["moe"], hn, cfg, rt.moe_strategy)
            else:
                h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (ck, cv)

        h = x
        new_cache = dict(cache)
        groups = [g for g in ("dense_layers", "layers") if g in params]
        k_parts, v_parts = [], []
        offset = 0
        for group in groups:
            stacked = params[group]
            n = _stack_len(stacked)
            ck = cache["k"][offset : offset + n]
            cv = cache["v"][offset : offset + n]
            if cfg.is_encoder_decoder:
                extra = (params["cross"], cache["cross_k"], cache["cross_v"])
            else:
                extra = (None,) if False else _none_like(n)
            xs = (stacked, ck, cv, extra)
            h, (nk, nv) = self._maybe_scan(body_fn, h, xs)
            k_parts.append(nk)
            v_parts.append(nv)
            offset += n
        new_cache["k"] = jnp.concatenate(k_parts, 0) if len(k_parts) > 1 else k_parts[0]
        new_cache["v"] = jnp.concatenate(v_parts, 0) if len(v_parts) > 1 else v_parts[0]
        return self._logits(params, h), new_cache

    def _decode_attn_paged(self, params, cache, x, pos):
        cfg, rt = self.cfg, self.rt
        b = x.shape[0]
        pos_v = jnp.broadcast_to(pos, (b,))
        table = cache["page_table"]

        def body_fn(h, xs):
            layer_p, kp, vp = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, kp, vp = _attn_decode_paged(
                layer_p["attn"], hn, kp, vp, table, pos_v, cfg, rt,
                use_rope=not cfg.max_position_embeddings,
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            if "moe" in layer_p:
                h = h + moe_mod.apply_moe(layer_p["moe"], hn, cfg, rt.moe_strategy)
            else:
                h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (kp, vp)

        h = x
        new_cache = dict(cache)
        k_parts, v_parts = [], []
        offset = 0
        for group in ("dense_layers", "layers"):
            if group not in params:
                continue
            stacked = params[group]
            n = _stack_len(stacked)
            xs = (stacked, cache["k_pages"][offset : offset + n],
                  cache["v_pages"][offset : offset + n])
            h, (nk, nv) = self._maybe_scan(body_fn, h, xs)
            k_parts.append(nk)
            v_parts.append(nv)
            offset += n
        new_cache["k_pages"] = jnp.concatenate(k_parts, 0) if len(k_parts) > 1 else k_parts[0]
        new_cache["v_pages"] = jnp.concatenate(v_parts, 0) if len(v_parts) > 1 else v_parts[0]
        return self._logits(params, h), new_cache

    def _decode_mla_paged(self, params, cache, x, pos):
        cfg, rt = self.cfg, self.rt
        b = x.shape[0]
        pos_v = jnp.broadcast_to(pos, (b,))
        table = cache["page_table"]

        def body_fn(h, xs):
            layer_p, kvp = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, kvp = mla_mod.apply_mla_paged(
                layer_p["attn"], hn, kvp, table, pos_v[:, None], cfg,
                impl=rt.resolve_paged_attn(),
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            if "moe" in layer_p:
                h = h + moe_mod.apply_moe(layer_p["moe"], hn, cfg, rt.moe_strategy)
            else:
                h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, kvp

        h = x
        parts = []
        offset = 0
        for group in ("dense_layers", "layers"):
            if group not in params:
                continue
            stacked = params[group]
            n = _stack_len(stacked)
            xs = (stacked, cache["kv_pages"][offset : offset + n])
            h, nkv = self._maybe_scan(body_fn, h, xs)
            parts.append(nkv)
            offset += n
        new_cache = dict(cache)
        new_cache["kv_pages"] = jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        return self._logits(params, h), new_cache

    def _decode_mla(self, params, cache, x, pos):
        cfg, rt = self.cfg, self.rt

        def body_fn(h, xs):
            layer_p, ckv, krope = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, new_c = mla_mod.apply_mla_decode(
                layer_p["attn"], hn, {"c_kv": ckv, "k_rope": krope}, pos, cfg
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            if "moe" in layer_p:
                h = h + moe_mod.apply_moe(layer_p["moe"], hn, cfg, rt.moe_strategy)
            else:
                h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (new_c["c_kv"], new_c["k_rope"])

        h = x
        c_parts, r_parts = [], []
        offset = 0
        for group in ("dense_layers", "layers"):
            if group not in params:
                continue
            stacked = params[group]
            n = _stack_len(stacked)
            xs = (stacked, cache["c_kv"][offset : offset + n], cache["k_rope"][offset : offset + n])
            h, (nc, nr) = self._maybe_scan(body_fn, h, xs)
            c_parts.append(nc)
            r_parts.append(nr)
            offset += n
        new_cache = dict(cache)
        new_cache["c_kv"] = jnp.concatenate(c_parts, 0) if len(c_parts) > 1 else c_parts[0]
        new_cache["k_rope"] = jnp.concatenate(r_parts, 0) if len(r_parts) > 1 else r_parts[0]
        return self._logits(params, h), new_cache

    def _decode_ssm(self, params, cache, x, pos):
        cfg, rt = self.cfg, self.rt

        def body_fn(h, xs):
            layer_p, st = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            out, new_st = ssm_mod.apply_mamba2_decode(layer_p["mixer"], hn, st, cfg)
            return h + out, new_st

        h = x
        new_cache = dict(cache)
        if cfg.family == "ssm":
            h, new_state = self._maybe_scan(body_fn, h, (params["layers"], cache["state"]))
            new_cache["state"] = new_state
            return self._logits(params, h), new_cache

        # hybrid (zamba2): segments with the shared attention block
        every = cfg.shared_attn_every
        n_inv = cfg.n_layers // every
        x0 = x
        state_parts = []
        sk, sv = [], []
        for inv in range(n_inv):
            delta, (nk, nv) = _shared_block_apply(
                params["shared"], h, x0, inv, cfg, rt,
                positions=None, cache=(cache["shared_k"][inv], cache["shared_v"][inv]), pos=pos,
            )
            h = h + delta
            sk.append(nk[None])
            sv.append(nv[None])
            seg_p = jax.tree.map(lambda a: a[inv * every : (inv + 1) * every], params["layers"])
            seg_s = jax.tree.map(lambda a: a[inv * every : (inv + 1) * every], cache["state"])
            h, new_st = self._maybe_scan(body_fn, h, (seg_p, seg_s))
            state_parts.append(new_st)
        rem = cfg.n_layers - n_inv * every
        if rem:
            seg_p = jax.tree.map(lambda a: a[n_inv * every :], params["layers"])
            seg_s = jax.tree.map(lambda a: a[n_inv * every :], cache["state"])
            h, new_st = self._maybe_scan(body_fn, h, (seg_p, seg_s))
            state_parts.append(new_st)
        new_cache["state"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *state_parts)
        new_cache["shared_k"] = jnp.concatenate(sk, 0)
        new_cache["shared_v"] = jnp.concatenate(sv, 0)
        return self._logits(params, h), new_cache

    # ------------------------------------------------------------- prefill
    def prefill(
        self,
        params: Params,
        tokens: jax.Array,
        *,
        frames: Optional[jax.Array] = None,
        patches: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Prefill forward: last-position logits (cache fill is fused into
        the same computation on TPU; the dry-run lowers this step)."""
        logits = self.forward(params, tokens, frames=frames, patches=patches)
        return logits[:, -1:]

    # ------------------------------------------------- fused chunked prefill
    @property
    def supports_fused_prefill(self) -> bool:
        """Can ``prefill_chunk`` ingest this architecture's prompts?

        Encoder-decoder / VLM need side inputs the serving cache does not
        carry, and MoE expert capacity is batch-shaped (right-padded chunk
        tokens would displace real tokens from experts, breaking parity
        with the token-at-a-time path)."""
        cfg = self.cfg
        if cfg.is_encoder_decoder or cfg.n_vision_tokens:
            return False
        if cfg.family == "moe":
            return False
        return True

    def prefill_chunk(
        self,
        params: Params,
        cache: Params,
        tokens: jax.Array,  # (B, T) right-padded prompt chunks
        offsets: jax.Array,  # (B,) cache position of each row's first token
        lengths: jax.Array,  # (B,) valid tokens per row; 0 = inactive row
    ) -> Tuple[jax.Array, Params]:
        """Ingest whole prompt chunks into the decode cache in ONE dispatch.

        Row ``b`` writes ``tokens[b, :lengths[b]]`` at cache positions
        ``offsets[b] .. offsets[b]+lengths[b]-1`` and returns the logits of
        its last valid token (``(B, padded_vocab)``) plus the updated cache
        — exactly what token-at-a-time decode ingestion would have produced,
        at chunk-size tokens per dispatch instead of one.
        """
        b, T = tokens.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        h, new_cache = self._prefill_hidden(params, cache, tokens, offsets, lengths)
        # gather each row's last valid hidden state BEFORE the vocab matmul
        # so the dispatch never materializes (B, T, vocab) logits
        last = jnp.clip(lengths - 1, 0, T - 1)
        h_last = h[jnp.arange(b), last][:, None]  # (b,1,d)
        return self._logits(params, h_last)[:, 0], new_cache

    def verify_chunk(
        self,
        params: Params,
        cache: Params,
        tokens: jax.Array,  # (B, T) right-padded [last accepted, k drafts]
        offsets: jax.Array,  # (B,) cache position of each row's first token
        lengths: jax.Array,  # (B,) valid tokens per row; 0 = inactive row
    ) -> Tuple[jax.Array, Params]:
        """Speculative-verify forward: the same fused chunk-extend as
        :meth:`prefill_chunk` but returning EVERY position's logits
        ``(B, T, padded_vocab)`` instead of only the last valid one.

        Position ``t``'s logits are the target model's distribution for
        the token AFTER ``tokens[b, t]``, conditioned on the cache plus
        ``tokens[b, :t+1]`` (the causal mask inside the extend path) —
        exactly what ``t`` sequential decode steps would produce, so the
        serving engine's acceptance rule can compare each draft against
        the token non-speculative decoding would have emitted.  ``T`` is
        ``spec_k + 1`` (small), so materializing the full logits block is
        cheap relative to the saved dispatches."""
        h, new_cache = self._prefill_hidden(params, cache, tokens, offsets, lengths)
        return self._logits(params, h), new_cache

    def _prefill_hidden(
        self, params: Params, cache: Params, tokens, offsets, lengths
    ) -> Tuple[jax.Array, Params]:
        """Shared chunk-extend backbone: embed + run the architecture's
        extend path, returning all-position hidden states ``(B, T, d)``
        and the updated cache.  Padded positions (``>= lengths[b]``) write
        nothing (valid-masked / OOB-sentinel dropped) and their hidden
        states are garbage the caller must not read."""
        cfg = self.cfg
        if not self.supports_fused_prefill:
            raise NotImplementedError(
                f"fused prefill unsupported for arch family {cfg.family!r} "
                "(enc-dec / vlm / moe)"
            )
        T = tokens.shape[1]
        offsets = jnp.asarray(offsets, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        positions = offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
        x = params["embed"][tokens]
        if cfg.max_position_embeddings:
            x = x + params["pos"][jnp.clip(positions, 0, cfg.max_position_embeddings - 1)]
        x = x.astype(self.rt.dtype)
        if "page_table" in cache:
            if cfg.use_mla:
                h, new_cache = self._prefill_mla_paged(params, cache, x, positions, valid)
            else:
                h, new_cache = self._prefill_attn_paged(params, cache, x, positions, valid)
        elif cfg.family in ("ssm", "hybrid"):
            h, new_cache = self._prefill_ssm(params, cache, x, positions, lengths, valid)
        elif cfg.use_mla:
            h, new_cache = self._prefill_mla(params, cache, x, positions, valid)
        else:
            h, new_cache = self._prefill_attn(params, cache, x, positions, valid)
        return h, new_cache

    def _prefill_attn(self, params, cache, x, positions, valid):
        cfg, rt = self.cfg, self.rt

        def body_fn(h, xs):
            layer_p, ck, cv = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, ck, cv = _attn_extend(
                layer_p["attn"], hn, ck, cv, positions, cfg,
                use_rope=not cfg.max_position_embeddings, valid=valid,
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (ck, cv)

        h = x
        new_cache = dict(cache)
        k_parts, v_parts = [], []
        offset = 0
        for group in ("dense_layers", "layers"):
            if group not in params:
                continue
            stacked = params[group]
            n = _stack_len(stacked)
            xs = (stacked, cache["k"][offset : offset + n], cache["v"][offset : offset + n])
            h, (nk, nv) = self._maybe_scan(body_fn, h, xs)
            k_parts.append(nk)
            v_parts.append(nv)
            offset += n
        new_cache["k"] = jnp.concatenate(k_parts, 0) if len(k_parts) > 1 else k_parts[0]
        new_cache["v"] = jnp.concatenate(v_parts, 0) if len(v_parts) > 1 else v_parts[0]
        return h, new_cache

    def _prefill_attn_paged(self, params, cache, x, positions, valid):
        cfg, rt = self.cfg, self.rt
        table = cache["page_table"]

        def body_fn(h, xs):
            layer_p, kp, vp = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, kp, vp = _attn_extend_paged(
                layer_p["attn"], hn, kp, vp, table, positions, cfg, rt,
                use_rope=not cfg.max_position_embeddings, valid=valid,
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (kp, vp)

        h, (nk, nv) = self._maybe_scan(
            body_fn, x, (params["layers"], cache["k_pages"], cache["v_pages"])
        )
        new_cache = dict(cache)
        new_cache["k_pages"] = nk
        new_cache["v_pages"] = nv
        return h, new_cache

    def _prefill_mla_paged(self, params, cache, x, positions, valid):
        cfg, rt = self.cfg, self.rt
        table = cache["page_table"]

        def body_fn(h, xs):
            layer_p, kvp = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, kvp = mla_mod.apply_mla_paged(
                layer_p["attn"], hn, kvp, table, positions, cfg,
                impl=rt.resolve_paged_attn(), valid=valid,
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, kvp

        h, nkv = self._maybe_scan(body_fn, x, (params["layers"], cache["kv_pages"]))
        new_cache = dict(cache)
        new_cache["kv_pages"] = nkv
        return h, new_cache

    def _prefill_mla(self, params, cache, x, positions, valid):
        cfg = self.cfg

        def body_fn(h, xs):
            layer_p, ckv, krope = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            a, new_c = mla_mod.apply_mla_extend(
                layer_p["attn"], hn, {"c_kv": ckv, "k_rope": krope}, positions, cfg,
                valid=valid,
            )
            h = h + a
            hn = apply_norm(layer_p["ln2"], h, cfg.norm, cfg.norm_eps)
            h = h + apply_mlp(layer_p["mlp"], hn, cfg.activation)
            return h, (new_c["c_kv"], new_c["k_rope"])

        h, (nc, nr) = self._maybe_scan(
            body_fn, x, (params["layers"], cache["c_kv"], cache["k_rope"])
        )
        new_cache = dict(cache)
        new_cache["c_kv"] = nc
        new_cache["k_rope"] = nr
        return h, new_cache

    def _prefill_ssm(self, params, cache, x, positions, lengths, valid):
        cfg, rt = self.cfg, self.rt

        def body_fn(h, xs):
            layer_p, st = xs
            hn = apply_norm(layer_p["ln1"], h, cfg.norm, cfg.norm_eps)
            out, new_st = ssm_mod.apply_mamba2_prefill(
                layer_p["mixer"], hn, st, cfg, valid=valid, lengths=lengths
            )
            return h + out, new_st

        h = x
        new_cache = dict(cache)
        if cfg.family == "ssm":
            h, new_state = self._maybe_scan(body_fn, h, (params["layers"], cache["state"]))
            new_cache["state"] = new_state
            return h, new_cache

        # hybrid (zamba2): shared attention block between SSM segments
        every = cfg.shared_attn_every
        n_inv = cfg.n_layers // every
        x0 = x
        state_parts, sk, sv = [], [], []
        for inv in range(n_inv):
            delta, (nk, nv) = _shared_block_apply(
                params["shared"], h, x0, inv, cfg, rt,
                positions=positions,
                cache=(cache["shared_k"][inv], cache["shared_v"][inv]),
                valid=valid,
            )
            h = h + delta
            sk.append(nk[None])
            sv.append(nv[None])
            seg_p = jax.tree.map(lambda a: a[inv * every : (inv + 1) * every], params["layers"])
            seg_s = jax.tree.map(lambda a: a[inv * every : (inv + 1) * every], cache["state"])
            h, new_st = self._maybe_scan(body_fn, h, (seg_p, seg_s))
            state_parts.append(new_st)
        rem = cfg.n_layers - n_inv * every
        if rem:
            seg_p = jax.tree.map(lambda a: a[n_inv * every :], params["layers"])
            seg_s = jax.tree.map(lambda a: a[n_inv * every :], cache["state"])
            h, new_st = self._maybe_scan(body_fn, h, (seg_p, seg_s))
            state_parts.append(new_st)
        new_cache["state"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *state_parts)
        new_cache["shared_k"] = jnp.concatenate(sk, 0)
        new_cache["shared_v"] = jnp.concatenate(sv, 0)
        return h, new_cache


# ----------------------------------------------------------------- helpers
def _stack_init(keys, init_fn):
    return jax.vmap(init_fn)(keys)


def _stack_len(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


def _unrolls_layers(xs) -> bool:
    """Whether :meth:`Model._maybe_scan` unrolls its loop over ``xs``: when
    every floating leaf of the stack is float32.

    A matmul at default precision rounds an f32 weight to bf16 on the TPU.
    Inside ``lax.scan`` XLA turns the rounding of one layer's slice into a
    convert of the whole stacked weight, which does not depend on the loop
    index and is hoisted out of the loop: every call then writes a bf16
    copy of the stack before its first matmul.  Unrolled, each slice's
    rounding fuses into its own matmul; the numbers are the same.  A stack
    that holds bf16 (a bf16 model, even where its router or SSM state is
    f32) has no such copy to make and keeps the scan, which keeps a deep
    program quick to compile."""
    floats = [a for a in jax.tree.leaves(xs) if jnp.issubdtype(a.dtype, jnp.floating)]
    return bool(floats) and all(a.dtype == jnp.float32 for a in floats)


def _stack_states(state: Params, n: int) -> Params:
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), state)


def _none_like(n: int):
    # placeholder pytree broadcastable through scan xs (unused branch)
    return (jnp.zeros((n, 1)), jnp.zeros((n, 1)), jnp.zeros((n, 1)))


def _pad128(x: int) -> int:
    return ((x + 127) // 128) * 128


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (prefer multiples of 128)."""
    best = 1
    for c in range(min(target, s), 0, -1):
        if s % c == 0:
            if c % 128 == 0:
                return c
            if best == 1:
                best = c  # best non-128-aligned fallback so far
    return best

"""Cell construction shared by the dry-run and the roofline harness.

A *cell* = (architecture x input shape x mesh).  For each cell this
module provides:

- ``input_specs``      — ShapeDtypeStruct stand-ins for every input
                         (weak-type-correct, shardable, no allocation);
- ``build_step``       — the jit-able step function (train / prefill /
                         decode) with logical-axis rules bound;
- ``shardings``        — in_shardings pytrees matched to the step inputs.

Decode cells lower ``serve_step`` (one new token against a seq_len KV
cache); ``long_500k`` additionally shards the cache sequence dim over
every mesh axis (context parallelism, DESIGN §3).  The serving-engine
hot paths lower as their own cells: ``serve_prefill_*`` (fused chunked
prefill, ``Model.prefill_chunk``) and ``serve_ragged_*`` (vectorized
per-row-position decode — the engine's one-dispatch-per-tick step).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ArchConfig, SHAPES, ShapeSpec, cell_applicable
from repro.models import Model, ModelRuntime
from repro.sharding.logical import axis_rules, train_rules
from repro.sharding.rules import (
    ShardingPolicy,
    axis_size,
    bytes_per_device,
    choose_policy,
    param_specs,
)
from repro.train.optimizer import AdamWConfig, Schedule, init_opt_state, opt_state_specs
from repro.train.steps import TrainStepConfig, make_train_step


# tokens ingested per row per serve_prefill dispatch (chunked prefill)
SERVE_PREFILL_CHUNK = 512

# serve_paged cell: page size (MXU-aligned) and pool fraction of the dense
# reservation — the cell exists to prove the paged decode step lowers with
# a pool strictly smaller than batch * max_len
SERVE_PAGE_SIZE = 128
SERVE_PAGED_POOL_FRACTION = 0.5


@dataclass
class Cell:
    arch: str
    shape: ShapeSpec
    cfg: ArchConfig
    model: Model
    step_fn: Callable
    args: Tuple  # ShapeDtypeStructs
    in_shardings: Tuple
    donate: Tuple[int, ...]
    rules: Dict
    policy: ShardingPolicy
    microbatches: int


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def decode_cell_rules(mesh: Mesh, shape: ShapeSpec) -> Dict:
    """Decode rule sets (DESIGN §3): KV cache seq over 'model'
    (flash-decode); long-context additionally over the dp axes."""
    multi = "pod" in mesh.shape
    r = train_rules(multi)
    if shape.name == "long_500k":
        r["kv_seq"] = tuple(dp_axes(mesh)) + ("model",)
        r["batch"] = None  # batch=1
        r["cache_batch"] = None
        r["heads"] = None
        r["kv_heads"] = None
        return r
    # flash-decode: cache seq over 'model'; heads/kv-heads must then
    # stay unsharded (a spec may use each mesh axis only once)
    r["kv_seq"] = "model"
    # paged cache: the page POOL dim shards over 'model' (pages are
    # unordered, the table indirection restores logical order per row)
    r["kv_pages"] = "model"
    r["kv_heads"] = None
    r["heads"] = None
    # decode reshards ACTIVATIONS, not weights (§Perf iter 3.2/3.3): the
    # FFN inputs are constrained to the 'data'-sharded hidden dim
    # ("act_embed" rule) so x @ W contracts over a sharded dim and lowers
    # to partial-matmul + psum of small activations instead of
    # all-gathering FSDP weight shards every token step.  Applying the
    # same to the whole residual stream (iter 3.2) made GSPMD gather the
    # batch-replicated cache — refuted; FFN-only keeps the cache layout.
    r["act_embed"] = "data"
    r["act_heads"] = "model"  # wo contraction over its 'model'-sharded dim
    r["act_batch"] = None  # these activations replicate batch while the
    #                        mesh axes carry their contraction dims
    # the residual stream itself lives d-sharded over 'data' at decode, so
    # row-parallel outputs (wo, FFN down-proj) keep their 'data'-sharded
    # output dim instead of forcing a weight gather (§Perf iter 3.5); the
    # KV cache keeps batch over 'data' via "cache_batch" (cf. refuted 3.2)
    r["embed"] = "data"
    r["batch"] = None
    return r


def pick_microbatches(cfg: ArchConfig, shape: ShapeSpec) -> int:
    """Keep ~128k global tokens per microbatch for train cells."""
    if shape.kind != "train":
        return 1
    tokens = shape.seq_len * shape.global_batch
    mb = max(1, tokens // 131_072)
    while shape.global_batch % mb:
        mb -= 1
    return mb


def auto_train_knobs(cfg: ArchConfig) -> Dict[str, Any]:
    """Size-adaptive production defaults (§Perf iterations 1.1-1.2):
    big models get 8-bit Adam moments, bf16 gradient accumulation and
    sqrt-segmented remat; small models keep plain fp32 state."""
    big = cfg.param_count() >= 30e9
    seg = 0
    if cfg.n_layers >= 24 and cfg.family in ("dense", "moe", "vlm"):
        target = max(2, int(round(cfg.n_layers ** 0.5)))
        layers = cfg.n_layers - (cfg.first_k_dense if cfg.family == "moe" else 0)
        for k in range(target, 1, -1):
            if layers % k == 0:
                seg = k
                break
    return {
        "moments_dtype": "int8" if big else "f32",
        "accum_dtype": "bf16" if big else "f32",
        "remat_segment": seg if big else 0,
    }


def make_opt_config(
    cfg: ArchConfig, *, moments_dtype: str = "f32", master_fp32: bool = True
) -> AdamWConfig:
    return AdamWConfig(
        schedule=Schedule(peak_lr=3e-4, warmup_steps=100, total_steps=10_000),
        moments_dtype=moments_dtype,
        master_fp32=master_fp32,  # bf16 params (+ fp32 master by default)
    )


def batch_specs(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> Tuple[Dict, Dict]:
    """(ShapeDtypeStructs, PartitionSpecs) for a training batch."""
    b, s = shape.global_batch, shape.seq_len
    dp = dp_axes(mesh)
    structs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    specs: Dict[str, Any] = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.is_encoder_decoder:
        structs["frames"] = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        specs["frames"] = P(dp, None, None)
    if cfg.n_vision_tokens:
        structs["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16
        )
        specs["patches"] = P(dp, None, None)
    return structs, specs


def _spec_tree_to_shardings(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )


def cache_specs(cfg: ArchConfig, cache_shapes, rules, mesh) -> Any:
    """PartitionSpecs for the decode cache from the logical rule set."""
    from repro.sharding.rules import axis_size

    def leaf_spec(path, leaf):
        # cache layouts (see Model.init_cache):
        #   k/v:            (L, B, T, Hkv, hd)   logical (None,batch,kv_seq,kv_heads,None)
        #   c_kv/k_rope:    (L, B, T, r)         (None,batch,kv_seq,None)
        #   cross_k/v:      (L, B, Tenc, H, hd)  (None,batch,None,kv_heads,None)
        #   shared_k/v:     (I, B, T, H, hd)
        #   state.conv_*:   (L, B, k-1, C)       (None,batch,None,ssm_inner?)
        #   state.ssm:      (L, B, h, p, n)      (None,batch,ssm_heads,None,None)
        name = path[-1] if path else ""
        logical: Tuple[Optional[str], ...]
        if name in ("k_pages", "v_pages"):
            #   k_pages/v_pages: (L, n_pages, Hkv, ps, hd)
            logical = (None, "kv_pages", "kv_heads", None, None)
        elif name == "kv_pages":
            #   MLA pool: (L, n_pages, ps, r+qr)
            logical = (None, "kv_pages", None, None)
        elif name == "page_table":
            logical = (None, None)  # tiny, replicated
        elif name in ("k", "v", "shared_k", "shared_v"):
            logical = (None, "cache_batch", "kv_seq", "kv_heads", None)
        elif name in ("cross_k", "cross_v"):
            logical = (None, "cache_batch", None, "kv_heads", None)
        elif name in ("c_kv", "k_rope"):
            logical = (None, "cache_batch", "kv_seq", None)
        elif name in ("conv_x",):
            logical = (None, "cache_batch", None, "ssm_inner")
        elif name in ("conv_B", "conv_C"):
            logical = (None, "cache_batch", None, None)
        elif name == "ssm":
            logical = (None, "cache_batch", "ssm_heads", None, None)
        else:
            logical = tuple(None for _ in leaf.shape)
        axes = []
        for dim, lg in zip(leaf.shape, logical):
            mesh_axes = rules.get(lg) if lg else None
            if mesh_axes is None:
                axes.append(None)
                continue
            size = axis_size(mesh, mesh_axes)
            axes.append(mesh_axes if (size <= dim and dim % size == 0) else None)
        return P(*axes)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    out = []
    for kp, leaf in flat:
        path = tuple(k.key if hasattr(k, "key") else str(k) for k in kp)
        out.append(leaf_spec(path, leaf))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    cfg_override: Optional[ArchConfig] = None,
    moments_dtype: str = "f32",
    master_fp32: bool = True,
    accum_dtype: str = "f32",
    remat_segment: int = 0,
    attn_impl: str = "auto",
    remat: bool = True,
    unroll_layers: bool = False,
    microbatches: Optional[int] = None,
    policy: Optional[ShardingPolicy] = None,
    logit_dtype=jnp.float32,
    sequence_parallel: bool = False,
) -> Cell:
    cfg = cfg_override or get_arch(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"cell ({arch},{shape_name}) not applicable: {reason}")

    if shape.kind == "train" and not unroll_layers:
        auto = auto_train_knobs(cfg)
        if moments_dtype == "f32":
            moments_dtype = auto["moments_dtype"]
        if accum_dtype == "f32":
            accum_dtype = auto["accum_dtype"]
        if remat_segment == 0:
            remat_segment = auto["remat_segment"]

    multi = "pod" in mesh.shape
    rt = ModelRuntime(
        dtype=jnp.bfloat16,
        attn_impl=attn_impl,
        remat=remat and shape.kind == "train",
        remat_segment=remat_segment,
        unroll_layers=unroll_layers,
        logit_dtype=logit_dtype,
        # shard_map EP is the production MoE path (roofline probes compile
        # it unrolled at full width/mesh in seconds); inside a full-depth
        # lax.scan the CPU SPMD pipeline's compile time is pathological
        # (>25 min for deepseek), so the scanned dry-run cells lower the
        # GSPMD gather path instead — same math, §Perf records both.
        moe_strategy="shardmap" if unroll_layers else "capacity",
    )
    model = Model(cfg, rt)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    if policy is None:
        # train state multiplier over bf16 params: fp32 master (2x) +
        # moments (int8 ~1x / f32 4x) + accumulator (1-2x) + params (1x)
        mult = 1.0
        if shape.kind == "train":
            mult = 5.0 if moments_dtype == "int8" else 7.0
        policy = choose_policy(params_shape, mesh, multi_pod=multi, state_multiplier=mult)
    p_specs, report = param_specs(params_shape, mesh, policy)
    p_shard = _spec_tree_to_shardings(p_specs, mesh)

    if shape.kind == "train":
        rules = train_rules(multi)
        if sequence_parallel:
            rules = dict(rules, residual_seq="model")
        if attn_impl == "auto":
            # flash-style memory for training backward: chunked attention
            # never materializes (S x S) score tensors as bwd residuals
            # (the Pallas kernel's recompute behaviour, in jnp form)
            attn_impl = "chunked"
            rt = dataclasses.replace(rt, attn_impl="chunked")
            model = Model(cfg, rt)
            params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        mb = microbatches if microbatches is not None else pick_microbatches(cfg, shape)
        opt_cfg = make_opt_config(cfg, moments_dtype=moments_dtype, master_fp32=master_fp32)
        tstep = make_train_step(
            model,
            TrainStepConfig(microbatches=mb, accum_dtype=accum_dtype, opt=opt_cfg),
            grad_shardings=p_shard,
        )

        def step(params, opt_state, batch, rng):
            return tstep(params, opt_state, batch, rng)

        opt_shape = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params_shape)
        o_specs = opt_state_specs(p_specs, opt_cfg)
        o_shard = _spec_tree_to_shardings(o_specs, mesh)
        b_structs, b_specs = batch_specs(cfg, shape, mesh)
        b_shard = _spec_tree_to_shardings(b_specs, mesh)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = (params_shape, opt_shape, b_structs, rng)
        in_shardings = (p_shard, o_shard, b_shard, NamedSharding(mesh, P()))
        donate = (0, 1)
    elif shape.kind == "prefill":
        rules = train_rules(multi)
        mb = 1

        def step(params, tokens, frames=None, patches=None):
            return model.prefill(params, tokens, frames=frames, patches=patches)

        b_structs, b_specs = batch_specs(cfg, shape, mesh)
        args = (params_shape, b_structs["tokens"])
        in_shardings = (p_shard, NamedSharding(mesh, b_specs["tokens"]))
        if cfg.is_encoder_decoder:
            args += (b_structs["frames"],)
            in_shardings += (NamedSharding(mesh, b_specs["frames"]),)
        if cfg.n_vision_tokens:
            args += (b_structs["patches"],)
            in_shardings += (NamedSharding(mesh, b_specs["patches"]),)
        step = _wrap_prefill(model, cfg)
        donate = ()
    elif shape.kind == "serve_prefill":
        # fused chunked prefill: the serving engine's prompt-ingestion
        # dispatch (SERVE_PREFILL_CHUNK tokens per row per call) writing
        # the decode cache in one shot
        rules = decode_cell_rules(mesh, shape)
        mb = 1
        b = shape.global_batch
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(b, shape.seq_len, dtype=jnp.bfloat16)
        )
        c_specs = cache_specs(cfg, cache_shape, rules, mesh)
        c_shard = _spec_tree_to_shardings(c_specs, mesh)

        def step(params, cache, tokens, offsets, lengths):
            return model.prefill_chunk(params, cache, tokens, offsets, lengths)

        args = (
            params_shape,
            cache_shape,
            jax.ShapeDtypeStruct((b, SERVE_PREFILL_CHUNK), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),  # per-row start offsets
            jax.ShapeDtypeStruct((b,), jnp.int32),  # per-row valid lengths
        )
        in_shardings = (
            p_shard,
            c_shard,
            NamedSharding(mesh, P(None, None)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        )
        donate = (1,)
    else:  # decode / serve_decode / serve_paged
        rules = decode_cell_rules(mesh, shape)
        mb = 1
        b = shape.global_batch
        if shape.kind == "serve_paged":
            # paged decode: the cell's whole point is a page pool strictly
            # smaller than the dense reservation — tokens resident, not
            # worst case.  The pool dim shards over 'model'; the page
            # table is scalar freight and stays replicated.
            pages_per_slot = shape.seq_len // SERVE_PAGE_SIZE
            n_pages = int(b * pages_per_slot * SERVE_PAGED_POOL_FRACTION)
            n_pages -= n_pages % axis_size(mesh, "model")
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(
                    b, shape.seq_len, dtype=jnp.bfloat16,
                    paged=True, page_size=SERVE_PAGE_SIZE, n_pages=n_pages,
                )
            )
        else:
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(b, shape.seq_len, dtype=jnp.bfloat16)
            )
        c_specs = cache_specs(cfg, cache_shape, rules, mesh)
        c_shard = _spec_tree_to_shardings(c_specs, mesh)
        tok_spec = P(None, None)  # tokens tiny; activations reshard per rules

        def step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        if shape.kind in ("serve_decode", "serve_paged"):
            # ragged continuous batching: per-row position vector [B] —
            # every slot advances in ONE dispatch regardless of depth mix
            pos_struct = jax.ShapeDtypeStruct((b,), jnp.int32)
        else:
            pos_struct = jax.ShapeDtypeStruct((), jnp.int32)  # uniform (serving cells)
        args = (
            params_shape,
            cache_shape,
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            pos_struct,
        )
        in_shardings = (
            p_shard,
            c_shard,
            NamedSharding(mesh, tok_spec),
            NamedSharding(mesh, P()),
        )
        donate = (1,)

    return Cell(
        arch=arch,
        shape=shape,
        cfg=cfg,
        model=model,
        step_fn=step,
        args=args,
        in_shardings=in_shardings,
        donate=donate,
        rules=rules,
        policy=policy,
        microbatches=mb,
    )


def _wrap_prefill(model: Model, cfg: ArchConfig):
    if cfg.is_encoder_decoder:
        return lambda params, tokens, frames: model.prefill(params, tokens, frames=frames)
    if cfg.n_vision_tokens:
        return lambda params, tokens, patches: model.prefill(params, tokens, patches=patches)
    return lambda params, tokens: model.prefill(params, tokens)


def lower_cell(cell: Cell, mesh: Mesh):
    """Trace + lower the cell's step under its rule context."""
    with axis_rules(mesh, cell.rules):
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            donate_argnums=cell.donate,
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*cell.args)
    return lowered

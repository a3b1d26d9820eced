"""Compile the serving path's kernels for a TPU v5e chip (described, not
attached) at ds-paper-100m widths, and read from the optimized programs how
their loop over the layers lowers.

Interpret-mode tests cannot show whether Mosaic accepts a kernel: these
compiles do, at no chip time.  Code that asks ``jax.default_backend()``
still sees the CPU here, so each test compiles with ``interpret=False``
itself (the whole-dispatch tests steer ``ops._default_interpret``).
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.flash_decode import heads_per_row

CFG = get_arch("ds-paper-100m")
B, MAX_LEN = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: a compile for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no TPU compiler installed
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize("T", [1, 16, 5])  # decode, prefill chunk, verify (spec_k=4)
def test_paged_kernel_compiles(one_chip, T, page_size, dtype):
    hkv, hd = CFG.n_kv_heads, CFG.resolved_head_dim
    r = heads_per_row(hkv, hd)
    P = MAX_LEN // page_size
    pool = (B * P, hkv // r, page_size, r * hd)
    fn = jax.jit(lambda *a: ops.paged_attention(*a, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (B, T, CFG.n_heads, hd), dtype),
        _spec(one_chip, pool, dtype),
        _spec(one_chip, pool, dtype),
        _spec(one_chip, (B, P), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_kernel_compiles_mla_absorbed(one_chip):
    """MLA's absorbed form: one shared latent page (width 576) per
    position, values from its first 512 columns."""
    ps, P, width = 16, MAX_LEN // 16, 576

    def absorbed(q, kv, table, offsets):
        pool = kv[:, None]  # (n_pages, 1, ps, width), as models/mla.py calls it
        return ops.paged_attention(q, pool, pool, table, offsets, v_width=512,
                                   interpret=False)

    compiled = jax.jit(absorbed).lower(
        _spec(one_chip, (B, 1, 16, width), jnp.bfloat16),
        _spec(one_chip, (B * P, ps, width), jnp.bfloat16),
        _spec(one_chip, (B, P), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


DISPATCHES = ["decode", "prefill", "verify", "prefill_rows"]


def _compile_dispatch(sharding, dispatch, dtype):
    """A serving dispatch at full width, through the compiled kernel, in
    ``dtype``: its optimized HLO text and the cache it was compiled for."""
    from repro.models import Model, ModelRuntime
    from repro.serving import sampling

    model = Model(CFG, ModelRuntime(dtype=dtype, paged_attn_impl="kernel"))

    def place(x):
        return _spec(sharding, x.shape, x.dtype)

    params = jax.tree.map(place, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(B, MAX_LEN, paged=True, page_size=16)))
    vec = lambda dt: _spec(sharding, (B,), dt)  # noqa: E731
    # (step, tokens per row, per-row int vectors before the temperatures)
    step, T, n_pos = {
        "decode": (sampling.make_decode_step(model, 0, True), 1, 1),
        "prefill": (sampling.make_prefill_step(model, 0, True), 16, 2),
        "verify": (sampling.make_verify_step(model, 0), 5, 2),
        # the paged engine's prefill: the rows that prefill gathered in-program
        "prefill_rows": (sampling.make_prefill_step(model, 0, True, rows=2), 16, 2),
    }[dispatch]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_default_interpret", lambda i: False if i is None else i)
        compiled = jax.jit(step).lower(
            params, cache, _spec(sharding, (B, T), jnp.int32),
            *[vec(jnp.int32)] * n_pos, vec(jnp.float32), *[vec(jnp.int32)] * 4,
        ).compile()
    return compiled.as_text(), cache


@pytest.fixture(scope="module")
def dispatch_hlo(one_chip):
    """``get(dispatch, dtype, scanned=False)``: each program compiled once;
    ``scanned`` forces the layer loop's ``lax.scan`` lowering."""
    from repro.models import transformer

    memo = {}

    def get(dispatch, dtype=jnp.float32, scanned=False):
        key = (dispatch, jnp.dtype(dtype).name, scanned)
        if key not in memo:
            with pytest.MonkeyPatch.context() as mp:
                if scanned:
                    mp.setattr(transformer, "_unrolls_layers", lambda xs: False)
                memo[key] = _compile_dispatch(one_chip, dispatch, dtype)
        return memo[key]

    return get


@pytest.mark.parametrize("dispatch", DISPATCHES)
def test_full_width_dispatch_compiles_without_pool_copies(dispatch_hlo, dispatch):
    """The serving engine's dispatches at full width, through the compiled
    kernel: nothing copies or transposes a layer's page pool on its way
    to the kernel."""
    text, cache = dispatch_hlo(dispatch)
    assert "tpu_custom_call" in text
    layer_pool = ",".join(str(d) for d in cache["k_pages"].shape[1:])
    pool_copies = [
        line.strip()[:160] for line in text.splitlines()
        if f"{layer_pool}]" in line and (" copy(" in line or " transpose(" in line)
    ]
    assert not pool_copies, pool_copies


def _top_level(text):
    """(computation header, body lines) of the entry and of each while
    body: the ops a program runs, as opposed to the insides of fusions."""
    bodies = set(re.findall(r" while\(.*?body=(%[\w.-]+)", text))
    for comp in re.split(r"\n(?=\S)", text):
        head, _, body = comp.partition("\n")
        if head.startswith("ENTRY") or head.split(" ")[0] in bodies:
            yield head, body.split("\n")


def _stack_converts(text):
    """Ops that make a bf16 copy of a whole stacked layer weight: a bf16
    result whose leading dimension is the layer count, computed from a
    ``params.layers`` parameter."""
    stacked = re.compile(rf"bf16\[{CFG.n_layers},\d+,")
    return [line.strip()[:160] for _, lines in _top_level(text) for line in lines
            if "(%params__layers" in line and stacked.search(line.split(" = ", 1)[-1])]


_INSTR = re.compile(r"(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_NO_WORK = {"parameter", "bitcast", "get-tuple-element", "tuple", "constant", "while",
            "copy-start", "copy-done"}


def _pool_work(text, cache):
    """(count, bytes) of the results a program computes that hold a layer's
    page pool or the whole stack of them, outside fusions, with a while
    loop's body counted once per layer."""
    L, pool = CFG.n_layers, cache["k_pages"]
    per_layer = math.prod(pool.shape[1:])
    count = nbytes = 0
    for head, lines in _top_level(text):
        trips = 1 if head.startswith("ENTRY") else L
        for m in map(_INSTR.match, (ln.strip() for ln in lines)):
            if not m or m.group(3) in _NO_WORK:
                continue
            dtype, dims, _ = m.groups()
            n = math.prod(int(d) for d in dims.split(",")) if dims else 1
            if dtype == "f32" and n in (per_layer, L * per_layer):
                count += trips
                nbytes += trips * n * pool.dtype.itemsize
    return count, nbytes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dispatch", DISPATCHES)
def test_layer_loop_lowering(dispatch_hlo, dispatch, dtype):
    """f32 layer stacks lower unrolled, so each weight's rounding to bf16
    fuses into its own matmul: no bf16 copy of a whole stack.  bf16 stacks
    keep the loop over the layers."""
    text, _ = dispatch_hlo(dispatch, dtype)
    if dtype == jnp.float32:
        assert not _stack_converts(text), _stack_converts(text)
        assert " while(" not in text
    else:
        assert " while(" in text


@pytest.mark.parametrize("dispatch", ["decode", "prefill_rows"])
def test_unrolled_layer_loop_moves_no_more_pool_than_scan(dispatch_hlo, dispatch):
    """Against the scanned lowering of the same f32 program (which does
    copy the stacked weights to bf16), the unrolled one computes no more
    pool-shaped results, in count or in bytes: each layer's pages are
    written back at its index, never rebuilt by a stack."""
    scanned, cache = dispatch_hlo(dispatch, scanned=True)
    unrolled, _ = dispatch_hlo(dispatch)
    assert _stack_converts(scanned) and " while(" in scanned
    got, want = _pool_work(unrolled, cache), _pool_work(scanned, cache)
    assert got[0] <= want[0] and got[1] <= want[1], (got, want)

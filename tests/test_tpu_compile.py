"""Compile the serving path's kernels for a TPU v5e chip (described, not
attached) at ds-paper-100m widths.

Interpret-mode tests cannot show whether Mosaic accepts a kernel: these
compiles do, at no chip time.  Code that asks ``jax.default_backend()``
still sees the CPU here, so each test compiles with ``interpret=False``
itself (the whole-dispatch tests steer ``ops._default_interpret``).
"""

import os

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


@pytest.mark.parametrize("dispatch", ["decode", "prefill", "verify", "prefill_rows"])
def test_full_width_dispatch_compiles_without_pool_copies(one_chip, monkeypatch, dispatch):
    """The serving engine's dispatches at full width, through the compiled
    kernel: nothing copies or transposes a layer's page pool on its way
    to the kernel."""
    from repro.models import Model, ModelRuntime
    from repro.serving import sampling

    monkeypatch.setattr(ops, "_default_interpret", lambda i: False if i is None else i)
    model = Model(CFG, ModelRuntime(paged_attn_impl="kernel"))

    def place(x):
        return _spec(one_chip, x.shape, x.dtype)

    params = jax.tree.map(place, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(B, MAX_LEN, paged=True, page_size=16)))
    vec = lambda dt: _spec(one_chip, (B,), dt)  # noqa: E731
    # (step, tokens per row, per-row int vectors before the temperatures)
    step, T, n_pos = {
        "decode": (sampling.make_decode_step(model, 0, True), 1, 1),
        "prefill": (sampling.make_prefill_step(model, 0, True), 16, 2),
        "verify": (sampling.make_verify_step(model, 0), 5, 2),
        # the paged engine's prefill: the rows that prefill gathered in-program
        "prefill_rows": (sampling.make_prefill_step(model, 0, True, rows=2), 16, 2),
    }[dispatch]
    compiled = jax.jit(step).lower(
        params, cache, _spec(one_chip, (B, T), jnp.int32),
        *[vec(jnp.int32)] * n_pos, vec(jnp.float32), *[vec(jnp.int32)] * 4,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_pool = ",".join(str(d) for d in cache["k_pages"].shape[1:])
    pool_copies = [
        line.strip()[:160] for line in text.splitlines()
        if f"{layer_pool}]" in line and (" copy(" in line or " transpose(" in line)
    ]
    assert not pool_copies, pool_copies

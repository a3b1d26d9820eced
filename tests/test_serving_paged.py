"""Paged KV cache: token parity vs the dense engine, page reuse after
free, pool accounting, on-device stop tokens, and the paged MLA path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model, ModelRuntime
from repro.serving.engine import Request, ServeEngine


def _setup(arch="ds-paper-100m", seed=0, **rt_kwargs):
    cfg = reduced(get_arch(arch))
    model = Model(cfg, ModelRuntime(**rt_kwargs))
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _ragged_requests(max_new=4, temperature=0.0, stop_token=None):
    """Mixed lengths + more requests than slots => mid-stream refills."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8], [42], [5, 4, 3, 2, 1], [17, 23, 31]]
    return [
        Request(uid=f"r{i}", prompt=list(p), max_new_tokens=max_new,
                temperature=temperature, stop_token=stop_token)
        for i, p in enumerate(prompts)
    ]


# ------------------------------------------------------------- token parity
def test_paged_matches_dense_token_for_token():
    """Tentpole parity: the paged engine (tight pool => mid-stream page
    reuse after free) must produce token-for-token identical output to
    the dense fused engine, greedy AND seeded temperature, on a ragged
    batch with mid-stream refills."""
    cfg, model, params = _setup()
    for temperature in (0.0, 0.7):
        dense = ServeEngine(model, params, max_batch=2, max_len=32,
                            prefill_chunk=4, rng_seed=7)
        dense.submit(_ragged_requests(temperature=temperature))
        dense.run_to_completion()
        paged = ServeEngine(model, params, max_batch=2, max_len=32,
                            prefill_chunk=4, rng_seed=7,
                            cache_mode="paged", page_size=8, total_pages=4)
        paged.submit(_ragged_requests(temperature=temperature))
        paged.run_to_completion()
        got_d = {r.uid: r.output for r in dense.finished}
        got_p = {r.uid: r.output for r in paged.finished}
        assert got_d == got_p, f"temperature={temperature}: {got_d} != {got_p}"
        # the pool (4 pages) is smaller than the lifetime page demand, so
        # parity above can only hold if freed pages were reused cleanly
        assert paged.page_allocs > paged.n_pages, "scenario never reused a page"
        assert paged.peak_pages <= paged.n_pages
        assert paged.peak_cache_bytes < paged.dense_cache_bytes
        # everything returned to the pool at drain
        assert paged.pages_in_use == 0
        assert sorted(paged._free_pages) == list(range(paged.n_pages))


def test_paged_matches_dense_decode_ingest_mla():
    """Paged MLA (deepseek: compressed latent pages, decode-path prompt
    ingestion since MoE has no fused prefill) matches the dense engine."""
    cfg, model, params = _setup("deepseek-v2-236b", seed=2)
    dense = ServeEngine(model, params, max_batch=2, max_len=32, rng_seed=3)
    dense.submit(_ragged_requests(max_new=3))
    dense.run_to_completion()
    paged = ServeEngine(model, params, max_batch=2, max_len=32, rng_seed=3,
                        cache_mode="paged", page_size=8, total_pages=6)
    paged.submit(_ragged_requests(max_new=3))
    paged.run_to_completion()
    assert not paged._use_prefill  # moe => decode-path ingestion
    got_d = {r.uid: r.output for r in dense.finished}
    got_p = {r.uid: r.output for r in paged.finished}
    assert got_d == got_p
    assert "kv_pages" in paged.cache and paged.peak_pages > 0


def test_paged_isolated_rows_and_refill():
    """A request's output must be independent of co-scheduled requests
    and of which physical pages it lands on after refills."""
    cfg, model, params = _setup(seed=2)
    long_p = [3, 1, 4, 1, 5, 9, 2, 6]
    solo = ServeEngine(model, params, max_batch=1, max_len=32)
    solo.submit([Request(uid="solo", prompt=list(long_p), max_new_tokens=4)])
    want = solo.run_to_completion()[0].output

    mixed = ServeEngine(model, params, max_batch=2, max_len=32,
                        cache_mode="paged", page_size=8, total_pages=6)
    mixed.submit([
        Request(uid="long", prompt=list(long_p), max_new_tokens=4),
        Request(uid="short", prompt=[2, 7], max_new_tokens=6),
        Request(uid="short2", prompt=[7], max_new_tokens=6),
    ])
    got = {r.uid: r.output for r in mixed.run_to_completion()}
    assert got["long"] == want


def test_paged_pool_exhaustion_raises():
    """Exhaustion is now recoverable (prefix eviction, then youngest-slot
    preemption — tests/test_serving_prefix.py), but a request that cannot
    fit the whole pool must still fail loudly: here every prompt+budget
    needs 2 pages of a 1-page pool."""
    cfg, model, params = _setup()
    engine = ServeEngine(model, params, max_batch=2, max_len=32,
                         prefill_chunk=4, cache_mode="paged",
                         page_size=8, total_pages=1)
    engine.submit(_ragged_requests())
    with pytest.raises(RuntimeError, match="pool exhausted"):
        engine.run_to_completion()


def test_paged_overlong_prompt_raises_clearly():
    """A prompt that cannot fit max_len must fail with a clear error at
    allocation, not an opaque page-table IndexError."""
    cfg, model, params = _setup()
    engine = ServeEngine(model, params, max_batch=1, max_len=16,
                         prefill_chunk=8, cache_mode="paged", page_size=8)
    engine.submit([Request(uid="big", prompt=list(range(1, 25)),
                           max_new_tokens=2)])
    with pytest.raises(ValueError, match="max_len"):
        engine.run_to_completion()


def test_paged_rejected_for_unpageable_arch():
    cfg, model, params = _setup("mamba2-1.3b", seed=1)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, max_batch=2, max_len=32, cache_mode="paged")


# ------------------------------------------------------------- stop tokens
def test_stop_token_finishes_early_on_device():
    """Satellite: the fused dispatch's done mask must finish a request the
    moment it emits its stop token (kept in the output), in dense and
    paged modes identically."""
    cfg, model, params = _setup(seed=4)
    probe = ServeEngine(model, params, max_batch=1, max_len=32)
    probe.submit([Request(uid="p", prompt=[1, 2, 3], max_new_tokens=6)])
    free_run = probe.run_to_completion()[0].output
    stop = free_run[2]  # finish after the 3rd token

    outs = {}
    for name, kwargs in (
        ("dense", {}),
        ("paged", dict(cache_mode="paged", page_size=8, total_pages=4)),
    ):
        e = ServeEngine(model, params, max_batch=2, max_len=32, **kwargs)
        e.submit([Request(uid="s", prompt=[1, 2, 3], max_new_tokens=6,
                          stop_token=stop)])
        outs[name] = e.run_to_completion()[0].output
        if name == "paged":
            assert e.pages_in_use == 0  # freed the moment the mask fired
    assert outs["dense"] == free_run[:3], (outs["dense"], free_run)
    assert outs["dense"] == outs["paged"]


# ------------------------------------------------------ model-level kernel path
def test_paged_kernel_impl_matches_jnp_impl():
    """The Pallas flash-decode path (interpret mode on CPU) must agree
    with the jnp gather fallback through full decode steps."""
    cfg, model, params = _setup()
    B, max_len, ps = 2, 32, 8
    P = max_len // ps
    n_pages = B * P
    cache = model.init_cache(B, max_len, paged=True, page_size=ps, n_pages=n_pages)
    table = np.full((B, P), n_pages, np.int32)
    table[0, :2] = [3, 0]
    table[1, :2] = [2, 1]
    cache["page_table"] = jnp.asarray(table)
    m_jnp = Model(cfg, ModelRuntime(paged_attn_impl="jnp"))
    m_ker = Model(cfg, ModelRuntime(paged_attn_impl="kernel"))
    toks = jnp.asarray([[5], [9]], jnp.int32)
    cache_j, cache_k = cache, cache
    for pos in ([0, 0], [1, 1], [2, 2]):
        pv = jnp.asarray(pos, jnp.int32)
        lj, cache_j = m_jnp.decode_step(params, cache_j, toks, pv)
        lk, cache_k = m_ker.decode_step(params, cache_k, toks, pv)
        np.testing.assert_allclose(
            np.asarray(lj), np.asarray(lk), rtol=2e-4, atol=2e-4
        )


def test_paged_prefill_chunk_kernel_matches_jnp():
    """Chunk-extend through the kernel == jnp fallback (ragged lengths,
    padded rows)."""
    cfg, model, params = _setup()
    B, max_len, ps = 2, 32, 8
    n_pages = B * (max_len // ps)
    toks = np.zeros((B, 4), np.int32)
    toks[0, :4] = [1, 2, 3, 4]
    toks[1, :2] = [9, 8]
    offs = jnp.zeros((B,), jnp.int32)
    lens = jnp.asarray([4, 2], jnp.int32)
    outs = {}
    for impl in ("jnp", "kernel"):
        m = Model(cfg, ModelRuntime(paged_attn_impl=impl))
        cache = m.init_cache(B, max_len, paged=True, page_size=ps, n_pages=n_pages)
        table = np.full((B, max_len // ps), n_pages, np.int32)
        table[0, 0] = 1
        table[1, 0] = 3
        cache["page_table"] = jnp.asarray(table)
        lg, _ = m.prefill_chunk(params, cache, jnp.asarray(toks), offs, lens)
        outs[impl] = np.asarray(lg)
    np.testing.assert_allclose(outs["jnp"], outs["kernel"], rtol=2e-4, atol=2e-4)

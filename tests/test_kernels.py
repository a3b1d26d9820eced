"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_decode import heads_per_row

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "bh,s,hd,block",
    [
        (2, 128, 64, 64),
        (1, 256, 128, 128),
        (3, 512, 64, 256),
        (2, 128, 192, 64),  # nemotron head_dim
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(bh, s, hd, block, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (bh, s, hd), dtype)
    k = jax.random.normal(ks[1], (bh, s, hd), dtype)
    v = jax.random.normal(ks[2], (bh, s, hd), dtype)
    from repro.kernels.flash_attention import flash_attention_bhsd

    out = flash_attention_bhsd(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    want = ref.attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    s, hd = 256, 64
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (2, s, hd), jnp.float32) for kk in ks)
    from repro.kernels.flash_attention import flash_attention_bhsd

    out = flash_attention_bhsd(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True
    )
    want = ref.attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_model_layout():
    b, s, h, hd = 2, 128, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    from repro.models.layers import attention_scores

    want = attention_scores(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


# ------------------------------------------------- paged flash decode/extend
def _paged_setup(B, Hkv, dk, ps, P, n_pages, offsets, dtype=jnp.float32, seed=0, r=1):
    """Random head-major page pools ``(n_pages, Hkv/r, ps, r*dk)``, ``r``
    heads side by side per row — the layout ``Model.init_cache(paged=True)``
    allocates per layer."""
    rng = np.random.default_rng(seed)
    shape = (n_pages, Hkv // r, ps, r * dk)
    k_pages = jnp.asarray(rng.standard_normal(shape), dtype)
    v_pages = jnp.asarray(rng.standard_normal(shape), dtype)
    return k_pages, v_pages


def _alloc_table(B, P, n_pages, frontiers, ps, seed=1):
    """Disjoint physical pages per row covering each row's frontier;
    everything else holds the out-of-bounds sentinel (unallocated)."""
    rng = np.random.default_rng(seed)
    perm = list(rng.permutation(n_pages))
    table = np.full((B, P), n_pages, np.int32)
    for b, frontier in enumerate(frontiers):
        for j in range(-(-frontier // ps)):
            table[b, j] = perm.pop()
    return jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T", [1, 4])  # flash-decode and chunk-extend
# GQA with one or two heads per pool row, and MLA-style Hkv=1
@pytest.mark.parametrize("hkv,g,r", [(2, 4, 1), (2, 4, 2), (1, 8, 1)])
def test_paged_attention_vs_reference(T, hkv, g, r, dtype):
    B, dk, ps, P = 3, 32, 8, 4
    n_pages = 10
    h = hkv * g
    offsets = np.asarray([5, 0, 9], np.int32)  # ragged rows
    k_pages, v_pages = _paged_setup(B, hkv, dk, ps, P, n_pages, offsets, dtype, r=r)
    table = _alloc_table(B, P, n_pages, offsets + T, ps)
    q = jnp.asarray(
        np.random.default_rng(2).standard_normal((B, T, h, dk)), dtype
    )
    out = ops.paged_attention(
        q, k_pages, v_pages, table, jnp.asarray(offsets), interpret=True
    )
    want = ref.paged_attention_reference(q, k_pages, v_pages, table, jnp.asarray(offsets))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("k", [1, 3])  # draft window sizes
def test_paged_verify_vs_reference(k):
    """The speculative-verify entry point must match its registered
    oracle on its own contract — k+1 candidate queries against the
    accepted history — not merely delegate to whatever paged_attention
    happens to do (a rewrite of the delegation must keep this green)."""
    B, dk, ps, P = 3, 32, 8, 4
    n_pages = 12
    hkv, g = 2, 4
    T = k + 1
    offsets = np.asarray([5, 0, 9], np.int32)
    k_pages, v_pages = _paged_setup(B, hkv, dk, ps, P, n_pages, offsets, r=2)
    table = _alloc_table(B, P, n_pages, offsets + T, ps)
    q = jnp.asarray(
        np.random.default_rng(7).standard_normal((B, T, hkv * g, dk)),
        jnp.float32,
    )
    out = ops.paged_verify(
        q, k_pages, v_pages, table, jnp.asarray(offsets), interpret=True
    )
    oracle = ref.ORACLES["paged_verify"]
    want = oracle(q, k_pages, v_pages, table, jnp.asarray(offsets))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5
    )


def test_oracle_registry_is_total():
    """Every public kernels.ops entry point has a registered oracle (the
    same invariant dslint R6 enforces statically)."""
    import inspect

    public = {
        name for name, fn in vars(ops).items()
        if inspect.isfunction(fn) and fn.__module__ == ops.__name__
        and not name.startswith("_")
    }
    assert public == set(ref.ORACLES), (
        f"ORACLES registry drift: ops has {sorted(public)}, "
        f"registry has {sorted(ref.ORACLES)}"
    )


def test_paged_attention_matches_contiguous_reference():
    """Pages laid out contiguously == plain causal attention over the
    logical sequence: the kernel's page indirection is position-exact."""
    B, T, h, dk, ps = 2, 8, 4, 16, 4
    seq = 16  # rows fully resident: positions 0..seq-1 already written
    P = seq // ps
    n_pages = B * P
    rng = np.random.default_rng(3)
    # identity layout: row b's logical page j is physical page b*P+j
    table = jnp.asarray(
        np.arange(B * P, dtype=np.int32).reshape(B, P)
    )
    kv = rng.standard_normal((B, seq, h, dk)).astype(np.float32)
    vv = rng.standard_normal((B, seq, h, dk)).astype(np.float32)
    r = heads_per_row(h, dk)  # 4 heads of 16 lanes per pool row

    def pages(x):  # (B, seq, h, dk) -> head-major (B*P, h/r, ps, r*dk)
        x = x.reshape(B * P, ps, h // r, r * dk).transpose(0, 2, 1, 3)
        return jnp.asarray(x)

    k_pages, v_pages = pages(kv), pages(vv)
    q = jnp.asarray(rng.standard_normal((B, T, h, dk)), jnp.float32)
    offsets = jnp.full((B,), seq - T, jnp.int32)  # chunk = the last T tokens
    out = ops.paged_attention(q, k_pages, v_pages, table, offsets, interpret=True)
    # oracle: causal attention of the full sequence, last T rows
    full_q = jnp.asarray(rng.standard_normal((B, seq, h, dk)), jnp.float32)
    full_q = full_q.at[:, seq - T :].set(q)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * h, -1, dk)  # noqa: E731
    want = ref.attention_reference(fold(full_q), fold(jnp.asarray(kv)), fold(jnp.asarray(vv)), causal=True)
    want = want.reshape(B, h, seq, dk).transpose(0, 2, 1, 3)[:, seq - T :]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_attention_parked_rows_are_finite_zero():
    """Rows whose pages were all freed (OOB sentinel everywhere) must
    produce zeros, not NaNs — the engine discards them but NaNs would
    poison the dispatch."""
    B, T, h, dk, ps, P, n_pages = 2, 1, 4, 16, 8, 4, 6
    rng = np.random.default_rng(4)
    k_pages = jnp.asarray(rng.standard_normal((n_pages, h, ps, dk)), jnp.float32)
    table = np.full((B, P), n_pages, np.int32)
    table[0, 0] = 2  # row 0 live, row 1 parked
    q = jnp.asarray(rng.standard_normal((B, T, h, dk)), jnp.float32)
    out = ops.paged_attention(
        q, k_pages, k_pages, jnp.asarray(table), jnp.asarray([3, 3], jnp.int32),
        interpret=True,
    )
    assert np.isfinite(np.asarray(out)).all()
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.any(np.asarray(out[0]) != 0.0)


# ----------------------------------------------------------------- SSD kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,p,n,chunk,block_h",
    [
        (2, 64, 4, 16, 16, 16, 2),
        (1, 128, 8, 32, 32, 32, 4),
        (2, 256, 16, 64, 128, 128, 8),  # mamba2-1.3b tile shape
    ],
)
def test_ssd_kernel_sweep(b, l, h, p, n, chunk, block_h, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, l, n), dtype)
    C = jax.random.normal(ks[4], (b, l, n), dtype)

    y, final = ops.ssd(x, dt, A, B, C, chunk=chunk, block_h=block_h, interpret=True)
    y_ref, final_ref = ref.ssd_reference(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(
        np.asarray(final), np.asarray(final_ref), rtol=1e-3, atol=1e-3
    )


def test_ssd_kernel_matches_sequential_recurrence():
    """Chunk kernel + glue == naive per-token recurrence."""
    b, l, h, p, n, chunk = 1, 32, 2, 8, 8, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))

    y, final = ops.ssd(x, dt, A, B, C, chunk=chunk, interpret=True)

    from repro.models.ssm import ssd_decode_step

    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        yt, state = ssd_decode_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(yt)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 256), (2, 8, 512), (3, 5, 128)])
def test_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], (shape[-1],), jnp.float32) + 1.0
    out = ops.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_reference(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


def test_model_attention_kernel_path():
    """The model's attn_impl='kernel' path equals the direct path."""
    from repro.configs import get_arch, reduced
    from repro.models import Model, ModelRuntime

    cfg = reduced(get_arch("ds-paper-100m"))
    rng = jax.random.PRNGKey(3)
    m_direct = Model(cfg, ModelRuntime(attn_impl="direct"))
    m_kernel = Model(cfg, ModelRuntime(attn_impl="kernel", attn_chunk=16))
    params = m_direct.init(rng)
    toks = jax.random.randint(rng, (2, 64), 0, cfg.vocab_size)
    a = m_direct.forward(params, toks)
    b = m_kernel.forward(params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_model_ssd_kernel_path():
    from repro.configs import get_arch, reduced
    from repro.models import Model, ModelRuntime

    cfg = reduced(get_arch("mamba2-1.3b"))
    rng = jax.random.PRNGKey(4)
    m_ref = Model(cfg, ModelRuntime(use_ssd_kernel=False))
    m_k = Model(cfg, ModelRuntime(use_ssd_kernel=True))
    params = m_ref.init(rng)
    toks = jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)
    a = m_ref.forward(params, toks)
    b = m_k.forward(params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

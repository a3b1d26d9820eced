"""The serving programs' loop over a float32 layer stack lowers unrolled
(``Model._maybe_scan``), so that each weight's rounding to bf16 on the TPU
fuses into its own matmul instead of a hoisted convert of the whole stack.
Unrolling changes how the loop lowers, never what it computes: the paged
decode, the row-gathered prefill and the verify programs give bit-identical
logits, sampled tokens, done masks and KV pages either way.  The scanned
lowering is reached by replacing the one function that chooses it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model, transformer
from repro.serving import sampling

B, MAX_LEN, PAGE, CHUNK, ROWS, SPEC = 4, 128, 16, 16, 2, 3
SEED = 5


def _dense_mla():
    """A dense arch with MLA attention (deepseek-v2 itself is MoE, which has
    no fused prefill)."""
    ds = reduced(get_arch("deepseek-v2-236b"))
    keys = ("use_mla", "kv_lora_rank", "q_lora_rank", "rope_head_dim",
            "nope_head_dim", "v_head_dim", "head_dim")
    return dataclasses.replace(reduced(get_arch("ds-paper-100m"), n_layers=3),
                               **{k: getattr(ds, k) for k in keys})


ARCHS = {
    "dense": lambda: reduced(get_arch("ds-paper-100m"), n_layers=3),
    "mla": _dense_mla,
    # MoE + MLA with a dense first layer: two stacked groups, decode only
    "moe": lambda: reduced(get_arch("deepseek-v2-236b"), n_layers=4),
}
CASES = [(a, s) for a in ARCHS for s in ("prefill_rows", "decode", "verify")
         if a != "moe" or s == "decode"]


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _row_vectors(temps):
    """temps, streams, steps, stops, max_news"""
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    return (jnp.asarray(temps, jnp.float32), i32(np.arange(B) + 3), i32([0] * B),
            i32([-1] * B), i32([64] * B))


def _prefill_rows_logits(model, params, cache, tokens, offsets, lengths):
    """The row-gathered prefill program's last-token logits, scattered back
    to slots: what ``make_prefill_step`` samples from."""
    table = cache["page_table"]
    idx, cache, tokens, offsets, lengths = sampling._gather_rows(
        ROWS, cache, tokens, offsets, lengths)
    logits, cache = model.prefill_chunk(params, cache, tokens, offsets, lengths)
    return sampling._to_slots(idx, B, table, cache, logits[:, :model.cfg.vocab_size])[0]


def _serve(model, params, cache, unrolled):
    """Run the prefill (rows gathered), decode and verify programs in turn,
    each as the engine compiles it and in its raw-logits form; return every
    output by stage."""
    assert transformer._unrolls_layers(params["layers"]) is unrolled
    rng = np.random.default_rng(SEED)
    vocab = model.cfg.vocab_size
    temps = [0.8, 0.0, 0.8, 0.0]
    out = {}

    lengths = jnp.asarray([11, 0, CHUNK, 0], jnp.int32)
    nxt = jnp.asarray(rng.integers(0, vocab, B), jnp.int32)
    if model.supports_fused_prefill:
        # rows 0 and 2 carry prompt tokens, so ROWS of B rows run
        tokens = jnp.asarray(rng.integers(0, vocab, (B, CHUNK)), jnp.int32)
        offsets = jnp.zeros(B, jnp.int32)
        logits = jax.jit(lambda *a: _prefill_rows_logits(model, *a))(
            params, cache, tokens, offsets, lengths)
        nxt, done, cache = jax.jit(sampling.make_prefill_step(model, SEED, rows=ROWS))(
            params, cache, tokens, offsets, lengths, *_row_vectors(temps))
        out["prefill_rows"] = (logits, nxt, done, cache)

    # decode: every row, at its own depth
    pos = jnp.maximum(lengths, 1)
    step_tokens = nxt[:, None]
    def decode_logits(*a):
        return model.decode_step(*a)[0][:, 0, :vocab]

    out["scans"] = str(jax.make_jaxpr(decode_logits)(params, cache, step_tokens, pos)).count("scan[")
    logits = jax.jit(decode_logits)(params, cache, step_tokens, pos)
    nxt, done, cache = jax.jit(sampling.make_decode_step(model, SEED))(
        params, cache, step_tokens, pos, *_row_vectors(temps))
    out["decode"] = (logits, nxt, done, cache)

    if not model.supports_fused_prefill:
        return out
    # verify: the last token and SPEC - 1 drafts on rows 0 and 2
    tokens = jnp.asarray(rng.integers(0, vocab, (B, SPEC)), jnp.int32).at[:, 0].set(nxt)
    lengths = jnp.asarray([SPEC, 0, SPEC - 1, 0], jnp.int32)
    offsets = pos + 1
    logits, _ = jax.jit(lambda *a: model.verify_chunk(*a))(params, cache, tokens, offsets, lengths)
    tgt, n_emit, done, cache = jax.jit(sampling.make_verify_step(model, SEED))(
        params, cache, tokens, offsets, lengths, *_row_vectors(temps))
    out["verify"] = (logits, tgt, n_emit, done, cache)
    return out


def _unrolled_and_scanned(arch):
    model = Model(ARCHS[arch]())
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, MAX_LEN, paged=True, page_size=PAGE)
    P = cache["page_table"].shape[1]
    cache["page_table"] = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    unrolled = _serve(model, params, cache, True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "_unrolls_layers", lambda xs: False)
        scanned = _serve(model, params, cache, False)
    return unrolled, scanned


@pytest.fixture(scope="module")
def served():
    """``get(arch)``: the arch's outputs under the unrolled and the scanned
    lowering, each served once."""
    memo = {}

    def get(arch):
        if arch not in memo:
            memo[arch] = _unrolled_and_scanned(arch)
        return memo[arch]

    return get


@pytest.mark.parametrize("arch,stage", CASES)
def test_unrolled_layer_loop_is_bit_identical_to_scan(served, arch, stage):
    unrolled, scanned = served(arch)
    assert unrolled["scans"] < scanned["scans"]  # two lowerings were compared
    got, want = unrolled[stage], scanned[stage]
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(_bits(g), _bits(w))
    # the cache moved: the comparison is not of two untouched pools
    pools = {k: v for k, v in got[-1].items() if k != "page_table"}
    assert all(np.any(np.asarray(v) != 0) for v in pools.values())


def test_choice_follows_the_stack_dtype():
    f32 = {"w": jnp.zeros((2, 4, 4), jnp.float32), "n": jnp.zeros((2, 4), jnp.float32)}
    assert transformer._unrolls_layers((f32, jnp.zeros((2, 8), jnp.float32)))
    # a bf16 model keeps the scan, where its router or state is f32 too
    bf16 = {"w": jnp.zeros((2, 4, 4), jnp.bfloat16), "router": jnp.zeros((2, 4, 4), jnp.float32)}
    assert not transformer._unrolls_layers((bf16, jnp.zeros((2, 8), jnp.bfloat16)))
    assert not transformer._unrolls_layers(jnp.zeros((2, 3), jnp.int32))

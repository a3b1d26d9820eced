"""Speculative decoding on the paged KV cache: byte parity with the
non-speculative engine (greedy AND seeded temperature), the fused
verify step's position-wise equivalence to sequential decode, stream
accounting across preemption replays under different ``spec_k``, and
allocator invariants under randomized speculative interleaving (seeded
``random``, not hypothesis — the env lacks it)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model, ModelRuntime
from repro.serving.engine import Request, ServeEngine
from repro.serving.sampling import make_verify_step


def _setup(seed=0):
    cfg = reduced(get_arch("ds-paper-100m"))
    model = Model(cfg, ModelRuntime())
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _mixed_requests():
    """Greedy + seeded-temperature rows, a stop-token row, and a row that
    runs into the max_len truncation point."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8], [42], [5, 4, 3, 2, 1]]
    reqs = [
        Request(uid=f"r{i}", prompt=list(p), max_new_tokens=10,
                temperature=0.0 if i % 2 == 0 else 0.8)
        for i, p in enumerate(prompts)
    ]
    reqs.append(Request(uid="stop", prompt=[3, 1, 4], max_new_tokens=10,
                        stop_token=7))
    reqs.append(Request(uid="long", prompt=[2, 7, 1, 8], max_new_tokens=64))
    return reqs


def _run(model, params, reqs, **kw):
    eng = ServeEngine(model, params, max_batch=3, max_len=32,
                      cache_mode="paged", page_size=8, **kw)
    eng.submit(reqs)
    eng.run_to_completion()
    return {r.uid: r.output for r in eng.finished}, eng


def test_spec_byte_identical_to_nonspec_both_proposers():
    """The tentpole's hard gate: greedy AND temperature outputs under
    speculation are byte-identical to the non-speculative engine, for
    the ngram proposer and for a draft model whose guesses are mostly
    wrong (separately-initialised weights) — acceptance only moves
    tokens-per-dispatch, never content."""
    cfg, model, params = _setup()
    base, _ = _run(model, params, _mixed_requests())

    got_n, eng_n = _run(model, params, _mixed_requests(),
                        speculative="ngram", spec_k=4)
    assert got_n == base
    assert eng_n.spec_dispatches > 0
    assert eng_n.stats.snapshot()["accepted_per_dispatch"] > 0

    draft = Model(cfg, ModelRuntime())
    dparams = draft.init(jax.random.PRNGKey(7))
    got_d, eng_d = _run(model, params, _mixed_requests(),
                        speculative="draft", spec_k=4,
                        draft_model=draft, draft_params=dparams)
    assert got_d == base
    assert eng_d.spec_dispatches > 0 and eng_d.draft_dispatches > 0
    # the pessimal draft exercises the rollback path constantly
    assert eng_d.draft_tokens_accepted < eng_d.draft_tokens_proposed


def test_ngram_cuts_dispatches_per_token_on_decode_heavy_traffic():
    """Short prompts, long generations, low batch: the latency-bound shape
    where nearly every dispatch is a decode tick.  The ngram proposer
    must land >= 2 tokens per verify dispatch and strictly cut target
    dispatches per token against the plain engine, emitting the same
    tokens."""
    cfg, model, params = _setup()

    def reqs():
        rng = np.random.default_rng(11)
        out = []
        for i in range(4):
            n = int(rng.integers(4, 12))
            prompt = [int(t) for t in rng.integers(1, 200, size=n)]
            out.append(Request(uid=f"d{i}", prompt=prompt, max_new_tokens=24))
        return out

    def per_token(eng):
        return eng.dispatches / (eng.tokens_emitted + eng.prompt_tokens_ingested)

    kw = dict(max_batch=2, max_len=128, prefill_chunk=8, cache_mode="paged", page_size=16)
    off = ServeEngine(model, params, **kw)
    ngram = ServeEngine(model, params, speculative="ngram", spec_k=8, **kw)
    for eng in (off, ngram):
        eng.submit(reqs())
        eng.run_to_completion()
    assert ({r.uid: r.output for r in ngram.finished}
            == {r.uid: r.output for r in off.finished})
    assert ngram.draft_tokens_accepted > 0
    assert ngram.stats.snapshot()["accepted_per_dispatch"] >= 2.0
    assert per_token(ngram) < per_token(off)


def test_spec_works_on_dense_cache_too():
    """Rewind is a frontier move, not a page operation, so speculation
    also runs (and stays byte-identical) on the dense cache."""
    cfg, model, params = _setup()
    base = ServeEngine(model, params, max_batch=3, max_len=32)
    base.submit(_mixed_requests())
    base.run_to_completion()
    spec = ServeEngine(model, params, max_batch=3, max_len=32,
                       speculative="ngram", spec_k=4)
    spec.submit(_mixed_requests())
    spec.run_to_completion()
    assert ({r.uid: r.output for r in spec.finished}
            == {r.uid: r.output for r in base.finished})
    assert spec.spec_dispatches > 0


def test_verify_step_positionwise_matches_sequential_decode():
    """Foundation of byte parity: one fused ``T = k + 1`` verify samples,
    position for position, exactly what ``k + 1`` sequential single-token
    decode dispatches would have — same logits conditioning (causal
    mask), same stream/step sampling keys.  Runs on the dense cache so
    the model is driven directly (a raw paged cache's page table belongs
    to the engine's allocator); the paged path is covered end-to-end by
    the byte-parity tests above."""
    cfg, model, params = _setup()
    B, L, k = 2, 32, 3
    prompt = [5, 9, 2, 7, 1]
    drafts = [3, 8, 4]  # arbitrary: verify scores them, then we compare
    rng_seed = 0

    # sequential oracle: feed [x0, d1..dk] one token at a time
    cache = model.init_cache(B, L)
    toks = jnp.asarray([prompt + [0] * k, prompt + [0] * k], jnp.int32)
    offs = jnp.asarray([0, 0], jnp.int32)
    lens = jnp.asarray([len(prompt) - 1] * 2, jnp.int32)
    _, cache = model.prefill_chunk(params, cache, toks[:, :len(prompt) - 1],
                                   offs, lens)
    seq_logits = []
    feed = [prompt[-1]] + drafts
    for t, tok in enumerate(feed):
        lg, cache = model.decode_step(
            params, cache,
            jnp.asarray([[tok]] * B, jnp.int32),
            jnp.asarray([len(prompt) - 1 + t] * B, jnp.int32),
        )
        seq_logits.append(np.asarray(lg[:, 0, :cfg.vocab_size]))

    # fused verify over the same positions
    verify = make_verify_step(model, rng_seed)
    cache = model.init_cache(B, L)
    _, cache = model.prefill_chunk(params, cache, toks[:, :len(prompt) - 1],
                                   offs, lens)
    tokens = jnp.asarray([feed] * B, jnp.int32)
    offsets = jnp.asarray([len(prompt) - 1] * B, jnp.int32)
    lengths = jnp.asarray([k + 1] * B, jnp.int32)
    temps = jnp.asarray([0.0, 0.9], jnp.float32)
    streams = jnp.asarray([0, 1], jnp.int32)
    steps = jnp.asarray([0, 0], jnp.int32)
    stops = jnp.full((B,), -1, jnp.int32)
    max_news = jnp.full((B,), 100, jnp.int32)
    tgt, n_emit, done, _ = verify(params, cache, tokens, offsets, lengths,
                                  temps, streams, steps, stops, max_news)
    tgt = np.asarray(tgt)

    # position-wise: the verify targets equal sampling the sequential
    # logits with the same (stream, step + t) keys — greedy row 0 via
    # argmax, temperature row 1 via the engine's device sampler
    from repro.serving.sampling import sample_tokens
    for t in range(k + 1):
        lg_t = jnp.asarray(seq_logits[t])
        want = np.asarray(sample_tokens(
            lg_t, temps, streams,
            jnp.asarray([t, t], jnp.int32), base_seed=rng_seed,
        ))
        assert tgt[0, t] == want[0], f"greedy row diverged at position {t}"
        assert tgt[1, t] == want[1], f"temp row diverged at position {t}"


def test_preempted_replay_identical_across_spec_k():
    """Deterministic-stream accounting: a request's sampling stream
    position depends only on tokens emitted — not on spec_k, not on how
    many drafts a dispatch carried, not on preemption replays.  A pool
    tight enough to force preemption mid-generation must yield identical
    outputs for the plain engine and speculative engines at different
    spec_k (temperature rows make stream misuse visible)."""
    cfg, model, params = _setup(3)

    def reqs():
        return [
            Request(uid=f"r{i}", prompt=[10 * i + j for j in range(1, 7)],
                    max_new_tokens=8, temperature=0.7)
            for i in range(5)
        ]

    outs = {}
    preempted = False
    for label, kw in (
        ("off", {}),
        ("k1", dict(speculative="ngram", spec_k=1)),
        ("k4", dict(speculative="ngram", spec_k=4)),
    ):
        # 5 pages for 3 slots of up to 2 pages each: growth pressure
        # forces preemption + replay partway through generation
        eng = ServeEngine(model, params, max_batch=3, max_len=16,
                          cache_mode="paged", page_size=8, total_pages=5,
                          **kw)
        eng.submit(reqs())
        eng.run_to_completion()
        outs[label] = {r.uid: r.output for r in eng.finished}
        preempted |= eng.preemptions > 0
    assert preempted, "pool never forced a preemption — weak test"
    assert outs["off"] == outs["k1"] == outs["k4"]


def test_spec_randomized_interleaving_invariants():
    """Satellite property test: drive the speculative paged engine
    through a seeded-random interleaving of submits and ticks on a pool
    tight enough to force preemption; after every tick the page
    refcounts must equal the holders (so CoW rollback never leaks or
    double-frees a page), no page may be aliased across slots in the
    generated region (rewind never exposes another slot's KV), and the
    final outputs must match the non-speculative dense engine byte for
    byte."""
    cfg, model, params = _setup()

    def _random_requests(rng, n):
        reqs = []
        for i in range(n):
            p = [rng.randrange(1, 99) for _ in range(rng.randrange(1, 10))]
            # long enough tails that three concurrent slots outgrow the
            # 5-page pool (up to ~26 tokens = 4 pages each)
            reqs.append(Request(uid=f"r{i}", prompt=p,
                                max_new_tokens=rng.randrange(6, 18),
                                temperature=0.5 if i % 2 else 0.0))
        return reqs

    def _check_invariants(eng):
        holders = {pid: [] for pid in range(eng.n_pages)}
        for row, pages in enumerate(eng._slot_pages):
            for j, pid in enumerate(pages):
                holders[pid].append((row, j))
        for pid in range(eng.n_pages):
            assert eng._page_refs[pid] == len(holders[pid]), (
                f"page {pid}: refcount {eng._page_refs[pid]} != "
                f"{len(holders[pid])} holders"
            )
        free = sorted(eng._free_pages
                      + [p for p in range(eng.n_pages) if eng._page_refs[p] > 0])
        assert free == list(range(eng.n_pages)), "free list / refs don't partition"
        for pid, maps in holders.items():
            assert len(maps) <= 1, (
                f"page {pid} aliased across slots {maps} with no prefix cache"
            )

    rejected_somewhere = preempted_somewhere = False
    for seed in (0, 1):
        rng = random.Random(seed)
        reqs = _random_requests(rng, 10)
        dense = ServeEngine(model, params, max_batch=3, max_len=32,
                            prefill_chunk=4, rng_seed=9)
        dense.submit([Request(uid=r.uid, prompt=list(r.prompt),
                              max_new_tokens=r.max_new_tokens,
                              temperature=r.temperature) for r in reqs])
        dense.run_to_completion()
        want = {r.uid: r.output for r in dense.finished}

        eng = ServeEngine(model, params, max_batch=3, max_len=32,
                          prefill_chunk=4, rng_seed=9,
                          cache_mode="paged", page_size=8, total_pages=5,
                          prefix_cache=False,  # so pages are never shared:
                          # any aliasing below is a rewind/refcount bug
                          speculative="ngram", spec_k=3)
        queue = list(reqs)
        steps = 0
        while (queue or eng.pending or eng.scheduler.has_active()) and steps < 500:
            if queue and rng.random() < 0.6:
                eng.submit([queue.pop(0) for _ in range(min(len(queue),
                                                            rng.randrange(1, 4)))])
            eng.step()
            steps += 1
            _check_invariants(eng)
        assert not queue and not eng.pending
        assert {r.uid: r.output for r in eng.finished} == want, (
            f"seed {seed}: speculative paged != one-shot dense"
        )
        assert eng.spec_dispatches > 0
        rejected_somewhere |= (eng.draft_tokens_accepted
                               < eng.draft_tokens_proposed)
        preempted_somewhere |= eng.preemptions > 0
    assert rejected_somewhere, "no draft was ever rejected — rollback untested"
    assert preempted_somewhere, "pool never came under pressure — weak test"


def test_spec_never_ooms_a_pool_the_plain_engine_fits():
    """Draft positions are best-effort: on a pool sized exactly for the
    non-speculative run (one slot, pages for prompt + max_new only), the
    speculative engine must shrink its drafts near the pool edge instead
    of raising pool exhaustion — and still emit identical bytes."""
    cfg, model, params = _setup()

    def reqs():
        # 4-token prompt + 28 new = exactly 4 pages at ps=8, but
        # max_len=40 leaves draft room: the optimistic pos+1+spec_k
        # reservation near the end wants a 5th page the pool lacks
        return [Request(uid="r", prompt=[5, 9, 2, 7], max_new_tokens=28)]

    outs = {}
    for label, kw in (("off", {}), ("spec", dict(speculative="ngram",
                                                 spec_k=8))):
        eng = ServeEngine(model, params, max_batch=1, max_len=40,
                          cache_mode="paged", page_size=8, total_pages=4,
                          **kw)
        eng.submit(reqs())
        eng.run_to_completion()
        outs[label] = {r.uid: r.output for r in eng.finished}
    assert outs["spec"] == outs["off"]
    assert len(outs["off"]["r"]) == 28


def test_spec_knob_validation():
    cfg, model, params = _setup()
    with pytest.raises(ValueError):
        ServeEngine(model, params, max_batch=2, max_len=32,
                    speculative="both")
    with pytest.raises(ValueError):
        ServeEngine(model, params, max_batch=2, max_len=32,
                    speculative="ngram", spec_k=0)
    # inert-knob policy: draft params with speculation off are refused
    with pytest.raises(ValueError):
        ServeEngine(model, params, max_batch=2, max_len=32,
                    draft_model=model, draft_params=params)
    with pytest.raises(ValueError):  # draft mode needs the draft model
        ServeEngine(model, params, max_batch=2, max_len=32,
                    speculative="draft")

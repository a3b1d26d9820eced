"""Layered serving engine: allocator/scheduler invariants under
randomized interleaved admission, completion, and preemption (seeded
``random``, not hypothesis — the env lacks it), scheduler policy knobs
(drain refill, prefill token budget), latency accounting, and the
cross-host prefix store (publish on one engine, hydrate on another)."""

import os

os.environ.setdefault("DS_DEBUG_INVARIANTS", "1")

import random

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.core.storage import ObjectStore
from repro.models import Model, ModelRuntime
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefix_store import PrefixStore


def _setup(seed=0):
    cfg = reduced(get_arch("ds-paper-100m"))
    model = Model(cfg, ModelRuntime())
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _random_requests(rng: random.Random, n: int):
    """Mixed workload over two shared one-page prefixes plus fully
    random prompts, random tails/budgets, seeded temperature."""
    prefixes = [[100 + j for j in range(8)], [200 + j for j in range(8)]]
    reqs = []
    for i in range(n):
        kind = rng.randrange(3)
        if kind < 2:  # shared-prefix request
            p = list(prefixes[kind]) + [rng.randrange(1, 99) for _ in range(rng.randrange(0, 5))]
        else:  # cold request
            p = [rng.randrange(1, 99) for _ in range(rng.randrange(1, 13))]
        reqs.append(Request(uid=f"r{i}", prompt=p,
                            max_new_tokens=rng.randrange(1, 5),
                            temperature=0.5))
    return reqs


def _check_allocator_invariants(eng: ServeEngine):
    ps = eng.page_size
    # refcount = slots mapping the page + 1 if the radix cache indexes it
    cached = eng.prefix.pages()
    assert len(set(cached)) == len(cached), "page indexed twice in the radix tree"
    cached_set = set(cached)
    holders = {pid: [] for pid in range(eng.n_pages)}
    for row, pages in enumerate(eng._slot_pages):
        for j, pid in enumerate(pages):
            holders[pid].append((row, j))
    for pid in range(eng.n_pages):
        want = len(holders[pid]) + (1 if pid in cached_set else 0)
        assert eng._page_refs[pid] == want, (
            f"page {pid}: refcount {eng._page_refs[pid]} != holders {want}"
        )
    # free list and referenced pages partition the pool
    assert sorted(eng._free_pages + [p for p in range(eng.n_pages)
                                     if eng._page_refs[p] > 0]) == list(range(eng.n_pages))
    assert eng.pages_in_use == sum(1 for p in range(eng.n_pages) if eng._page_refs[p] > 0)
    # no page aliased across UNRELATED slots: every multi-slot page must
    # back the same page-aligned prompt chunk in each mapping slot
    for pid, maps in holders.items():
        if len(maps) < 2:
            continue
        chunks = []
        for row, j in maps:
            req = eng.slots[row].req
            assert req is not None, f"parked slot {row} still maps page {pid}"
            assert (j + 1) * ps <= len(req.prompt), (
                f"page {pid} shared inside slot {row}'s generated region"
            )
            chunks.append(tuple(req.prompt[j * ps:(j + 1) * ps]))
        assert len(set(chunks)) == 1, (
            f"page {pid} aliased across unrelated slots: {chunks}"
        )


def test_randomized_interleaving_invariants_and_one_shot_parity():
    """Drive the paged prefix-sharing engine through a seeded-random
    interleaving of submits and ticks on a pool tight enough to force
    eviction and preemption; allocator invariants must hold at every
    tick, the drain state must return to the cached-prefix baseline, and
    outputs must be byte-identical to the one-shot static dense batch."""
    cfg, model, params = _setup()
    preempted_somewhere = False
    for seed in (0, 1):
        rng = random.Random(seed)
        reqs = _random_requests(rng, 10)
        # one-shot static-batch oracle: everything submitted up front
        dense = ServeEngine(model, params, max_batch=3, max_len=32,
                            prefill_chunk=4, rng_seed=9)
        dense.submit([Request(uid=r.uid, prompt=list(r.prompt),
                              max_new_tokens=r.max_new_tokens,
                              temperature=r.temperature) for r in reqs])
        dense.run_to_completion()
        want = {r.uid: r.output for r in dense.finished}

        eng = ServeEngine(model, params, max_batch=3, max_len=32,
                          prefill_chunk=4, rng_seed=9,
                          cache_mode="paged", page_size=8, total_pages=5)
        queue = list(reqs)
        steps = 0
        while (queue or eng.pending or eng.scheduler.has_active()) and steps < 500:
            if queue and rng.random() < 0.6:
                eng.submit([queue.pop(0) for _ in range(min(len(queue),
                                                            rng.randrange(1, 4)))])
            eng.step()
            steps += 1
            _check_allocator_invariants(eng)
        assert not queue and not eng.pending
        got = {r.uid: r.output for r in eng.finished}
        assert got == want, f"seed {seed}: staggered paged != one-shot dense"
        # drain baseline: only radix-cached pages remain, each at ref 1
        cached = sorted(eng.prefix.pages())
        assert eng.pages_in_use == len(cached)
        assert all(eng._page_refs[p] == 1 for p in cached)
        preempted_somewhere |= (eng.preemptions + eng.prefix_evictions) > 0
    assert preempted_somewhere, "pool never came under pressure — weak test"


def test_prefill_token_budget_interleaves_and_stays_token_parity():
    """A finite per-tick prefill budget spreads prompt ingestion over
    ticks (more prefill dispatches, mid-prefill rows sit decode out) but
    must not change a single emitted token."""
    cfg, model, params = _setup(2)
    def reqs():
        return [Request(uid=f"r{i}", prompt=list(range(1 + i, 13 + i)),
                        max_new_tokens=3) for i in range(3)]
    free = ServeEngine(model, params, max_batch=2, max_len=32, prefill_chunk=8)
    free.submit(reqs())
    free.run_to_completion()
    budgeted = ServeEngine(model, params, max_batch=2, max_len=32,
                           prefill_chunk=8, prefill_token_budget=4)
    budgeted.submit(reqs())
    budgeted.run_to_completion()
    assert ({r.uid: r.output for r in free.finished}
            == {r.uid: r.output for r in budgeted.finished})
    assert budgeted.prefill_dispatches > free.prefill_dispatches
    assert budgeted.prompt_tokens_ingested == free.prompt_tokens_ingested


def test_prefill_budget_mid_prefill_row_cannot_corrupt_shared_page():
    """Regression: a full-prompt radix hit stranded mid-prefill by the
    tick budget keeps a LIVE page table while sitting the decode out;
    the batch-wide decode write at its position must be copy-on-write
    privatized, or it lands garbage KV in the published shared page and
    every later request stitching that prefix reads it."""
    cfg, model, params = _setup(5)
    PRE16 = [11, 12, 13, 14, 15, 16, 17, 18, 21, 22, 23, 24, 25, 26, 27, 28]
    def drive(eng):
        # warm publishes the prefix, a gets decode-ready ALONE, then b+c
        # are admitted together: the 1-token tick budget leaves one of
        # them stranded mid-prefill on ticks where a's decode dispatches.
        # hazard = a decode ran while a stranded row's next write position
        # sat inside a page someone else still references
        hazard = False
        eng.submit([Request(uid="warm", prompt=list(PRE16), max_new_tokens=2)])
        eng.run_to_completion()
        eng.submit([Request(uid="a", prompt=[1, 2], max_new_tokens=12)])
        for _ in range(5):
            eng.step()
        eng.submit([
            Request(uid="b", prompt=list(range(31, 43)), max_new_tokens=2),
            Request(uid="c", prompt=list(PRE16), max_new_tokens=3),
        ])
        while eng.pending or eng.scheduler.has_active():
            before = eng.decode_dispatches
            eng.step()
            if eng.cache_mode != "paged" or eng.decode_dispatches == before:
                continue
            for s in eng.slots:
                # a decode ran while this row, mid-prefill, had its next
                # write position inside its stitched prefix — the exact
                # window where an unprivatized write corrupts the cache
                if (s.req is not None and s.remaining_prompt
                        and s.pos < s.hit_tokens):
                    hazard = True
        eng.submit([Request(uid="d", prompt=PRE16 + [90, 91], max_new_tokens=3)])
        eng.run_to_completion()
        return {r.uid: r.output for r in eng.finished}, hazard

    want, _ = drive(ServeEngine(model, params, max_batch=3, max_len=32,
                                prefill_chunk=8))
    eng = ServeEngine(model, params, max_batch=3, max_len=32, prefill_chunk=8,
                      prefill_token_budget=1,
                      cache_mode="paged", page_size=8, total_pages=16)
    got, hazard = drive(eng)
    assert hazard, "scenario never stranded a stitched row across a decode"
    assert got == want
    # the hazard was real: c was stitched into the published pages and
    # the decode ticked while it sat mid-prefill
    assert eng.prompt_tokens_skipped > 0
    assert eng.cow_copies > 0


def test_preempted_attempt_latency_samples_are_voided():
    """A preempted request's aborted queue-wait/TTFT samples must not
    survive into the percentiles — only the successful attempts count,
    one pair per request."""
    cfg, model, params = _setup()
    reqs = [Request(uid=f"r{i}", prompt=[10 + i, 20 + i, 30 + i, 40 + i,
                                         50 + i, 60 + i, 70 + i],
                    max_new_tokens=6, temperature=0.5) for i in range(4)]
    tight = ServeEngine(model, params, max_batch=2, max_len=32,
                        prefill_chunk=4, rng_seed=5,
                        cache_mode="paged", page_size=8, total_pages=3)
    tight.submit(reqs)
    tight.run_to_completion()
    assert tight.preemptions > 0, "scenario never forced a preemption"
    t = tight.scheduler.timing()
    assert t["queue_wait_ticks"]["n"] == 4
    assert t["ttft_ticks"]["n"] == 4
    # the voided slots are still in the lists (index-stable windowing)
    assert len(tight.scheduler.queue_waits) > 4
    assert None in tight.scheduler.queue_waits


def test_prefill_budget_fair_share_does_not_starve_short_prompts():
    """Regression: lowest-index-first budget distribution let a long
    prompt in a lower row hold a short prompt hostage for its whole
    ingestion; the fair-share planner must let the short request finish
    while the long prompt is still being ingested."""
    cfg, model, params = _setup(6)
    eng = ServeEngine(model, params, max_batch=2, max_len=64, prefill_chunk=8,
                      prefill_token_budget=4)
    eng.submit([
        Request(uid="long", prompt=list(range(1, 41)), max_new_tokens=2),
        Request(uid="short", prompt=[91, 92, 93, 94], max_new_tokens=2),
    ])
    eng.run_to_completion()
    assert eng.finished[0].uid == "short", (
        "short request starved behind the long prompt's budget"
    )
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid["short"].first_token_tick < by_uid["long"].first_token_tick


def test_prefill_budget_refused_where_it_would_corrupt_or_noop():
    """A finite budget holds rows mid-prefill across decode ticks: on
    recurrent state the batch-wide dispatch would corrupt the held row's
    recurrence, and without the fused prefill path the knob is inert —
    both must be refused at construction."""
    import pytest

    cfg = reduced(get_arch("mamba2-1.3b"))
    model = Model(cfg, ModelRuntime())
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="recurrent"):
        ServeEngine(model, params, max_batch=2, max_len=32, prefill_chunk=4,
                    prefill_token_budget=4)
    cfg2, model2, params2 = _setup()
    with pytest.raises(ValueError, match="fused prefill"):
        ServeEngine(model2, params2, max_batch=2, max_len=32,
                    prefill_chunk=0, prefill_token_budget=4)
    with pytest.raises(ValueError, match="positive"):
        ServeEngine(model2, params2, max_batch=2, max_len=32,
                    prefill_token_budget=0)


def test_trim_samples_bounds_lists_and_remaps_slot_indices():
    from repro.serving.scheduler import RequestScheduler
    from repro.serving.types import EngineStats

    sched = RequestScheduler(2, EngineStats())
    sched.queue_waits = list(range(10))
    sched.ttfts = list(range(5))
    sched.slots[0].wait_idx = 8   # survives the trim -> remapped
    sched.slots[1].wait_idx = 2   # falls off the front -> -1
    sched.slots[0].ttft_idx = 4
    sched.trim_samples(4)
    assert sched.queue_waits == [6, 7, 8, 9]
    assert sched.ttfts == [1, 2, 3, 4]
    assert sched.slots[0].wait_idx == 2 and sched.slots[1].wait_idx == -1
    assert sched.slots[0].ttft_idx == 3
    # the cumulative dropped offsets advance with the trim
    assert sched.waits_dropped == 6 and sched.ttfts_dropped == 1


def test_timing_marks_survive_trim():
    """Regression: a measurement window recorded before trim_samples must
    keep addressing the same samples afterwards.  sample_marks() returns
    absolute sample ids and timing() windows by them, so the per-loop
    trim in the streaming lease cannot silently slide the window."""
    from repro.serving.scheduler import RequestScheduler
    from repro.serving.types import EngineStats

    sched = RequestScheduler(2, EngineStats())
    sched.queue_waits = list(range(10))
    sched.ttfts = list(range(8))
    marks = sched.sample_marks()
    assert marks == {"waits_since": 10, "ttfts_since": 8}
    sched.queue_waits += [100, 200]
    sched.ttfts += [300]
    before = sched.timing(**marks)
    assert before["queue_wait_ticks"]["n"] == 2
    assert before["queue_wait_ticks"]["max"] == 200.0
    assert before["ttft_ticks"]["n"] == 1
    # trim away most of the history; the post-mark samples survive and
    # the window must be unchanged (the old length-relative semantics
    # would have summarized pre-mark samples here)
    sched.trim_samples(3)
    assert sched.timing(**marks) == before
    # marks recorded AFTER a trim keep working too
    marks2 = sched.sample_marks()
    sched.queue_waits.append(7)
    t = sched.timing(**marks2)
    assert t["queue_wait_ticks"]["n"] == 1 and t["queue_wait_ticks"]["max"] == 7.0
    # a window whose samples were entirely trimmed away degrades to the
    # retained suffix instead of crashing or going negative
    assert sched.timing(0, 0)["queue_wait_ticks"]["n"] == 4


class _CacheStub:
    """Minimal KVCacheManager stand-in for scheduler-only tests."""

    def __init__(self):
        self.released = []

    def can_admit(self):
        return True

    def reset_row(self, row):
        pass

    def stitch_prefix(self, row, slot):
        pass

    def release_slot(self, row):
        self.released.append(row)


def test_preempt_for_never_victimizes_the_requester():
    """Regression: pool-exhaustion escalation must never select the
    requesting row as victim — preempting the requester mid-allocation
    released the pages it was assembling and handed its own row back to
    the allocator.  The victim is the youngest slot strictly younger
    than the requester; when the requester is itself the youngest,
    preempt_for answers YIELD without touching anything (the cache
    manager requeues the row only after its allocation loop unwinds)."""
    from repro.serving.scheduler import RequestScheduler
    from repro.serving.types import EngineStats, Request

    sched = RequestScheduler(3, EngineStats())
    sched.cache = _CacheStub()
    sched.submit([Request(uid=f"r{i}", prompt=[1, 2]) for i in range(3)])
    sched.begin_tick()  # admits r0/r1/r2 into rows 0/1/2 (seq order)
    assert all(s.req is not None for s in sched.slots)
    # newest admission (row 2) triggers the escalation: preempt_for must
    # NOT preempt it (nor any older slot) — it answers YIELD and leaves
    # every slot untouched
    assert sched.preempt_for(2) == RequestScheduler.YIELD
    assert all(s.req is not None for s in sched.slots)
    assert sched.stats.preemptions == 0 and not sched.pending
    # escalation from the OLDEST slot preempts the youngest other
    assert sched.preempt_for(0) == 2
    assert sched.pending and sched.pending[0].uid == "r2"
    assert sched.preempt_for(0) == 1
    # nothing younger left active: requester row 0 must not preempt
    # itself; with no other slot active at all the answer is None (the
    # allocator raises — a lone request that cannot fit fails loudly)
    assert sched.preempt_for(0) is None
    assert sched.slots[0].req is not None and sched.stats.preemptions == 2


def test_pool_exhaustion_from_newest_admission_yields_cleanly():
    """End-to-end regression for the same bug: a late-arriving request
    whose prefill exhausts the pool while it is the youngest slot used
    to be preempted by preempt_for MID-allocation.  It must now yield at
    the clean seam instead — preempt_for never returns the requester,
    the requeue happens after the allocation loop unwinds — while the
    older slot keeps its pages (age priority, no inversion livelock);
    outputs stay byte-identical to the dense engine."""
    cfg, model, params = _setup(7)
    def drive(eng):
        # "old" runs alone for a few ticks (its 8 total tokens fit one
        # page), then "new" arrives with a 20-token prompt whose
        # single-tick chunked prefill wants 3 pages — exhausting the
        # 3-page pool while "new" is the youngest active slot
        eng.submit([Request(uid="old", prompt=[1, 2], max_new_tokens=6,
                            temperature=0.5)])
        for _ in range(2):
            eng.step()
        eng.submit([Request(uid="new", prompt=list(range(10, 30)),
                            max_new_tokens=4, temperature=0.5)])
        eng.run_to_completion(max_steps=200)
        return {r.uid: r.output for r in eng.finished}

    dense = ServeEngine(model, params, max_batch=2, max_len=32,
                        prefill_chunk=8, rng_seed=5)
    want = drive(dense)
    tight = ServeEngine(model, params, max_batch=2, max_len=32,
                        prefill_chunk=8, rng_seed=5,
                        cache_mode="paged", page_size=8, total_pages=3)
    preempted, escalations = [], []
    orig_preempt = tight.scheduler.preempt
    def preempt_spy(row):
        preempted.append(tight.slots[row].req.uid)
        orig_preempt(row)
    tight.scheduler.preempt = preempt_spy
    orig_for = tight.scheduler.preempt_for
    def for_spy(row):
        out = orig_for(row)
        escalations.append((row, out))
        return out
    tight.cache_mgr.preempt_for = for_spy
    got = drive(tight)
    assert escalations, "scenario never escalated to the scheduler"
    # preempt_for never selects the requesting row as victim
    assert all(victim != row for row, victim in escalations)
    # the newest slot yielded (requeued at the seam) at least once...
    from repro.serving.scheduler import RequestScheduler
    assert any(v == RequestScheduler.YIELD for _, v in escalations)
    assert "new" in preempted and tight.preemptions > 0
    # ...and never dragged the older slot down with it (age priority)
    assert "old" not in preempted, (
        "the newcomer inverted age priority by preempting the older slot"
    )
    assert got == want, "yield under exhaustion changed emitted tokens"
    assert len(got) == 2
    assert all(r >= 0 for r in tight._page_refs)


def test_prefix_store_refused_where_it_would_be_inert(tmp_path):
    """The cross-host store moves bytes only through the radix cache
    over paged pool pages; configurations where it could never act are
    refused, not silently accepted."""
    import pytest

    cfg, model, params = _setup()
    store = PrefixStore(ObjectStore(str(tmp_path / "s")), "ns")
    with pytest.raises(ValueError, match="prefix_store"):
        ServeEngine(model, params, max_batch=1, max_len=32,
                    prefix_store=store)  # dense cache
    with pytest.raises(ValueError, match="prefix_store"):
        ServeEngine(model, params, max_batch=1, max_len=32,
                    cache_mode="paged", page_size=8, total_pages=4,
                    prefix_cache=False, prefix_store=store)


def test_percentiles_nearest_rank_and_voided_samples():
    from repro.serving.types import percentiles

    p = percentiles([1, 2, 3, 4, 5, 6, 7, 8, 9, 500])
    assert p["n"] == 10
    assert p["p90"] == 9.0, "p90 of 10 samples is rank 9, not the max"
    assert p["p50"] == 5.0 and p["max"] == 500.0
    assert percentiles([None, 5, None])["n"] == 1
    assert percentiles([None])["n"] == 0


def test_scheduler_records_queue_wait_and_ttft():
    cfg, model, params = _setup(3)
    eng = ServeEngine(model, params, max_batch=1, max_len=32)
    eng.submit([Request(uid="a", prompt=[1, 2], max_new_tokens=2),
                Request(uid="b", prompt=[3, 4], max_new_tokens=2)])
    eng.run_to_completion()
    t = eng.scheduler.timing()
    assert t["queue_wait_ticks"]["n"] == 2 and t["ttft_ticks"]["n"] == 2
    # b waited behind a in the single slot
    assert t["queue_wait_ticks"]["max"] > t["queue_wait_ticks"]["p50"] or (
        eng.scheduler.queue_waits[1] > eng.scheduler.queue_waits[0]
    )
    for r in eng.finished:
        assert r.admit_tick >= 0 and r.first_token_tick >= r.admit_tick
        assert r.done_tick >= r.first_token_tick


# ------------------------------------------------- cross-host prefix store
PREFIX = [11, 12, 13, 14, 15, 16, 17, 18, 21, 22, 23, 24, 25, 26, 27, 28]


def test_prefix_store_publish_then_hydrate_across_engines(tmp_path):
    """Engine A (worker 1) publishes a completed prompt's pages to the
    object store; a COLD engine B (worker 2, empty radix cache) must
    hydrate them at admission, skip those prefill tokens, and still be
    byte-identical to a dense run."""
    cfg, model, params = _setup()
    store = ObjectStore(str(tmp_path / "store"))
    def mk(ns="ns"):
        return ServeEngine(model, params, max_batch=2, max_len=32,
                           prefill_chunk=4, rng_seed=7,
                           cache_mode="paged", page_size=8, total_pages=10,
                           prefix_store=PrefixStore(store, ns))
    a = mk()
    a.submit([Request(uid="warm", prompt=PREFIX + [50], max_new_tokens=2)])
    a.run_to_completion()
    assert a.prefix_store_pages_published == 2  # both full chunks
    assert a.prefix_store_pages_hydrated == 0  # nothing to pull: it was first

    dense = ServeEngine(model, params, max_batch=2, max_len=32,
                        prefill_chunk=4, rng_seed=7)
    dense.submit([Request(uid="cold", prompt=PREFIX + [60, 61], max_new_tokens=4)])
    want = dense.run_to_completion()[0].output

    b = mk()
    b.submit([Request(uid="cold", prompt=PREFIX + [60, 61], max_new_tokens=4)])
    got = b.run_to_completion()[0].output
    assert got == want
    assert b.prefix_store_pages_hydrated == 2
    assert b.prefix_store_tokens_hydrated == 16
    assert b.prompt_tokens_skipped == 16  # hydrated pages were stitched
    # republication is suppressed: the pages are already content-addressed
    assert b.prefix_store_pages_published == 0
    # local drain invariants hold with hydrated pages in the tree
    assert b.pages_in_use == len(b.prefix.pages())


def test_prefix_store_ttl_sweep(tmp_path):
    """sweep(ttl_s) deletes pages older than the TTL by object mtime and
    leaves fresh ones; ttl 0 clears the prefix.  Closes the 'store grows
    until an operator sweeps' caveat."""
    import os
    import time

    store = ObjectStore(str(tmp_path / "store"))
    ps = PrefixStore(store, "ns")
    page = {"k": np.zeros((2, 2), np.float32)}
    old_key, new_key = "aa" * 32, "bb" * 32
    ps.publish(old_key, page)
    ps.publish(new_key, page)
    # age one object by rewinding its filesystem mtime 1000 s
    old_path = os.path.join(store.root, ps._object_key(old_key))
    past = time.time() - 1000.0
    os.utime(old_path, (past, past))
    assert ps.sweep(500.0) == 1
    assert not ps.exists(old_key) and ps.exists(new_key)
    # explicit ``now`` pins the clock (deterministic TTL arithmetic)
    head = store.head(ps._object_key(new_key))
    assert ps.sweep(100.0, now=head.mtime + 50.0) == 0
    assert ps.sweep(100.0, now=head.mtime + 200.0) == 1
    assert list(store.list("kvprefix/")) == []
    # an empty prefix sweeps to zero, not an error
    assert ps.sweep(0.0) == 0


def test_async_publisher_flush_errors_and_restart(tmp_path):
    """The background publisher's contract: flush() blocks until every
    submitted write was attempted; a failing put is counted + dropped
    without killing the worker; close() is restartable (a later submit
    spins the worker back up)."""
    store = ObjectStore(str(tmp_path / "store"))
    ps = PrefixStore(store, "ns")
    pub = ps.publisher()
    page = {"k": np.arange(4, dtype=np.float32).reshape(2, 2)}

    pub.submit("aa" * 32, page)
    pub.submit("bb" * 32, page)
    pub.flush()
    for key in ("aa" * 32, "bb" * 32):
        got = ps.fetch(key, like=page)
        assert got is not None and np.array_equal(got["k"], page["k"])

    # a raising put is logged + dropped; the worker thread survives
    real_publish = ps.publish
    def boom(key, arrays):
        raise OSError("store down")
    ps.publish = boom
    pub.submit("cc" * 32, page)
    pub.flush()
    assert pub.errors == 1 and not ps.exists("cc" * 32)
    ps.publish = real_publish
    pub.submit("dd" * 32, page)  # same worker, next write succeeds
    pub.flush()
    assert ps.exists("dd" * 32)

    # close() drains and stops the worker but the publisher is reusable
    pub.close()
    assert pub._thread is None
    pub.submit("ee" * 32, page)
    pub.close()
    assert ps.exists("ee" * 32)


def test_prefix_store_namespace_isolation(tmp_path):
    """Different namespaces (different params identity) must never share
    pages: engine C under another namespace sees a cold store."""
    cfg, model, params = _setup()
    store = ObjectStore(str(tmp_path / "store"))
    a = ServeEngine(model, params, max_batch=1, max_len=32, prefill_chunk=4,
                    cache_mode="paged", page_size=8, total_pages=8,
                    prefix_store=PrefixStore(store, "model-A"))
    a.submit([Request(uid="w", prompt=list(PREFIX), max_new_tokens=2)])
    a.run_to_completion()
    assert a.prefix_store_pages_published > 0
    c = ServeEngine(model, params, max_batch=1, max_len=32, prefill_chunk=4,
                    cache_mode="paged", page_size=8, total_pages=8,
                    prefix_store=PrefixStore(store, "model-B"))
    c.submit([Request(uid="x", prompt=list(PREFIX), max_new_tokens=2)])
    c.run_to_completion()
    assert c.prefix_store_pages_hydrated == 0
    assert c.prefix_store_pages_published > 0  # published under its own keys


def test_prefix_store_rejects_incompatible_payload(tmp_path):
    """A blob that does not match the pool's leaf shapes (colliding
    namespace from another config) is a miss, not a crash/corruption."""
    cfg, model, params = _setup()
    store = ObjectStore(str(tmp_path / "store"))
    ps_store = PrefixStore(store, "shared-ns")
    # forge an incompatible page under the key engine B will look up
    key = ps_store.child_key(ps_store.root_key(), PREFIX[:8])
    store.put_bytes(f"kvprefix/{key[:2]}/{key}",
                    PrefixStore.pack({"k_pages": np.zeros((1, 2), np.float32)}))
    # and a truncated/garbage blob (e.g. a partially swept object) under
    # the SECOND chunk's key: hydration stops there, no crash
    key2 = ps_store.child_key(key, PREFIX[8:16])
    store.put_bytes(f"kvprefix/{key2[:2]}/{key2}", b"not an npz")
    # and a PK-magic-but-truncated npz (a partially written object whose
    # zip central directory is gone): np.load raises zipfile.BadZipFile,
    # which is neither ValueError nor OSError — must be a miss, not a
    # worker crash
    valid = PrefixStore.pack({"k": np.zeros((2, 2), np.float32)})
    assert valid[:2] == b"PK"
    key3 = ps_store.child_key(ps_store.root_key(), [77] * 8)
    store.put_bytes(f"kvprefix/{key3[:2]}/{key3}", valid[:20])
    assert ps_store.fetch(
        key3, {"k": np.zeros((2, 2), np.float32)}
    ) is None
    b = ServeEngine(model, params, max_batch=1, max_len=32, prefill_chunk=4,
                    cache_mode="paged", page_size=8, total_pages=8,
                    prefix_store=PrefixStore(store, "shared-ns"))
    b.submit([Request(uid="x", prompt=list(PREFIX), max_new_tokens=2),
              Request(uid="y", prompt=[77] * 8 + [1, 2], max_new_tokens=2)])
    b.run_to_completion()
    assert b.prefix_store_pages_hydrated == 0

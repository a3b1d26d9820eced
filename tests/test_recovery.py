"""Work-preserving recovery: generation checkpoints survive a crash at
*every* tick of a seeded workload byte-identically (crash-point sweep),
the checkpoint fallback ladder degrades to full replay on missing /
corrupt / mismatched records, the async publisher and the serve-side
retry helper ride out transient storage faults, the prefix store rejects
content-hash mismatches as counted misses, and the chaos monkey's
flaky_storage / flaky_queue windows inject exactly the transient
``ConnectionError`` discipline the serving tier retries against."""

import os

os.environ.setdefault("DS_DEBUG_INVARIANTS", "1")

import random
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import DurableQueue, FleetFile, VirtualClock
from repro.core.chaos import ChaosEvent, ChaosMonkey
from repro.core.fleet import SpotFleet
from repro.core.queue import install_fault_hook, remove_fault_hook
from repro.core.storage import ObjectStore
from repro.launch.serve import (
    _checkpoint_valid,
    _seal_checkpoint,
    _try_resume,
    _uid_safe,
    _with_retries,
)
from repro.models import Model, ModelRuntime
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefix_store import AsyncPublisher, PrefixStore

import fleet_drills


def _setup(seed=0):
    cfg = reduced(get_arch("ds-paper-100m"))
    model = Model(cfg, ModelRuntime())
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _workload(rng: random.Random, n: int):
    """Mixed sampled workload over a shared one-page prefix plus cold
    prompts — temperature > 0 so byte-identical resumption genuinely
    depends on the preserved sampling-stream position, not on greedy
    argmax hiding a stream reset."""
    prefix = [100 + j for j in range(8)]
    reqs = []
    for i in range(n):
        if rng.randrange(3) < 2:
            p = list(prefix) + [rng.randrange(1, 99)
                                for _ in range(rng.randrange(0, 4))]
        else:
            p = [rng.randrange(1, 99) for _ in range(rng.randrange(1, 11))]
        reqs.append(Request(uid=f"r{i}", prompt=p,
                            max_new_tokens=rng.randrange(2, 5),
                            temperature=0.5))
    return reqs


def _clones(reqs):
    return [Request(uid=r.uid, prompt=list(r.prompt),
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature) for r in reqs]


def _engine(model, params, ps):
    return ServeEngine(model, params, max_batch=2, max_len=32,
                       prefill_chunk=4, rng_seed=7,
                       cache_mode="paged", page_size=8, total_pages=10,
                       prefix_cache=True, prefix_store=ps)


# ------------------------------------------------------ crash-point sweep
def test_crash_point_sweep_every_tick_byte_identical(tmp_path):
    """Revoke the worker at EVERY tick index of a seeded workload:
    checkpoint whatever slots are checkpointable, preempt everything,
    hand the survivors to a fresh engine over the same object store
    (resumes via ``submit_resume``, the rest as full replays), and the
    combined outputs must be byte-identical to an uninterrupted run with
    zero lost requests — while ``DS_DEBUG_INVARIANTS=1`` asserts
    refcount == holders after every tick of both engines."""
    _, model, params = _setup()
    reqs = _workload(random.Random(3), 6)
    store = ObjectStore(str(tmp_path / "store"))

    # uninterrupted oracle on the SAME engine config (also measures the
    # total tick count the sweep walks)
    oracle = _engine(model, params, PrefixStore(store, "sweep"))
    oracle.submit(_clones(reqs))
    oracle.run_to_completion()
    want = {r.uid: list(r.output) for r in oracle.finished}
    n_ticks = oracle.scheduler.tick
    assert len(want) == len(reqs) and n_ticks > 3

    total_resumes = total_recovered = 0
    for t in range(1, n_ticks):
        ps_a = PrefixStore(store, "sweep")
        a = _engine(model, params, ps_a)
        a.submit(_clones(reqs))
        for _ in range(t):
            a.step()
        done_a = {r.uid: list(r.output) for r in a.finished}

        # the revocation drain: checkpoint every active slot that has
        # emitted anything, then preempt it back to pending
        ckpts = {}
        for row, slot in enumerate(a.scheduler.slots):
            if slot.req is None:
                continue
            ck = a.checkpoint_slot(row)
            if ck is not None:
                ckpts[ck["uid"]] = ck
            a.scheduler.preempt(row)
        a.cache_mgr.flush_store()  # published pages durable before handoff
        a.cache_mgr.check_invariants()
        survivors = list(a.scheduler.pending)
        assert len(done_a) + len(survivors) == len(reqs), "request lost at drain"

        b = _engine(model, params, PrefixStore(store, "sweep"))
        for r in survivors:
            if r.uid in ckpts:
                b.submit_resume(ckpts[r.uid])
            else:
                # a replayed request re-enters the fleet through the queue
                # with a fresh local stream; temperature > 0 here, so pin
                # the original stream the oracle drew (what the greedy
                # production path gets for free) to isolate KV correctness
                clone = _clones([r])[0]
                b.submit([clone])
                clone.sample_stream = r.sample_stream
        b.run_to_completion()
        b.cache_mgr.check_invariants()

        got = dict(done_a)
        got.update({r.uid: list(r.output) for r in b.finished})
        assert got == want, f"crash at tick {t} diverged"
        assert b.stats.checkpoint_resumes == len(ckpts)
        total_resumes += b.stats.checkpoint_resumes
        total_recovered += b.stats.tokens_recovered
        assert b.stats.tokens_recovered == sum(
            len(c["output"]) - 1 for c in ckpts.values())

    # the sweep must actually have exercised mid-decode resumption
    assert total_resumes > 0 and total_recovered > 0


# --------------------------------------------------- checkpoint fallback
def _ctx(tmp_path):
    return SimpleNamespace(store=ObjectStore(str(tmp_path / "ctx")),
                           clock=VirtualClock())


def _mid_decode_checkpoint(model, params, store, req):
    """Run a request partway, checkpoint it, and return (sealed record,
    the oracle's full output)."""
    oracle = _engine(model, params, PrefixStore(store, "ladder"))
    oracle.submit(_clones([req]))
    oracle.run_to_completion()
    want = list(oracle.finished[0].output)
    assert len(want) >= 3

    a = _engine(model, params, PrefixStore(store, "ladder"))
    a.submit(_clones([req]))
    while (a.scheduler.slots[0].req is None
           or len(a.scheduler.slots[0].req.output) < 2):
        a.step()  # admission happens at the first tick
    ck = a.checkpoint_slot(0)
    assert ck is not None and 2 <= len(ck["output"]) < len(want)
    a.cache_mgr.flush_store()
    return _seal_checkpoint(ck), want


def test_fallback_ladder_missing_corrupt_and_mismatched(tmp_path):
    """Rung one resumes byte-identically from a sealed checkpoint; a
    missing, bit-flipped, or request-mismatched record is a counted
    ``checkpoint_fallback`` and the full replay still lands on the
    oracle's exact tokens."""
    _, model, params = _setup()
    store = ObjectStore(str(tmp_path / "store"))
    ctx = _ctx(tmp_path)
    req = Request(uid="lad/0", prompt=[5, 6, 7, 8, 9], max_new_tokens=4,
                  temperature=0.5)
    sealed, want = _mid_decode_checkpoint(model, params, store, req)
    prefix = "serve/x/checkpoints/"
    key = f"{prefix}{_uid_safe(req.uid)}.json"
    assert "/" not in _uid_safe(req.uid)[4:]  # uid slash never splits the key

    # rung one: valid checkpoint -> mid-decode resume, byte-identical
    ctx.store.put_json(key, sealed)
    b = _engine(model, params, PrefixStore(store, "ladder"))
    assert _try_resume(b, ctx, prefix, _clones([req])[0]) is not None
    b.run_to_completion()
    assert list(b.finished[0].output) == want
    assert b.stats.checkpoint_resumes == 1 and b.stats.checkpoint_fallbacks == 0
    assert b.stats.tokens_recovered == len(sealed["output"]) - 1

    # sha seal: tampering any field (or resealing a record that no longer
    # matches the queue message) fails validation
    flipped = dict(sealed, output=[sealed["output"][0] + 1]
                   + sealed["output"][1:])
    assert not _checkpoint_valid(flipped, _clones([req])[0])
    wrong_req = dict(sealed)
    wrong_req.pop("sha")
    wrong_req = _seal_checkpoint(dict(wrong_req, max_new_tokens=99))
    assert not _checkpoint_valid(wrong_req, _clones([req])[0])

    for label, record in (("missing", None), ("corrupt", flipped),
                          ("mismatched", wrong_req)):
        if record is None:
            ctx.store.delete(key)
        else:
            ctx.store.put_json(key, record)
        c = _engine(model, params, PrefixStore(store, "ladder"))
        clone = _clones([req])[0]
        assert _try_resume(c, ctx, prefix, clone) is None, label
        assert c.stats.checkpoint_fallbacks == 1 and c.stats.checkpoint_resumes == 0
        c.submit([clone])  # rungs two/three: replay (store pages may stitch)
        c.run_to_completion()
        assert list(c.finished[0].output) == want, label


# ------------------------------------------------------- async publisher
def _page_arrays():
    return {"k": np.arange(8, dtype=np.float32).reshape(2, 4),
            "v": np.ones((2, 4), np.float32)}


def test_async_publisher_retries_transient_faults(tmp_path):
    ps = PrefixStore(ObjectStore(str(tmp_path / "s")), "pub")
    page = ps.child_key(ps.root_key(), [1, 2, 3])
    real_publish, fails = ps.publish, {"n": 2}

    def flaky(page_key, arrays):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise ConnectionError("transient put")
        real_publish(page_key, arrays)

    ps.publish = flaky
    pub = AsyncPublisher(ps, max_attempts=4, retry_base=0.0, retry_cap=0.0)
    pub.submit(page, _page_arrays())
    pub.flush()
    assert pub.retries == 2 and pub.errors == 0
    assert ps.exists(page)
    assert ps.fetch(page, _page_arrays()) is not None
    pub.close()


def test_async_publisher_gives_up_after_max_attempts(tmp_path):
    ps = PrefixStore(ObjectStore(str(tmp_path / "s")), "pub")
    page = ps.child_key(ps.root_key(), [4, 5, 6])

    def always_down(page_key, arrays):
        raise ConnectionError("store down")

    ps.publish = always_down
    pub = AsyncPublisher(ps, max_attempts=3, retry_base=0.0, retry_cap=0.0)
    pub.submit(page, _page_arrays())
    pub.flush()
    # every attempt but the last counts as a retry; the final failure is
    # a dropped page (cold for other workers), never an exception
    assert pub.retries == 2 and pub.errors == 1
    assert not ps.exists(page)
    pub.close()
    with pytest.raises(ValueError):
        AsyncPublisher(ps, max_attempts=0)


# ------------------------------------------------- content-hash verification
def test_fetch_rejects_hash_mismatch_as_counted_miss(tmp_path):
    ps = PrefixStore(ObjectStore(str(tmp_path / "s")), "hash")
    like = _page_arrays()
    page = ps.child_key(ps.root_key(), [7, 8, 9])
    other = ps.child_key(ps.root_key(), [10, 11, 12])
    ps.publish(page, like)
    assert ps.fetch(page, like) is not None and ps.hash_mismatches == 0

    # blob whose digest binds it to a DIFFERENT key (wrong-content
    # overwrite / blob copied under the wrong key)
    ps.store.put_bytes(ps._object_key(page),
                       PrefixStore.pack(like, page_key=other))
    assert ps.fetch(page, like) is None and ps.hash_mismatches == 1

    # legacy/digest-less blob: also rejected (no binding to verify)
    ps.store.put_bytes(ps._object_key(page), PrefixStore.pack(like))
    assert ps.fetch(page, like) is None and ps.hash_mismatches == 2

    # republishing heals the key
    ps.publish(page, like)
    got = ps.fetch(page, like)
    assert got is not None and np.array_equal(got["k"], like["k"])
    assert ps.hash_mismatches == 2


# ----------------------------------------------------- chaos flaky faults
def _fleet(clk, name):
    return SpotFleet(FleetFile(startup_seconds=0.0), clock=clk, app_name=name)


def test_flaky_storage_faults_first_attempt_per_key_within_scope(tmp_path):
    clk = VirtualClock()
    store = ObjectStore(str(tmp_path / "store"))
    chaos = ChaosMonkey(_fleet(clk, "Flaky"), clk, store=store, events=[
        ChaosEvent(kind="flaky_storage", at=0.0, duration=60.0,
                   scope="serve/"),
    ])
    assert [r.kind for r in chaos.tick()] == ["flaky_storage"]

    store.put_bytes("other/x", b"ok")  # outside scope: untouched
    with pytest.raises(ConnectionError):
        store.put_bytes("serve/a", b"1")
    store.put_bytes("serve/a", b"1")  # second attempt on the key succeeds
    with pytest.raises(ConnectionError):
        store.get_bytes("serve/a")  # get is a distinct (op, key) token
    assert store.get_bytes("serve/a") == b"1"
    assert chaos.counters["storage_faults"] == 2

    # the serve-side retry helper rides straight through the window on a
    # fresh key: one transient fault, retried, data lands
    _with_retries(lambda: store.put_bytes("serve/c", b"3"),
                  key="serve/c", clock=clk)
    assert chaos.counters["storage_faults"] == 3
    # window expiry: the wrapper stays installed but passes through
    clk.sleep(120.0)
    assert store.get_bytes("serve/c") == b"3"
    store.put_bytes("serve/b", b"2")
    assert chaos.counters["storage_faults"] == 3  # nothing new after expiry


def test_flaky_queue_hook_faults_consumer_ops_once_each(tmp_path):
    clk = VirtualClock()
    q = DurableQueue(str(tmp_path / "q.sqlite"), clock=clk)
    q.send_batch([{"i": i} for i in range(3)])
    chaos = ChaosMonkey(_fleet(clk, "FlakyQ"), clk, queue=q, events=[
        ChaosEvent(kind="flaky_queue", at=0.0, duration=30.0),
    ])
    assert [r.kind for r in chaos.tick()] == ["flaky_queue"]

    q.send({"i": 99})  # the producer side is never faulted
    with pytest.raises(ConnectionError):
        q.receive()
    m = q.receive()  # first retry succeeds: no message is ever lost
    assert m is not None
    with pytest.raises(ConnectionError):
        q.delete(m)
    assert q.delete(m)
    assert chaos.counters["queue_faults"] == 2

    # the hook reaches EVERY handle on the same sqlite file (workers open
    # their own), keyed by absolute path — and a second window re-arms
    other_handle = DurableQueue(q.path, clock=clk)
    chaos._arm_flaky_queue(ChaosEvent(kind="flaky_queue", at=0.0,
                                      duration=30.0), clk.now())
    with pytest.raises(ConnectionError):
        other_handle.receive()
    assert other_handle.receive() is not None
    remove_fault_hook(q.path)
    assert q.receive() is not None  # unhooked: clean


def test_queue_fault_hook_registry_is_per_path(tmp_path):
    clk = VirtualClock()
    q1 = DurableQueue(str(tmp_path / "a.sqlite"), clock=clk)
    q2 = DurableQueue(str(tmp_path / "b.sqlite"), clock=clk)
    q1.send({"x": 1})
    q2.send({"x": 2})
    calls = []
    install_fault_hook(q1.path, lambda op, path: calls.append((op, path)))
    try:
        assert q1.receive() is not None
        assert q2.receive() is not None  # other path: hook never consulted
    finally:
        remove_fault_hook(q1.path)
    assert [op for op, _ in calls] == ["receive"]


def test_with_retries_exhausts_then_raises_and_misses_propagate():
    clk = VirtualClock()
    calls = {"n": 0}

    def always_flaky():
        calls["n"] += 1
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        _with_retries(always_flaky, key="k", clock=clk, attempts=3)
    assert calls["n"] == 3

    def miss():
        calls["n"] += 1
        raise FileNotFoundError("no such key")

    calls["n"] = 0
    with pytest.raises(FileNotFoundError):
        _with_retries(miss, key="k", clock=clk, attempts=3)
    assert calls["n"] == 1  # a miss is not transient: no retry burned


# ------------------------------------------------------------ recovery drill
@pytest.fixture(scope="module")
def recovery(tmp_path_factory):
    """Three fleets through the same revocations and flaky storage/queue
    windows: checkpoints off, on, and on but unreadable."""
    return fleet_drills.recovery_drill(str(tmp_path_factory.mktemp("recovery")))


def test_recovery_drill_all_legs_lossless(recovery):
    for leg in ("replay", "checkpoint", "sabotage"):
        r = recovery[leg]
        assert r["lost_requests"] == 0, leg
        assert r["byte_identical"] is True, leg
        assert r["revocations_injected"] >= 2, leg
        # the flaky windows actually fired and retries rode them out
        assert r["storage_faults"] > 0 and r["queue_faults"] > 0, leg


def test_recovery_drill_checkpoint_resumes_sabotage_falls_back(recovery):
    """Checkpointed drains hand their emitted tail to the resuming worker
    instead of re-decoding it (>= 3x fewer tokens re-decoded than full
    replay); with every record unreadable the fleet walks the fallback
    ladder to replay instead."""
    replay, ckpt, sab = (recovery[k] for k in ("replay", "checkpoint", "sabotage"))
    assert replay["checkpoints_published"] == 0
    assert replay["tokens_redecoded"] > 0  # a generation was interrupted
    assert ckpt["checkpoints_published"] > 0
    assert ckpt["checkpoint_resumes"] > 0
    assert ckpt["tokens_recovered"] > 0
    assert replay["tokens_redecoded"] >= 3 * max(ckpt["tokens_redecoded"], 1)
    assert sab["checkpoint_fallbacks"] > 0
    assert sab["checkpoint_resumes"] == 0

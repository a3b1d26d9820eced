"""Disaggregated prefill/decode serving, layer by layer: role-aware
scheduler admission contracts, engine role validation, the role-split
autoscaler policy, publisher-side dedup of pending page keys, pin-aware
TTL sweeps, and the sweep-races-a-handoff regression (byte-identical
fallback when the store lies)."""

import os
import threading
import time

os.environ.setdefault("DS_DEBUG_INVARIANTS", "1")

import jax
import numpy as np
import pytest

import repro.launch.serve  # noqa: F401  (registers distributed-serve)
import repro.launch.train  # noqa: F401
from repro.core import DSConfig, FleetFile, VirtualClock
from repro.core.autoscaler import Autoscaler, ProgressBoard
from repro.core.cluster import ECSCluster, Service, TaskDefinition
from repro.core.fleet import SpotFleet
from repro.core.queue import DurableQueue
from repro.core.storage import ObjectStore
from repro.launch.train import build_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefix_store import PrefixStore
from repro.serving.scheduler import RequestScheduler
from repro.serving.types import EngineStats

import fleet_drills

JOB = {"arch": "ds-paper-100m", "arch_overrides": "reduced"}
PAGE = 8


@pytest.fixture(scope="module")
def model_params():
    model = build_model(JOB)
    return model, model.init(jax.random.PRNGKey(0))


# ------------------------------------------------- scheduler role gates
def test_decode_scheduler_refuses_fresh_prefill_work():
    sched = RequestScheduler(2, EngineStats(), role="decode")
    with pytest.raises(RuntimeError, match="refuses fresh prefill work"):
        sched.submit([Request(uid="a", prompt=[1, 2])])
    assert sched.pending == []
    # sealed handoffs are the only admissible work, and they keep the
    # prefill worker's stream id (fresh assignment would collide)
    req = Request(uid="h", prompt=[1, 2, 3])
    req.sample_stream = 5
    sched.submit_handoff(req)
    assert sched.pending[-1] is req and req.handoff
    assert sched._n_submitted == 6


def test_prefill_scheduler_refuses_handoff_admissions():
    sched = RequestScheduler(2, EngineStats(), role="prefill")
    with pytest.raises(RuntimeError, match="refuses handoff"):
        sched.submit_handoff(Request(uid="h", prompt=[1, 2]))
    sched.submit([Request(uid="a", prompt=[1, 2])])  # fresh work is fine
    assert len(sched.pending) == 1


def test_scheduler_role_validation():
    with pytest.raises(ValueError, match="role"):
        RequestScheduler(2, EngineStats(), role="verifier")


# ------------------------------------------------- engine role validation
def test_engine_role_validation(tmp_path, model_params):
    model, params = model_params
    ps = PrefixStore(ObjectStore(str(tmp_path / "store")), "ns")
    paged = dict(cache_mode="paged", page_size=PAGE, prefix_store=ps)
    with pytest.raises(ValueError, match="worker_role"):
        ServeEngine(model, params, worker_role="draft", **paged)
    # a storage-mediated handoff without storage is refused up front
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="prefix_store"):
            ServeEngine(model, params, worker_role=role)
    # a prefill worker has no decode ticks: chunked prefill is mandatory
    # and speculative decoding can never run
    with pytest.raises(ValueError, match="chunked-prefill"):
        ServeEngine(model, params, worker_role="prefill",
                    prefill_chunk=0, **paged)
    with pytest.raises(ValueError, match="speculative"):
        ServeEngine(model, params, worker_role="prefill",
                    speculative="ngram", **paged)


# --------------------------------------------- role-split autoscaler
def _scaler(tmp_path, clk, **over):
    cfg = DSConfig(
        app_name="Split", cluster_machines=1,
        machine_type=["sim.large"], machine_price=1.0,
        autoscale="slo", min_workers=1, max_workers=10,
        autoscale_queue_per_worker=4, autoscale_target_p99_ttft=10.0,
        autoscale_up_cooldown_seconds=60.0,
        autoscale_down_cooldown_seconds=600.0,
        autoscale_max_step=2, monitor_poll_seconds=60.0, **over,
    )
    queue = DurableQueue(str(tmp_path / "jobs.sqlite"), clock=clk)
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="Split")
    fleet.request(target_capacity=1, bid=1.0, machine_types=["sim.large"])
    cluster = ECSCluster()
    cluster.register_service(Service(
        name="SplitService",
        task_definition=TaskDefinition.from_config(cfg),
        desired_count=1,
    ))
    board = ProgressBoard()
    return Autoscaler(cfg, queue, fleet, cluster, clock=clk,
                      board=board), board, fleet


def test_role_split_autoscaler_sizes_pools_independently(tmp_path):
    clk = VirtualClock()
    asc, board, fleet = _scaler(tmp_path, clk)

    # per-role demand: prefill off the request-queue backlog, decode off
    # the decode-queue backlog; the fleet target is the sum
    board.put("w_pre", {"kind": "serve", "role": "prefill", "backlog": 8},
              clk.now())
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 4,
                        "active": 0, "p99_ttft": 0.0}, clk.now())
    d = asc.tick()
    assert d.desired == 3 and d.applied
    assert "role-split prefill=2 decode=1" in d.reason
    assert fleet.target_capacity == 3

    # decode SLO breach steps the decode pool up past its queue-depth
    # answer; the prefill share rides on top
    clk.sleep(60.0)
    board.put("w_pre", {"kind": "serve", "role": "prefill", "backlog": 0},
              clk.now())
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 0,
                        "active": 0, "p99_ttft": 25.0}, clk.now())
    d = asc.tick()
    assert d.desired == 4 and d.applied
    assert "decode slo breach" in d.reason and "prefill=1" in d.reason

    # hysteresis: decode p99 inside (target/2, target] holds the fleet
    # instead of shrinking both pools into a breach
    clk.sleep(60.0)
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 0,
                        "active": 0, "p99_ttft": 7.0}, clk.now())
    d = asc.tick()
    assert d.desired == 4 and "decode slo hold" in d.reason

    # active-slot pressure sizes the decode pool even with an empty queue
    clk.sleep(60.0)
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 0,
                        "active": 12, "p99_ttft": 0.0}, clk.now())
    d = asc.tick()
    assert "role-split prefill=1 decode=3" in d.reason and d.desired == 4

    # a mixed fleet sizes its unified share exactly like the legacy policy
    clk.sleep(60.0)
    board.put("w_pre", {"kind": "serve", "role": "prefill", "backlog": 0},
              clk.now())
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 0,
                        "active": 0, "p99_ttft": 0.0}, clk.now())
    board.put("w_uni", {"kind": "serve", "backlog": 8}, clk.now())
    d = asc.tick()
    assert "unified=2" in d.reason and d.desired == 4

    # each live role keeps a floor of one worker: a pipeline with either
    # stage empty serves nothing
    clk.sleep(600.0)
    board.put("w_pre", {"kind": "serve", "role": "prefill", "backlog": 0},
              clk.now())
    board.put("w_dec", {"kind": "serve", "role": "decode", "backlog": 0,
                        "active": 0, "p99_ttft": 0.0}, clk.now())
    board.put("w_uni", {"kind": "serve", "backlog": 0}, clk.now())
    d = asc.tick()
    assert d.desired == 2 and "prefill=1 decode=1" in d.reason


def test_unified_role_tags_keep_the_legacy_policy(tmp_path):
    """serve leases now always tag their role; an all-unified fleet must
    still run the single-pool policy with its original reason strings."""
    clk = VirtualClock()
    asc, board, _ = _scaler(tmp_path, clk)
    board.put("w1", {"kind": "serve", "role": "unified", "backlog": 8,
                     "p99_ttft": 0.0}, clk.now())
    d = asc.tick()
    assert d.desired == 2 and "reported backlog=8" in d.reason


# ----------------------------------------------- publisher dedup
def test_async_publisher_dedups_pending_page_keys(tmp_path):
    store = ObjectStore(str(tmp_path / "store"))
    ps = PrefixStore(store, "ns")
    arrays = {"k": np.arange(8, dtype=np.float32)}
    gate = threading.Event()
    real_publish = ps.publish
    ps.publish = lambda key, arrs: (gate.wait(5.0), real_publish(key, arrs))
    pub = ps.publisher()
    try:
        key = "ab" * 32
        assert pub.submit(key, dict(arrays)) is True
        # the first write is gated in the worker thread, so the key is
        # deterministically still pending: the resubmit is dropped...
        assert pub.submit(key, dict(arrays)) is False
        assert pub.dedup_hits == 1
        # ...and a deduped CALLABLE submit never snapshots at all
        pulled = []
        assert pub.submit(key, lambda: pulled.append(1) or dict(arrays)) is False
        assert pub.dedup_hits == 2 and pulled == []
        # a different key is not deduped
        assert pub.submit("cd" * 32, dict(arrays)) is True
        gate.set()
        pub.flush()
        assert ps.exists(key)
        # once landed the key is pending no more: resubmit is accepted
        assert pub.submit(key, dict(arrays)) is True
        pub.flush()
        assert pub.dedup_hits == 2 and pub.errors == 0
    finally:
        gate.set()
        pub.close()


# ------------------------------------------- pin-aware TTL sweep
def _age(store: ObjectStore, key: str, seconds: float) -> None:
    old = time.time() - seconds
    os.utime(os.path.join(store.root, key), (old, old))


def test_sweep_honors_fresh_pins_and_collects_expired_markers(tmp_path):
    store = ObjectStore(str(tmp_path / "store"))
    ps = PrefixStore(store, "ns")
    arrays = {"k": np.arange(8, dtype=np.float32)}
    keep, drop = "aa" * 32, "bb" * 32
    ps.publish(keep, arrays)
    ps.publish(drop, arrays)
    ps.pin(keep)  # fresh marker
    ps.pin(drop)
    # both pages are past the TTL; only drop's marker is stale too
    for key in (keep, drop):
        _age(store, f"kvprefix/{key[:2]}/{key}", 7200.0)
    _age(store, f"kvprefix-pins/{drop[:2]}/{drop}", 7200.0)
    assert ps.sweep(3600.0) == 1  # pages only; markers are not counted
    assert ps.exists(keep), "fresh pin must exempt an expired page"
    assert not ps.exists(drop)
    # the expired marker was garbage-collected, the fresh one kept
    assert not store.exists(f"kvprefix-pins/{drop[:2]}/{drop}")
    assert store.exists(f"kvprefix-pins/{keep[:2]}/{keep}")
    # pins protect by TTL, not forever: once the marker expires the
    # page is reclaimed like any other
    _age(store, f"kvprefix-pins/{keep[:2]}/{keep}", 7200.0)
    assert ps.sweep(3600.0) == 1
    assert not ps.exists(keep)


# --------------------------- sweep races a handoff: fallback regression
def _paged_engine(model, params, store, role="unified"):
    return ServeEngine(
        model, params, max_batch=2, max_len=32, prefill_chunk=4,
        cache_mode="paged", page_size=PAGE,
        prefix_store=PrefixStore(store, "ns"), worker_role=role,
    )


def test_sweep_mid_handoff_pins_protect_then_fallback_is_byte_identical(
    tmp_path, model_params
):
    """The full storage-mediated handoff at engine level, with the TTL
    sweep fired in the window between handoff-enqueue and decode-side
    admission.  With fresh pins the chain survives and hydration is a
    guaranteed hit; with the chain destroyed (expired pins) the decode
    engine falls back down the replay ladder and the output is STILL
    byte-identical to a dense monolith."""
    model, params = model_params
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
    oracle = ServeEngine(model, params, max_batch=2, max_len=32,
                         prefill_chunk=4)
    oracle.submit([Request(uid="o", prompt=list(prompt), max_new_tokens=4)])
    want = oracle.run_to_completion()[0].output
    assert len(want) == 4

    store = ObjectStore(str(tmp_path / "store"))
    pre = _paged_engine(model, params, store, role="prefill")
    pre.submit([Request(uid="h", prompt=list(prompt), max_new_tokens=4)])
    fin = pre.run_to_completion()
    # prefill role: prompt ingested and published, zero tokens decoded
    assert fin[0].output == [] and pre.stats.tokens_emitted == 0
    assert pre.stats.decode_dispatches == 0
    chain = pre.cache_mgr.chain_keys_for(prompt)
    assert len(chain) == 2  # one full page + the sub-page tail
    ps = PrefixStore(store, "ns")
    for k in chain:
        assert ps.exists(k)
        ps.pin(k)  # what _publish_handoff does before enqueueing
    rec = {"uid": "h", "prompt": list(prompt), "output": [],
           "sample_stream": 0, "max_new_tokens": 4, "temperature": 0.0,
           "stop_token": None}

    # TTL sweep races the handoff: every PAGE is past the TTL, but the
    # handoff's fresh pins protect the whole chain
    for root, _, files in os.walk(os.path.join(store.root, "kvprefix")):
        for f in files:
            old = time.time() - 7200.0
            os.utime(os.path.join(root, f), (old, old))
    assert ps.sweep(3600.0) == 0
    for k in chain:
        assert ps.exists(k)

    dec = _paged_engine(model, params, store, role="decode")
    dec.submit_handoff(dict(rec))
    assert dec.run_to_completion()[0].output == want
    assert dec.stats.handoffs_admitted == 1
    assert dec.stats.handoff_fallbacks == 0
    assert dec.stats.prefix_store_pages_hydrated > 0
    assert dec.stats.hydration_fetch_ops > 0
    assert dec.stats.prefix_store_bytes_fetched > 0
    assert dec.snapshot()["hydration_ticks"]["n"] == 1

    # now the store lies: ttl 0 expires the pins and destroys the chain
    # mid-handoff.  Admission falls back down the replay ladder — and
    # the output is byte-identical anyway
    assert ps.sweep(0.0) > 0
    assert not ps.exists(chain[0])
    dec2 = _paged_engine(model, params, store, role="decode")
    dec2.submit_handoff(dict(rec))
    assert dec2.run_to_completion()[0].output == want
    assert dec2.stats.handoff_fallbacks == 1
    assert dec2.stats.prefix_store_pages_hydrated == 0


# --------------------------------------------------------- disaggregation drill
@pytest.fixture(scope="module")
def disagg(tmp_path_factory):
    """A monolithic and a prefill/decode-split fleet at equal hardware."""
    return fleet_drills.disagg_drill(str(tmp_path_factory.mktemp("disagg")))


def test_disagg_drill_lossless_every_request_handed_off(disagg):
    for leg in ("monolith", "split"):
        r = disagg[leg]
        assert r["lost_requests"] == 0, leg
        assert r["dead_letters"] == 0, leg
        assert r["byte_identical"] is True, leg
    split, n = disagg["split"], disagg["n_requests"]
    # every request travels the storage-mediated handoff path once, with
    # no replay fallbacks and no seal rejects on a healthy store
    assert split["handoffs_published"] == split["handoffs_admitted"] == n
    assert split["handoff_fallbacks"] == 0
    assert split["handoff_seal_rejects"] == 0
    assert disagg["monolith"]["handoffs_published"] == 0
    # the prefill pool never decodes; the decode pool hydrates its KV
    assert split["roles"]["prefill"]["tokens_emitted"] == 0
    assert split["roles"]["prefill"]["decode_dispatches"] == 0
    assert split["prefix_store_pages_hydrated"] > 0
    assert split["hydration_fetch_ops"] > 0
    assert split["prefix_store_bytes_fetched"] > 0


def test_disagg_drill_split_beats_monolith(disagg):
    """Decode-side p99 TTFT (engine ticks) and tokens per engine tick
    strictly beat the monolith, whose chunked prefill interleaves into
    the same ticks."""
    split, mono = disagg["split"], disagg["monolith"]
    assert split["ttft_ticks_p99"] < mono["ttft_ticks_p99"]
    assert split["tokens_per_tick"] > mono["tokens_per_tick"]

"""Elastic serving-fleet robustness: autoscaler policy (hysteresis,
cooldowns, step bounds), deterministic chaos drills (same seed => same
revocation schedule => byte-identical outputs and counters), dead-letter
redrive under churn, placement/deregistration regressions, and the
worker's capped-exponential retry backoff."""

import os

os.environ.setdefault("DS_DEBUG_INVARIANTS", "1")

import jax  # noqa: F401  (initialize the platform before model builds)
import numpy as np
import pytest

import repro.launch.serve  # noqa: F401  (registers distributed-serve)
import repro.launch.train  # noqa: F401
from repro.core import (
    DSConfig,
    DSRuntime,
    FleetFile,
    JobFile,
    SimRunner,
    VirtualClock,
)
from repro.core.autoscaler import Autoscaler, ProgressBoard
from repro.core.chaos import ChaosEvent, ChaosMonkey
from repro.core.cluster import ECSCluster, Service, TaskDefinition
from repro.core.fleet import SpotFleet
from repro.core.queue import DurableQueue, Message
from repro.core.storage import ObjectStore
from repro.core.worker import _stable_key, backoff_delay
from repro.launch.serve import reset_serve_state
from repro.launch.train import build_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefix_store import PrefixStore

import fleet_drills

TICK = 30.0

# small-but-real serving job (same reduced arch the stream tests use),
# paged + prefix-store so a drain has publications to flush
DRILL = {
    "arch": "ds-paper-100m",
    "arch_overrides": "reduced",
    # long decodes: one engine step fully prefills a prompt, so the
    # decode tail is what keeps requests in flight when the notice lands
    "max_new_tokens": 12,
    "max_len": 32,
    "max_batch": 2,
    "prefill_chunk": 4,
    "cache_mode": "paged",
    "page_size": 8,
    "prefix_cache": True,
    "prefix_store": True,
}
SYS_PROMPT = [11, 12, 13, 14, 15, 16, 17, 18,
              21, 22, 23, 24, 25, 26, 27, 28]
DRILL_PROMPTS = [SYS_PROMPT + [31 + i] for i in range(6)]

COUNTER_KEYS = (
    "revocation_notices", "drain_requeued_requests", "requests_resumed",
    "lease_slices", "lease_resumes",
    "prefix_store_pages_published", "prefix_store_pages_hydrated",
)


def _reference_outputs(job, prompts, max_new):
    """One-shot static-batch oracle with the payload's own model path."""
    model = build_model(job)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params,
                      max_batch=job["max_batch"], max_len=job["max_len"],
                      prefill_chunk=job["prefill_chunk"])
    eng.submit([Request(uid=f"q{i}", prompt=list(p), max_new_tokens=max_new)
                for i, p in enumerate(prompts)])
    eng.run_to_completion()
    return {r.uid: r.output for r in eng.finished}


def _aggregate_counters(store, out):
    """Sum engine counters over every worker's segment summary under
    ``out``.  Both records a worker leaves are *cumulative* for that
    worker — the slice/drain record under leases/ and the final
    RESULTS-*.json — so exactly one per worker is summed, with the final
    summary superseding the slice record (noop permit summaries carry no
    counters and contribute zero)."""
    finals, slices = {}, {}
    for info in store.list(f"{out}/"):
        if not info.key.endswith(".json"):
            continue
        base = info.key.rsplit("/", 1)[-1][: -len(".json")]
        if "/leases/" in info.key:
            slices[base] = store.get_json(info.key)
        elif "/RESULTS-" in info.key:
            finals[base.split("RESULTS-", 1)[-1]] = store.get_json(info.key)
    totals = {k: 0 for k in COUNTER_KEYS}
    for snap in {**slices, **finals}.values():
        for k in COUNTER_KEYS:
            totals[k] += int(snap.get(k, 0))
    return totals


def _served_outputs(store, out):
    prefix = f"{out}/requests/"
    return {
        info.key[len(prefix):-len(".json")]:
            store.get_json(info.key)["completion"]
        for info in store.list(prefix)
        if info.key.endswith(".json")
    }


# --------------------------------------------------------------- autoscaler
def test_autoscaler_policy_hysteresis_cooldowns_and_step_bounds(tmp_path):
    clk = VirtualClock()
    cfg = DSConfig(
        app_name="Scale", cluster_machines=1,
        machine_type=["sim.large"], machine_price=1.0,
        autoscale="slo", min_workers=1, max_workers=6,
        autoscale_queue_per_worker=4, autoscale_target_p99_ttft=10.0,
        autoscale_up_cooldown_seconds=60.0,
        autoscale_down_cooldown_seconds=600.0,
        autoscale_max_step=2, monitor_poll_seconds=60.0,
    )
    queue = DurableQueue(str(tmp_path / "jobs.sqlite"), clock=clk)
    queue.send_batch([{"n": i} for i in range(8)])
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="Scale")
    fleet.request(target_capacity=1, bid=1.0, machine_types=["sim.large"])
    cluster = ECSCluster()
    cluster.register_service(Service(
        name="ScaleService",
        task_definition=TaskDefinition.from_config(cfg),
        desired_count=1,
    ))
    board = ProgressBoard()
    asc = Autoscaler(cfg, queue, fleet, cluster, clock=clk, board=board)

    # 1. no serve reports yet: job-queue fallback (8 visible / 4 per
    # worker => 2); a non-serve progress payload must be ignored
    board.put("w0", {"kind": "train", "backlog": 100}, clk.now())
    d = asc.tick()
    assert d.applied and d.desired == 2 and "job-queue" in d.reason
    assert fleet.target_capacity == 2
    # ECS desired count follows the fleet target
    assert cluster.services["ScaleService"].desired_count == 2

    # 2. immediate re-tick with a big reported backlog: up-cooldown blocks
    board.put("w1", {"kind": "serve", "backlog": 40, "p99_ttft": 0.0},
              clk.now())
    d = asc.tick()
    assert not d.applied and "up-cooldown" in d.reason
    assert fleet.target_capacity == 2

    # 3. cooldown elapsed: scale up, but only by max_step (2 -> 4, not 6)
    clk.sleep(60.0)
    board.put("w1", {"kind": "serve", "backlog": 40, "p99_ttft": 0.0},
              clk.now())
    d = asc.tick()
    assert d.applied and d.desired == 4
    assert fleet.target_capacity == 4

    # 4. SLO breach scales up even with an empty queue, clamped to max
    clk.sleep(60.0)
    board.put("w1", {"kind": "serve", "backlog": 0, "p99_ttft": 25.0},
              clk.now())
    d = asc.tick()
    assert d.applied and d.desired == 6 and "slo breach" in d.reason
    assert fleet.target_capacity == 6

    # 5. hysteresis band (target/2, target]: hold, don't shrink
    clk.sleep(60.0)
    board.put("w1", {"kind": "serve", "backlog": 0, "p99_ttft": 7.0},
              clk.now())
    d = asc.tick()
    assert not d.applied and d.desired == 6 and "slo hold" in d.reason
    assert fleet.target_capacity == 6

    # 6. quiet fleet wants to shrink, but the down-cooldown (measured
    # from the LAST SCALE-UP too) blocks the first attempt
    clk.sleep(60.0)
    board.put("w1", {"kind": "serve", "backlog": 4, "p99_ttft": 1.0},
              clk.now())
    d = asc.tick()
    assert not d.applied and "down-cooldown" in d.reason
    assert fleet.target_capacity == 6

    # 7. after the down-cooldown: shrink, step-bounded (6 -> 4, not 1)
    clk.sleep(600.0)
    board.put("w1", {"kind": "serve", "backlog": 4, "p99_ttft": 1.0},
              clk.now())
    d = asc.tick()
    assert d.applied and d.desired == 4
    assert fleet.target_capacity == 4
    assert cluster.services["ScaleService"].desired_count == 4

    # autoscale="off" is a hard no-op
    off = Autoscaler(DSConfig(app_name="Off"), queue, fleet, cluster,
                     clock=clk, board=board)
    assert off.tick() is None
    assert fleet.target_capacity == 4


# ------------------------------------------------------------- chaos drills
def _run_drill(base, tag, *, chaos_seed):
    """One elastic serve run under a seeded revocation drill; returns
    (outputs, chaos log, aggregated counters, run summary, queue)."""
    reset_serve_state()
    clk = VirtualClock()
    cfg = DSConfig(
        app_name="Drill", payload="distributed-serve",
        cluster_machines=1, tasks_per_machine=1,
        machine_type=["sim.large"], machine_price=1.0,
        # fill the machine: placement bin-packs by resources, and a
        # half-size task would put both workers on one instance
        cpu_shares=8192, memory_mb=16384,
        sqs_message_visibility=240.0, check_if_done=False,
        idle_alarm_seconds=100_000.0, monitor_poll_seconds=TICK,
        autoscale="queue", min_workers=1, max_workers=2,
        autoscale_queue_per_worker=2,
        autoscale_up_cooldown_seconds=TICK,
        autoscale_down_cooldown_seconds=3600.0,
    )
    rt = DSRuntime(cfg, store_root=str(base / f"store_{tag}"), clock=clk)
    rt.setup()
    rq_path = str(base / f"requests_{tag}.sqlite")
    rq = DurableQueue(rq_path, default_visibility=240.0,
                      max_receive_count=6, clock=clk)
    rq.send_batch([
        {"uid": f"q{i}", "prompt": p,
         "max_new_tokens": DRILL["max_new_tokens"]}
        for i, p in enumerate(DRILL_PROMPTS)
    ])
    out = "serve/drill"
    rt.submit_job(JobFile(
        shared=dict(
            DRILL,
            request_queue=rq_path,
            expected_requests=len(DRILL_PROMPTS),
            output_prefix=out,
            stream_slice_ticks=2,
            stream_idle_polls=8,
            request_visibility=240.0,
            request_max_receive_count=6,
        ),
        groups=[{} for _ in range(2)],  # one lease permit per worker slot
    ))
    rt.start_cluster(FleetFile(startup_seconds=TICK, market_seed=7))
    chaos = ChaosMonkey.revocation_drill(
        rt.fleet, clk, seed=chaos_seed, n_revocations=1,
        start=3 * TICK, spacing=2 * TICK, notice_seconds=2 * TICK,
        store=rt.store, logs=rt.logs,
    )
    summary = SimRunner(rt, tick_seconds=TICK, chaos=chaos).run(max_ticks=300)
    outputs = _served_outputs(rt.store, out)
    counters = _aggregate_counters(rt.store, out)
    log = [(r.kind, r.target, r.time) for r in chaos.log]
    return outputs, log, counters, summary, rq


def test_chaos_drill_is_deterministic_and_loses_nothing(tmp_path):
    """Same chaos seed => identical revocation schedule => byte-identical
    completions AND identical aggregated counter snapshots across two
    runs — the replay property the churn benchmark's gates rely on."""
    out_a, log_a, ctr_a, summary_a, rq_a = _run_drill(
        tmp_path, "a", chaos_seed=1234)
    # run 1 correctness: the notice was delivered and honoured
    assert summary_a.preemptions >= 1  # the revoked machine terminated
    assert ctr_a["revocation_notices"] >= 1
    assert ctr_a["drain_requeued_requests"] >= 1
    assert ctr_a["requests_resumed"] >= 1  # requeued work found a survivor
    assert ctr_a["prefix_store_pages_published"] > 0
    # every request completed exactly once, byte-identical to the
    # undisturbed static-batch oracle, and none died
    assert rq_a.counts() == {"visible": 0, "in_flight": 0, "dead": 0}
    want = _reference_outputs(DRILL, DRILL_PROMPTS, DRILL["max_new_tokens"])
    assert out_a == want, "churned completions diverged from the oracle"

    out_b, log_b, ctr_b, _, _ = _run_drill(tmp_path, "b", chaos_seed=1234)
    assert log_a == log_b, "same seed must replay the same fault schedule"
    assert out_a == out_b
    assert ctr_a == ctr_b, (ctr_a, ctr_b)


def test_revocation_drill_schedule_is_seeded(tmp_path):
    clk = VirtualClock()
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="Sched")
    mk = lambda seed: ChaosMonkey.revocation_drill(  # noqa: E731
        fleet, clk, seed=seed, n_revocations=3, start=60.0,
        spacing=120.0, notice_seconds=60.0)
    sched = lambda m: [(e.at, e.victim) for e in m.pending]  # noqa: E731
    assert sched(mk(7)) == sched(mk(7))
    assert sched(mk(7)) != sched(mk(8))


def test_delay_heartbeat_suppresses_liveness_for_the_window():
    clk = VirtualClock()
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="Hb")
    fleet.request(target_capacity=1, bid=1.0, machine_types=["sim.large"])
    fleet.tick()
    inst = fleet.running()[0]
    chaos = ChaosMonkey(fleet, clk, events=[
        ChaosEvent(kind="delay_heartbeat", at=0.0, victim=0, duration=90.0)
    ])
    assert [r.kind for r in chaos.tick()] == ["delay_heartbeat"]
    assert chaos.counters["heartbeat_delays"] == 1
    assert chaos.allow_heartbeat(inst) is False  # wedged-looking host
    clk.sleep(90.0)
    assert chaos.allow_heartbeat(inst) is True


def test_truncated_prefix_blob_is_a_fetch_miss_not_a_crash(tmp_path):
    store = ObjectStore(str(tmp_path / "store"))
    ps = PrefixStore(store, namespace="chaos-test")
    like = {"k": np.arange(8, dtype=np.float32).reshape(2, 4),
            "v": np.ones((2, 4), np.float32)}
    page = ps.child_key(ps.root_key(), [1, 2, 3])
    ps.publish(page, like)
    got = ps.fetch(page, like)
    assert got is not None and np.array_equal(got["k"], like["k"])
    clk = VirtualClock()
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="Blob")
    chaos = ChaosMonkey(fleet, clk, store=store, events=[
        ChaosEvent(kind="truncate_blob", at=0.0, victim=0)
    ])
    assert [r.kind for r in chaos.tick()] == ["truncate_blob"]
    assert chaos.counters["blobs_truncated"] == 1
    assert ps.fetch(page, like) is None  # hydration degrades, never raises


# ------------------------------------------------------------- DLQ redrive
def test_dead_letter_redrive_after_revocation_churn(tmp_path):
    """A revocation drain requeues claimed requests WITHOUT refunding
    their receive budget, so churn still marches poison work to the DLQ
    (here: max_receive_count=1, so one drain condemns every in-flight
    request) — and the lease is NOT wedged by them.  An operator redrive
    plus rerun then serves everything byte-identically."""
    reset_serve_state()
    clk = VirtualClock()

    def runtime(queue_name):
        cfg = DSConfig(
            app_name="Dlq", payload="distributed-serve",
            cluster_machines=1, tasks_per_machine=1,
            machine_type=["sim.large"], machine_price=1.0,
            sqs_message_visibility=240.0, check_if_done=False,
            idle_alarm_seconds=100_000.0, monitor_poll_seconds=TICK,
            sqs_queue_name=queue_name,
        )
        rt = DSRuntime(cfg, store_root=str(tmp_path / "store"), clock=clk)
        rt.setup()
        return rt

    prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 10]]
    rq_path = str(tmp_path / "requests.sqlite")
    rq = DurableQueue(rq_path, default_visibility=240.0,
                      max_receive_count=1, clock=clk)
    rq.send_batch([
        {"uid": f"q{i}", "prompt": p, "max_new_tokens": 6}
        for i, p in enumerate(prompts)
    ])
    job = {
        "arch": "ds-paper-100m", "arch_overrides": "reduced",
        "max_new_tokens": 6, "max_len": 32, "max_batch": 2,
        "prefill_chunk": 4,
        "request_queue": rq_path,
        "expected_requests": len(prompts),
        "output_prefix": "serve/dlq",
        "stream_slice_ticks": 1,  # nothing completes before the drain
        "stream_idle_polls": 4,
        "request_visibility": 240.0,
        "request_max_receive_count": 1,
    }
    rt = runtime("DlqJobs1")
    rt.submit_job(JobFile(shared=dict(job), groups=[{}]))
    rt.start_cluster(FleetFile(startup_seconds=TICK))
    # one explicit notice against the (only) serving instance, with two
    # ticks of warning so the drain runs before the machine dies
    chaos = ChaosMonkey(rt.fleet, clk, events=[
        ChaosEvent(kind="revoke", at=2.5 * TICK, victim=0,
                   notice_seconds=2 * TICK)
    ])
    summary = SimRunner(rt, tick_seconds=TICK, chaos=chaos).run(max_ticks=80)
    # the replacement lease DLQ'd the poisoned requests at claim time and
    # exited through the idle path — the fleet tore down instead of
    # wedging on work that can never complete
    assert summary.jobs_done >= 1, f"{summary}"
    assert rq.counts() == {"visible": 0, "in_flight": 0, "dead": 3}
    assert _served_outputs(rt.store, "serve/dlq") == {}

    # operator redrive: receive budgets reset, messages visible again
    assert rq.redrive_dead_letters() == 3
    assert rq.counts()["visible"] == 3

    # rerun against the SAME output prefix with a healthy receive budget
    reset_serve_state()
    rt2 = runtime("DlqJobs2")
    job2 = dict(job, request_max_receive_count=3)
    rt2.submit_job(JobFile(shared=job2, groups=[{}]))
    rt2.start_cluster(FleetFile(startup_seconds=TICK))
    summary2 = SimRunner(rt2, tick_seconds=TICK).run(max_ticks=120)
    assert summary2.jobs_done == 1, f"{summary2}"
    assert rq.counts() == {"visible": 0, "in_flight": 0, "dead": 0}
    got = _served_outputs(rt2.store, "serve/dlq")
    want = _reference_outputs(job, prompts, 6)
    assert got == want, "redriven requests diverged from the oracle"


# ------------------------------------------------- cluster regressions
def test_deregister_service_drops_its_tasks_and_reregister_counts_live():
    clk = VirtualClock()
    fleet = SpotFleet(FleetFile(startup_seconds=0.0), clock=clk,
                      app_name="App")
    fleet.request(target_capacity=2, bid=1.0, machine_types=["sim.large"])
    fleet.tick()
    cluster = ECSCluster()

    def td():
        # a FRESH definition object each time: placement and teardown
        # must match by config equality, not object identity
        return TaskDefinition(family="AppTask", payload="p",
                              cpu_shares=1024, memory_mb=1024,
                              docker_cores=1)

    cluster.register_service(Service(name="AppService",
                                     task_definition=td(), desired_count=2))
    assert len(cluster.place("AppService", fleet, clk.now())) == 2
    # re-registering (equal config, new TaskDefinition object) must see
    # its live tasks and place nothing more
    cluster.register_service(Service(name="AppService",
                                     task_definition=td(), desired_count=2))
    assert cluster.place("AppService", fleet, clk.now()) == []
    assert len(cluster.tasks) == 2
    # deregistration drops the task records too (the family is "AppTask"
    # while the service is "AppService": a name-prefix match never fires)
    cluster.deregister_service("AppService")
    assert cluster.tasks == {}
    cluster.deregister_service("AppService")  # idempotent


# ---------------------------------------------------------------- backoff
def test_backoff_delay_is_capped_exponential_with_stable_jitter():
    # deterministic: same (key, attempt) always yields the same delay
    assert (backoff_delay(5.0, 3, cap=240.0, key="k")
            == backoff_delay(5.0, 3, cap=240.0, key="k"))
    # distinct keys de-synchronize (the anti-thundering-herd property)
    assert (backoff_delay(5.0, 3, cap=240.0, key="k")
            != backoff_delay(5.0, 3, cap=240.0, key="other"))
    # jitter=0: exact doubling from the base, capped at the visibility
    assert backoff_delay(5.0, 1, cap=240.0, key="k", jitter=0) == 5.0
    assert backoff_delay(5.0, 2, cap=240.0, key="k", jitter=0) == 10.0
    assert backoff_delay(5.0, 4, cap=240.0, key="k", jitter=0) == 40.0
    assert backoff_delay(5.0, 10, cap=240.0, key="k", jitter=0) == 240.0
    # attempt < 1 clamps to the first step (receive_count starts at 1)
    assert backoff_delay(5.0, 0, cap=240.0, key="k", jitter=0) == 5.0
    # jitter only ever shrinks the delay, never past the schedule
    for attempt in range(1, 9):
        d = backoff_delay(5.0, attempt, cap=240.0, key="k")
        assert 0.0 < d <= min(240.0, 5.0 * 2 ** (attempt - 1))


def test_stable_key_is_content_addressed_across_redeliveries():
    body = {"uid": "q0", "prompt": [1, 2, 3]}
    m1 = Message(id="uuid-a", body=dict(body), receipt="r1", receive_count=1)
    m2 = Message(id="uuid-b", body=dict(body), receipt="r2", receive_count=3)
    # same content => same jitter key, even across fresh message ids
    # (ids are uuid4 — keying on them would break schedule replay)
    assert _stable_key(m1) == _stable_key(m2)
    m3 = Message(id="uuid-c", body={"uid": "q1", "prompt": [9]},
                 receipt="r3", receive_count=1)
    assert _stable_key(m1) != _stable_key(m3)


# ------------------------------------------------------ elastic churn drill
@pytest.fixture(scope="module")
def churn(tmp_path_factory):
    """A static and an autoscaled fleet, each through the same arrival
    spike and the same two seeded mid-spike spot revocations."""
    return fleet_drills.churn_drill(str(tmp_path_factory.mktemp("churn")))


def test_churn_drill_loses_nothing_byte_identical(churn):
    for name in ("static", "autoscaled"):
        r = churn[name]
        assert r["lost_requests"] == 0, name
        assert r["byte_identical"] is True, name
        assert r["revocations_injected"] >= 2, name
        assert r["revocation_notices"] >= 1, name  # somebody drained gracefully


def test_churn_drill_autoscaled_beats_static_p99_and_hydrates(churn):
    """The autoscaler's reason to exist: the spike's p99 turnaround beats
    the static fleet's; and survivors warm up from the cross-host prefix
    store instead of re-prefilling."""
    static, auto = churn["static"], churn["autoscaled"]
    assert auto["workers_peak"] > static["workers_peak"]
    assert auto["p99_ttft_s"] < static["p99_ttft_s"]
    assert auto["prefix_store_pages_hydrated"] > 0

"""Spot fleet, ECS placement, and monitor behaviour (paper Steps 3-4)."""

import os

import pytest

from repro.core import (
    DSConfig,
    DSRuntime,
    DurableQueue,
    ECSCluster,
    FleetFile,
    InstanceState,
    JobFile,
    Monitor,
    Service,
    SimRunner,
    SpotFleet,
    TaskDefinition,
    VirtualClock,
    register_payload,
)


def mkfleet(clk, **ff_kwargs):
    ff = FleetFile(startup_seconds=5.0, **ff_kwargs)
    return SpotFleet(ff, clock=clk, app_name="T")


def test_fleet_fulfills_target_after_startup():
    clk = VirtualClock()
    fleet = mkfleet(clk)
    fleet.request(target_capacity=3, bid=1.0, machine_types=["sim.large"])
    assert len(fleet.pending()) == 3 and not fleet.running()
    clk.advance(5.0)
    fleet.tick()
    assert len(fleet.running()) == 3


def test_outbid_gets_no_capacity_then_recovers():
    clk = VirtualClock()
    fleet = mkfleet(clk)
    fleet.request(target_capacity=2, bid=0.0001, machine_types=["sim.large"])
    assert fleet.fulfilled_capacity() == 0  # priced out
    fleet.bid = 1.0  # market came back under our (new) bid
    fleet.tick()
    assert fleet.fulfilled_capacity() == 2


def test_preemption_and_backfill():
    clk = VirtualClock()
    fleet = mkfleet(clk, preemption_rate_per_hour=60.0, market_seed=7)  # ~1/min
    fleet.request(target_capacity=4, bid=1.0, machine_types=["sim.small"])
    clk.advance(5.0)
    fleet.tick()
    preempted = 0
    for _ in range(60):
        clk.advance(60.0)
        dead = fleet.tick()
        preempted += sum(1 for i in dead if i.terminate_reason == "spot-preemption")
        # back-fill restores the target on the same tick
        assert fleet.fulfilled_capacity() == 4
    assert preempted > 5, "preemption injection should have fired repeatedly"


def test_cheapest_mode_no_backfill():
    clk = VirtualClock()
    fleet = mkfleet(clk, preemption_rate_per_hour=120.0, market_seed=3)
    fleet.request(target_capacity=4, bid=1.0, machine_types=["sim.small"])
    clk.advance(5.0)
    fleet.tick()
    fleet.replace_on_terminate = False  # what cheapest mode sets
    fleet.modify_target(1)
    for _ in range(30):
        clk.advance(60.0)
        fleet.tick()
    assert fleet.fulfilled_capacity() <= 1


def test_placement_respects_capacity():
    clk = VirtualClock()
    fleet = mkfleet(clk)
    fleet.request(target_capacity=1, bid=1.0, machine_types=["sim.large"])  # 8 vcpu, 16GB
    clk.advance(5.0)
    fleet.tick()
    cluster = ECSCluster()
    # each task wants 4 vcpus -> exactly 2 fit on a sim.large
    td = TaskDefinition(family="t", payload="p", cpu_shares=4096, memory_mb=4096, docker_cores=1)
    cluster.register_service(Service(name="S", task_definition=td, desired_count=5))
    placed = cluster.place("S", fleet, clk.now())
    assert len(placed) == 2, "bin-packing must stop at instance capacity"
    # oversized task never places (the paper's documented failure mode)
    td_big = TaskDefinition(family="b", payload="p", cpu_shares=99999, memory_mb=4096, docker_cores=1)
    cluster.register_service(Service(name="B", task_definition=td_big, desired_count=1))
    assert cluster.place("B", fleet, clk.now()) == []


def test_oversized_instance_takes_extra_tasks():
    """'ECS will keep placing Dockers onto an instance until it is full.'"""
    clk = VirtualClock()
    fleet = mkfleet(clk)
    fleet.request(target_capacity=1, bid=2.0, machine_types=["sim.xlarge"])  # 16 vcpu
    clk.advance(5.0)
    fleet.tick()
    cluster = ECSCluster()
    td = TaskDefinition(family="t", payload="p", cpu_shares=2048, memory_mb=2048, docker_cores=1)
    cluster.register_service(Service(name="S", task_definition=td, desired_count=8))
    placed = cluster.place("S", fleet, clk.now())
    assert len(placed) == 8  # more than the 2-ish the user probably intended


@register_payload("noop-sleep")
def noop_sleep(job, ctx):
    for _ in range(int(job.get("beats", 1))):
        ctx.heartbeat()
    return {"ok": True}


@register_payload("always-fails")
def always_fails(job, ctx):
    raise ValueError("intentional failure")


def _runtime(tmp_path, clk, payload="noop-sleep", machines=2, **cfg_kwargs):
    kwargs = dict(
        app_name="T",
        payload=payload,
        cluster_machines=machines,
        tasks_per_machine=1,
        machine_type=["sim.large"],
        machine_price=1.0,
        sqs_message_visibility=180.0,
        check_if_done=False,
        monitor_poll_seconds=60.0,
    )
    kwargs.update(cfg_kwargs)
    cfg = DSConfig(**kwargs)
    rt = DSRuntime(cfg, store_root=str(tmp_path / "store"), clock=clk)
    rt.setup()
    return rt


def test_sim_runner_drains_queue_and_tears_down(tmp_path):
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk)
    rt.submit_job(JobFile(shared={"beats": 1}, groups=[{"g": i} for i in range(10)]))
    rt.start_cluster(FleetFile(startup_seconds=5.0))
    runner = SimRunner(rt, tick_seconds=60.0)
    summary = runner.run()
    assert summary.jobs_done == 10
    assert rt.queue.counts() == {"visible": 0, "in_flight": 0, "dead": 0}
    # teardown: fleet cancelled, logs exported to the store
    assert rt.fleet.fulfilled_capacity() == 0
    assert any(o.key.startswith("logs/T/") for o in rt.store.list("logs/"))


def test_poison_jobs_end_in_dlq_without_wedging(tmp_path):
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk, payload="always-fails", max_receive_count=2,
                  sqs_message_visibility=60.0)
    rt.submit_job(JobFile(groups=[{"g": 0}, {"g": 1}]))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    runner = SimRunner(rt, tick_seconds=60.0)
    summary = runner.run(max_ticks=50)
    assert summary.jobs_done == 0
    assert rt.monitor.finished, "cluster must tear down despite poison jobs"
    # teardown purges the DLQ, but the summary still counts what it held
    assert summary.dead_letters == 2


def test_teardown_sweeps_expired_kvprefix_pages(tmp_path):
    """With ``kvprefix_ttl_seconds`` set, the monitor's teardown sweep
    deletes expired cross-host KV prefix pages from the object store
    (ttl 0 = clear the prefix); without the knob the store is left
    alone."""
    import numpy as np

    from repro.serving.prefix_store import PrefixStore

    clk = VirtualClock()
    rt = _runtime(tmp_path, clk, kvprefix_ttl_seconds=0.0)
    ps = PrefixStore(rt.store, "ns")
    ps.publish("aa" * 32, {"k": np.zeros((2, 2), np.float32)})
    ps.publish("bb" * 32, {"k": np.ones((2, 2), np.float32)})
    rt.submit_job(JobFile(shared={"beats": 1}, groups=[{"g": 0}]))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    runner = SimRunner(rt, tick_seconds=60.0)
    runner.run()
    assert rt.monitor.finished
    assert list(rt.store.list("kvprefix/")) == []
    assert any("kvprefix" in e["message"] for e in rt.logs.events("monitor"))

    # default config (no TTL): pages persist across the run
    clk2 = VirtualClock()
    rt2 = _runtime(tmp_path / "2", clk2)
    PrefixStore(rt2.store, "ns").publish(
        "cc" * 32, {"k": np.zeros((2, 2), np.float32)}
    )
    rt2.submit_job(JobFile(shared={"beats": 1}, groups=[{"g": 0}]))
    rt2.start_cluster(FleetFile(startup_seconds=0.0))
    SimRunner(rt2, tick_seconds=60.0).run()
    assert rt2.monitor.finished
    assert len(list(rt2.store.list("kvprefix/"))) == 1


def test_idle_alarm_terminates_stalled_instance(tmp_path):
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk, machines=1, idle_alarm_seconds=900.0)
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    rt.fleet.tick()
    monitor = rt.make_monitor()
    # queue is empty -> but make it look non-empty so teardown doesn't race
    rt.queue.send({"g": 0})
    rt.queue.receive(visibility_timeout=10_000.0)  # someone holds a job forever
    inst = rt.fleet.running()[0]
    inst.last_heartbeat = clk.now()
    for _ in range(16):
        clk.advance(60.0)
        report = monitor.tick()
    assert inst.state == InstanceState.TERMINATED
    assert inst.terminate_reason == "idle-alarm"


def test_thread_runner_counts_dead_letters(tmp_path):
    """A payload exception that dead-letters its job shows in the real
    runner's summary (the DLQ is purged at teardown, before it returns)."""
    from repro.core import ThreadRunner

    rt = DSRuntime(
        DSConfig(app_name="T", payload="always-fails", cluster_machines=1,
                 machine_type=["sim.large"], machine_price=1.0,
                 sqs_message_visibility=0.5, max_receive_count=1,
                 check_if_done=False),
        store_root=str(tmp_path / "store"),
    )
    rt.setup()
    rt.submit_job(JobFile(groups=[{"g": 0}, {"g": 1}]))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = ThreadRunner(rt).run()
    assert summary.jobs_done == 0 and summary.jobs_failed == 2
    assert summary.dead_letters == 2


@pytest.mark.parametrize("payload,rc", [("noop-sleep", 0), ("always-fails", 1)])
def test_worker_host_exit_code_reports_dead_letters(tmp_path, payload, rc):
    """``worker_host`` exits non-zero exactly when a job dead-lettered."""
    from repro.launch.worker_host import run_worker_host

    workdir = tmp_path / "w"
    workdir.mkdir()
    cfg = DSConfig(app_name="T", payload=payload, cluster_machines=1,
                   machine_type=["sim.large"], machine_price=1.0,
                   sqs_message_visibility=0.5, max_receive_count=1,
                   check_if_done=False)
    (workdir / "config.json").write_text(cfg.to_json())
    (workdir / "fleet.json").write_text(FleetFile(startup_seconds=0.0).to_json())
    rt = DSRuntime(cfg, store_root=str(workdir / "store"))
    rt.setup()
    rt.submit_job(JobFile(groups=[{"g": 0}]))
    assert run_worker_host(str(workdir)) == rc
    summary = rt.store.get_json("summary/T.json")
    assert summary["dead_letters"] == rc


def test_run_ds_commands_do_not_import_jax():
    """The control-node commands stay off JAX: only the worker host they
    start may hold the accelerator."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = "import sys, repro.launch.run_ds; sys.exit(int('jax' in sys.modules))"
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code], env=env).returncode == 0

"""Simulated serving fleets for the fleet drill tests (not a test module:
pytest collects nothing here).

Three drills run whole ``distributed-serve`` fleets on ``SimRunner``
with a virtual clock, so every count and latency is deterministic on any
host:

- :func:`churn_drill` — a static and an autoscaled fleet under the same
  arrival spike and the same seeded spot revocations;
- :func:`recovery_drill` — the same chaos plus transient storage/queue
  fault windows, with generation checkpoints off, on, and on but
  unreadable;
- :func:`disagg_drill` — a monolithic and a prefill/decode-split fleet
  at equal hardware.

Each returns one result dict per fleet, with ``byte_identical`` set
against an undisturbed direct-engine oracle.
"""

from __future__ import annotations

import os

import jax
import numpy as np

import repro.launch.serve  # noqa: F401  (registers distributed-serve)
import repro.launch.train  # noqa: F401
from repro.core import DSConfig, DSRuntime, FleetFile, JobFile, SimRunner, VirtualClock
from repro.core.chaos import ChaosMonkey
from repro.core.queue import DurableQueue
from repro.launch.serve import reset_serve_state
from repro.launch.train import build_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.types import percentiles

PAGE = 16
TICK = 30.0

CHURN_JOB = {
    "arch": "ds-paper-100m", "arch_overrides": "reduced",
    "max_new_tokens": 12, "max_len": 64, "max_batch": 2,
    "prefill_chunk": 8, "cache_mode": "paged",
    "page_size": PAGE, "prefix_cache": True,
    "prefix_store": True,
    "stream_slice_ticks": 4, "stream_idle_polls": 60,
    "request_visibility": 240.0, "request_max_receive_count": 6,
}

DISAGG_JOB = dict(
    CHURN_JOB,
    # one chunk per engine tick: without the per-tick ingest cap a whole
    # prompt lands in a single step and prefill never contends with
    # decode, which is exactly the interference the role split exists to
    # remove (a decode worker's hydrated admissions ingest only the
    # one-token frontier)
    prefill_token_budget=8,
)
del DISAGG_JOB["max_new_tokens"]

# lease robustness counters summed over every segment summary a churn run
# leaves behind (per-worker RESULTS-*.json + drained leases/*.json)
CHURN_COUNTERS = (
    "revocation_notices", "drain_requeued_requests", "requests_resumed",
    "lease_slices", "lease_resumes",
    "prefix_store_pages_hydrated", "prefix_store_pages_published",
    # work-preserving recovery: generation checkpoints written at drain,
    # resumes that restored emitted tokens from them, and the fallback /
    # flaky-storage books that prove the degradation ladder was walked
    "checkpoints_published", "checkpoint_resumes", "tokens_recovered",
    "checkpoint_fallbacks", "decode_tokens_discarded",
    "publish_retries", "prefix_store_hash_mismatches",
)

# per-role counters summed over a disaggregated fleet's segment summaries
DISAGG_COUNTERS = (
    "ticks", "tokens_emitted", "prompt_tokens_ingested",
    "prompt_tokens_skipped", "decode_dispatches", "prefill_dispatches",
    "handoffs_published", "handoffs_admitted",
    "handoff_fallbacks", "handoff_seal_rejects",
    "prefix_store_pages_hydrated", "prefix_store_pages_published",
    "hydration_fetch_ops", "prefix_store_bytes_fetched",
    "publish_dedup_hits",
)


# ------------------------------------------------------------- request bodies
def churn_request_bodies(n_requests: int, max_new: int, *, seed: int):
    """One shared page-sized system prefix (so survivors can hydrate it
    from the cross-host store after a revocation) plus short distinct
    tails."""
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=PAGE)]
    return [
        {"uid": f"c{i}",
         "prompt": prefix + [int(t) for t in rng.integers(1, 200, size=3)],
         "max_new_tokens": max_new}
        for i in range(n_requests)
    ]


def disagg_request_bodies(n_requests: int, *, long_tail: int, short_tail: int,
                          long_new: int, short_new: int, seed: int = 31):
    """A shared page-sized system prefix, then an alternating mix of
    long-prompt / short-generation and short-prompt / long-generation
    requests — the shape where interleaved chunked prefill steals the
    most decode ticks from a monolithic worker."""
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=PAGE)]
    bodies = []
    for i in range(n_requests):
        tail_len, max_new = ((long_tail, short_new) if i % 2 == 0
                             else (short_tail, long_new))
        bodies.append({
            "uid": f"g{i}",
            "prompt": prefix + [int(t) for t in rng.integers(1, 200, size=tail_len)],
            "max_new_tokens": max_new,
        })
    return bodies


def direct_outputs(job: dict, bodies) -> dict:
    """The undisturbed oracle: the same requests through one direct engine
    (greedy sampling streams are submit-order keyed, so output is
    scheduling- and fleet-invariant)."""
    model = build_model(job)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_batch=job["max_batch"],
                      max_len=job["max_len"], prefill_chunk=job["prefill_chunk"])
    eng.submit([Request(uid=b["uid"], prompt=list(b["prompt"]),
                        max_new_tokens=b["max_new_tokens"]) for b in bodies])
    eng.run_to_completion()
    return {r.uid: list(r.output) for r in eng.finished}


# ------------------------------------------------------------------ helpers
def _segments(store, out: str) -> list:
    """One cumulative record per worker under ``out``: the final RESULTS-
    summary where the worker wrote one, else its last slice/drain record
    under leases/ (summing both would double-count — each is
    cumulative)."""
    finals, slices = {}, {}
    for seg_prefix in (f"{out}/RESULTS-", f"{out}/leases/"):
        for info in store.list(seg_prefix):
            if not info.key.endswith(".json"):
                continue
            base = info.key.rsplit("/", 1)[-1][:-len(".json")]
            if "/leases/" in info.key:
                slices[base] = store.get_json(info.key)
            else:
                finals[base.split("RESULTS-", 1)[-1]] = store.get_json(info.key)
    return list({**slices, **finals}.values())


def _records(store, out: str) -> dict:
    prefix = f"{out}/requests/"
    return {
        info.key[len(prefix):-len(".json")]: store.get_json(info.key)
        for info in store.list(prefix)
        if info.key.endswith(".json")
    }


def _sender(rq, clk, arrivals: dict, submitted_at: dict):
    def on_tick(t):
        for body in arrivals.get(t, ()):
            submitted_at[body["uid"]] = clk.now()
            rq.send(dict(body, submitted_at=clk.now()))
    return on_tick


# -------------------------------------------------------------------- fleets
def run_churn_fleet(*, label: str, autoscale: str, max_fleet: int, bodies,
                    serve_job: dict, arrivals: dict, chaos_seed: int,
                    workdir: str, flaky_duration: float = 0.0,
                    flaky_scope: str = "",
                    sabotage_checkpoints: bool = False) -> dict:
    """One simulated serving fleet under an arrival spike and a seeded
    spot-revocation drill: elastic serving leases stream requests from a
    shared DurableQueue, the chaos monkey revokes instances mid-spike
    (the victims drain gracefully and requeue their in-flight work), and
    survivors/replacements resume it — hydrating the shared prefix's KV
    page from the object store instead of re-prefilling.

    ``flaky_duration`` > 0 opens a transient storage+queue fault window
    alongside every revocation (``ChaosMonkey.recovery_drill``), so the
    drain/resume paths must survive first-attempt put/get/receive
    failures via retry.  ``sabotage_checkpoints`` makes every generation
    checkpoint unreadable (reads under ``checkpoints/`` raise), forcing
    resumes down the fallback ladder to prefix-hit full replay."""
    # worker ids repeat across independent simulated runs: stale warm
    # engines must not let lease state "survive" a simulated fleet swap
    reset_serve_state()
    clk = VirtualClock()
    cfg = DSConfig(
        app_name=f"Churn{label.capitalize()}",
        payload="distributed-serve",
        cluster_machines=1,
        tasks_per_machine=1,
        machine_type=["sim.large"],
        machine_price=1.0,
        # one task fills a sim.large (8 vcpus): ECS bin-packs by
        # resources, so a half-size task would double up on the first
        # machine and leave scaled-up instances idle (and revocations
        # would hit workerless machines)
        cpu_shares=8192,
        memory_mb=16384,
        sqs_message_visibility=240.0,
        check_if_done=False,
        idle_alarm_seconds=100_000.0,  # chaos drives churn, not idle alarms
        monitor_poll_seconds=TICK,
        autoscale=autoscale,
        min_workers=1,
        max_workers=max_fleet,
        autoscale_queue_per_worker=3,
        autoscale_target_p99_ttft=6.0,
        autoscale_up_cooldown_seconds=TICK,
        autoscale_down_cooldown_seconds=600.0,
        autoscale_max_step=2,
    )
    rt = DSRuntime(cfg, store_root=os.path.join(workdir, f"store_{label}"), clock=clk)
    rt.setup()
    if sabotage_checkpoints:
        # total checkpoint loss: puts still land (the durable-before-ack
        # ordering is still exercised) but every read raises, so every
        # resume must fall back to prefix-hit full replay.  The chaos
        # monkey's flaky wrapper stacks on top of this one, so reads are
        # ALSO transiently faulted first — the full ladder in one leg.
        orig_get = rt.store.get_bytes

        def sabotaged_get(key, *a, **kw):
            if "/checkpoints/" in key:
                raise FileNotFoundError(f"chaos: checkpoint sabotaged {key!r}")
            return orig_get(key, *a, **kw)

        rt.store.get_bytes = sabotaged_get
    rq_path = os.path.join(workdir, f"requests_{label}.sqlite")
    rq = DurableQueue(
        rq_path,
        default_visibility=float(serve_job["request_visibility"]),
        max_receive_count=int(serve_job["request_max_receive_count"]),
        clock=clk,
    )
    job = dict(serve_job, request_queue=rq_path,
               expected_requests=len(bodies), output_prefix="serve/churn")
    # interchangeable lease permits, one per potential worker: any permit
    # a worker claims resumes that worker's own warm engine
    rt.submit_job(JobFile(shared=job, groups=[{} for _ in range(max_fleet)]))
    rt.start_cluster(FleetFile(startup_seconds=TICK, market_seed=7))
    # first notice lands mid-spike (arrivals peak at tick 4); an event
    # whose victim pool is empty (everything already revoked) stays
    # pending and fires once a replacement is running, so the static
    # single-machine fleet eats both revocations back to back
    drill = dict(seed=chaos_seed, n_revocations=2, start=3 * TICK,
                 spacing=3 * TICK, notice_seconds=2 * TICK,
                 store=rt.store, logs=rt.logs)
    if flaky_duration > 0:
        chaos = ChaosMonkey.recovery_drill(
            rt.fleet, clk, flaky_duration=flaky_duration,
            flaky_scope=flaky_scope, queue=rq, **drill)
    else:
        chaos = ChaosMonkey.revocation_drill(rt.fleet, clk, **drill)
    submitted_at = {}
    runner = SimRunner(rt, tick_seconds=TICK, chaos=chaos,
                       on_tick=_sender(rq, clk, arrivals, submitted_at))
    runner.run(max_ticks=600)
    records = _records(rt.store, "serve/churn")
    result = {k: 0 for k in CHURN_COUNTERS}
    for seg in _segments(rt.store, "serve/churn"):
        for k in CHURN_COUNTERS:
            # noop permit summaries carry no counter block
            result[k] += int(seg.get(k, 0))
    # client-observed latency: submit (queue send) -> completion record,
    # in virtual seconds — the fleet-level SLO the autoscaler is graded on
    turnarounds = [rec["done_at"] - submitted_at[uid]
                   for uid, rec in records.items() if uid in submitted_at]
    result.update(
        p99_ttft_s=percentiles(turnarounds)["p99"],
        lost_requests=len(bodies) - len(records),
        revocations_injected=chaos.counters["revocations"],
        # tokens decode had to redo: everything rolled back at preemption
        # minus everything a checkpoint resume restored (the held-back
        # re-dispatch token stays, by design)
        tokens_redecoded=(result["decode_tokens_discarded"]
                          - result["tokens_recovered"]),
        storage_faults=chaos.counters.get("storage_faults", 0),
        queue_faults=chaos.counters.get("queue_faults", 0),
        workers_peak=max((r.running_instances for r in runner.monitor.history),
                         default=0),
        outputs={uid: r["completion"] for uid, r in records.items()},
    )
    rq.close()
    reset_serve_state()
    return result


def run_disagg_fleet(*, label: str, split: bool, bodies, serve_job: dict,
                     arrivals: dict, workdir: str) -> dict:
    """One simulated serving fleet over the disaggregation workload, at
    fixed hardware (two machines, autoscaling off).  ``split=False``
    runs the monolithic baseline: two unified permits draining one
    request queue.  ``split=True`` runs the same two machines role-split
    — one prefill permit that chunk-prefills prompts, publishes their KV
    chains to the prefix store and enqueues sealed handoff records, and
    one decode permit that hydrates those chains on demand and decodes.
    The serving-side metrics (TTFT in engine ticks, tokens per engine
    tick) are counter-derived."""
    reset_serve_state()
    clk = VirtualClock()
    cfg = DSConfig(
        app_name=f"Disagg{label.capitalize()}",
        payload="distributed-serve",
        cluster_machines=2,
        tasks_per_machine=1,
        machine_type=["sim.large"],
        machine_price=1.0,
        # one task fills a sim.large: both legs get exactly two workers
        # on two machines, so the comparison is at equal total hardware
        cpu_shares=8192,
        memory_mb=16384,
        sqs_message_visibility=240.0,
        check_if_done=False,
        idle_alarm_seconds=100_000.0,
        monitor_poll_seconds=TICK,
        autoscale="off",
        min_workers=2,
        max_workers=2,
    )
    rt = DSRuntime(cfg, store_root=os.path.join(workdir, f"store_{label}"), clock=clk)
    rt.setup()
    queue_kw = dict(default_visibility=float(serve_job["request_visibility"]),
                    max_receive_count=int(serve_job["request_max_receive_count"]),
                    clock=clk)
    rq_path = os.path.join(workdir, f"requests_{label}.sqlite")
    rq = DurableQueue(rq_path, **queue_kw)
    n = len(bodies)
    if split:
        dq_path = os.path.join(workdir, f"decode_{label}.sqlite")
        dq = DurableQueue(dq_path, **queue_kw)
        # distinct per-role output prefixes keep each pool's RESULTS-*
        # and leases/* segments separately aggregatable
        groups = [
            {"worker_role": "prefill", "request_queue": rq_path,
             "decode_queue": dq_path, "expected_requests": n,
             "output_prefix": "serve/dpre"},
            {"worker_role": "decode", "request_queue": dq_path,
             "expected_requests": n, "output_prefix": "serve/ddec"},
        ]
        outs = {"prefill": "serve/dpre", "decode": "serve/ddec"}
        serving = "decode"
    else:
        dq = None
        groups = [{"request_queue": rq_path, "expected_requests": n,
                   "output_prefix": "serve/mono"} for _ in range(2)]
        outs = {"unified": "serve/mono"}
        serving = "unified"
    rt.submit_job(JobFile(shared=dict(serve_job), groups=groups))
    rt.start_cluster(FleetFile(startup_seconds=TICK, market_seed=7))
    runner = SimRunner(rt, tick_seconds=TICK, on_tick=_sender(rq, clk, arrivals, {}))
    runner.run(max_ticks=600)
    records = _records(rt.store, outs[serving])
    roles = {}
    for role, out in outs.items():
        agg = {k: 0 for k in DISAGG_COUNTERS}
        ttft = 0.0
        for seg in _segments(rt.store, out):
            for k in DISAGG_COUNTERS:
                agg[k] += int(seg.get(k, 0))
            t = seg.get("timing", {}).get("ttft_ticks", {})
            ttft = max(ttft, float(t.get("p99", 0.0)))
        # fleet-level serving latency: the worst worker's p99 TTFT, in
        # engine ticks from admission to first emitted token
        agg["ttft_ticks_p99"] = ttft
        agg["tokens_per_tick"] = agg["tokens_emitted"] / max(agg["ticks"], 1)
        roles[role] = agg
    result = dict(
        roles[serving],
        # handoffs are published by the prefill pool — sum across roles
        handoffs_published=sum(r["handoffs_published"] for r in roles.values()),
        lost_requests=n - len(records),
        dead_letters=rq.counts()["dead"] + (dq.counts()["dead"] if dq else 0),
        roles=roles,
        outputs={uid: r["completion"] for uid, r in records.items()},
    )
    rq.close()
    if dq is not None:
        dq.close()
    reset_serve_state()
    return result


# -------------------------------------------------------------------- drills
def churn_drill(workdir: str) -> dict:
    """Static vs autoscaled fleet under the same spike and the same two
    seeded revocations: a trickle at tick 2, most of the load at tick 4."""
    bodies = churn_request_bodies(10, CHURN_JOB["max_new_tokens"], seed=21)
    oracle = direct_outputs(CHURN_JOB, bodies)
    out = {}
    for name, auto, cap in (("static", "off", 1), ("autoscaled", "slo", 3)):
        r = run_churn_fleet(label=name, autoscale=auto, max_fleet=cap,
                            bodies=bodies, serve_job=CHURN_JOB,
                            arrivals={2: bodies[:3], 4: bodies[3:]},
                            chaos_seed=1234, workdir=workdir)
        r["byte_identical"] = r.pop("outputs") == oracle
        out[name] = r
    return out


def recovery_drill(workdir: str) -> dict:
    """The churn spike and seeded revocations with transient storage and
    queue fault windows riding along every notice, through three fleets:
    generation checkpoints off (every drained request replays its decode
    from token zero), on (drained requests resume mid-generation), and
    on but sabotaged (every record unreadable, so resumes walk the
    fallback ladder down to prefix-hit full replay)."""
    job = dict(CHURN_JOB, max_new_tokens=14)
    bodies = churn_request_bodies(8, 14, seed=33)
    oracle = direct_outputs(job, bodies)
    out = {}
    for name, over, sabotage in (
            ("replay", {"generation_checkpoints": False}, False),
            ("checkpoint", {}, False),
            ("sabotage", {}, True)):
        r = run_churn_fleet(
            label=name, autoscale="slo", max_fleet=3, bodies=bodies,
            serve_job=dict(job, **over), arrivals={2: bodies[:3], 4: bodies[3:]},
            chaos_seed=4321, workdir=workdir,
            flaky_duration=120.0,  # covers notice -> drain -> early resume
            flaky_scope="serve/churn/,kvprefix/", sabotage_checkpoints=sabotage,
        )
        r["byte_identical"] = r.pop("outputs") == oracle
        out[name] = r
    return out


def disagg_drill(workdir: str) -> dict:
    """Monolithic vs role-split fleet at equal hardware (two machines
    each), one arrival per tick so the admission backlog stays shallow
    and TTFT measures prefill latency rather than burst queueing."""
    bodies = disagg_request_bodies(6, long_tail=24, short_tail=4,
                                   long_new=16, short_new=6)
    oracle = direct_outputs(DISAGG_JOB, bodies)
    out = {}
    for name, split in (("monolith", False), ("split", True)):
        r = run_disagg_fleet(label=name, split=split, bodies=bodies,
                             serve_job=DISAGG_JOB,
                             arrivals={2 + i: [b] for i, b in enumerate(bodies)},
                             workdir=workdir)
        r["byte_identical"] = r.pop("outputs") == oracle
        out[name] = r
    out["n_requests"] = len(bodies)
    return out

"""Serving-engine benchmark: fused single-dispatch engine vs the seed's
per-position-group engine, plus the paged-KV-cache engine, on a ragged
continuous-batching scenario — and the shared-prefix radix cache on a
shared-system-prompt scenario.

The ragged scenario is deliberately hostile to per-group dispatching:
mixed prompt lengths and more requests than slots, so mid-stream refills
keep the batch ragged and the seed engine degenerates toward one jitted
call per occupied slot per token.  The fused engine issues exactly one
decode dispatch per tick and ingests prompts in ``prefill_chunk``-token
slices.

The ``paged`` engine is the fused engine with ``cache_mode="paged"`` and
a page pool sized to the workload's *actual* demand instead of the dense
``max_batch x max_len`` worst case; it reports ``peak_cache_bytes`` /
``pages_in_use_peak`` next to dispatches/token, and the run fails if the
paged peak is not strictly below the dense reservation (tokens/sec must
also stay within 10% of the dense fused engine in full runs — wall-clock
is too noisy to gate in ``--smoke``).  The ragged paged run keeps
``prefix_cache=False``: it is the PR 2 per-slot baseline.

The shared-prefix scenario sends many requests carrying one system
prompt with short distinct tails, after a priming request has populated
the radix cache (steady-state serving).  It compares the dense fused
engine, the per-slot paged engine, and the prefix-cache paged engine:
emitted tokens must be byte-identical across all three, the prefix
engine must prefill >= 2x fewer prompt tokens than the per-slot paged
baseline (``prompt_tokens_skipped``), and its ``peak_cache_bytes`` must
come in below the per-slot paged peak (shared pages are stored once,
not per slot).

The mid-page-divergence scenario sends prompts sharing a prefix that
ends *inside* a page (not on a page boundary), after a priming request.
It compares the dense fused engine, the page-aligned prefix engine
(``prefix_match="page"``, the PR 3 behaviour) and the sub-page prefix
engine (``prefix_match="token"``, the default): outputs must be
byte-identical across all three, and the sub-page engine must prefill
strictly fewer prompt tokens than the page-aligned engine — the tokens
it recovers by copy-on-writing the partially-matched page and resuming
prefill from the mid-page offset (``prefix_hit_tokens_partial`` /
``cow_partial_stitches``).

The decode-heavy (speculative) scenario sends short prompts with long
generations at low batch — the latency-bound shape where nearly every
dispatch is a decode tick and speculation pays — and compares
``speculative="off"`` against the ``ngram`` prompt-lookup proposer and
the ``draft`` small-model proposer on the paged engine.
Outputs must be byte-identical across all three (the tentpole's hard
gate: speculation may change only how many tokens land per dispatch,
never which tokens), every speculative engine must actually verify
(``spec_dispatches > 0``), and at least one proposer must land >= 2.0
tokens per verify dispatch (``accepted_per_dispatch``) while strictly
cutting dispatches/token — all counter-based and gated in smoke.  The
>= 1.5x tokens/sec gate runs full-mode only.

The staggered-arrival scenario demonstrates continuous batching: one
long generation plus short requests arriving one per tick, run under
``refill_policy="continuous"`` (freed rows admit mid-flight) and the
``"drain"`` baseline (refill only an empty batch).  Outputs must be
byte-identical — submit-order sampling streams make scheduling policy
invisible to content — while continuous batching must show strictly
lower mean time-to-first-token.  Every scenario additionally records
queue-wait and TTFT percentiles in engine ticks (deterministic on any
host, unlike wall-clock).

The disaggregation scenario runs the same mixed long-prompt /
long-generation workload through two simulated fleets at equal total
hardware (two machines each): a monolithic fleet of unified workers and
a role-split fleet of one prefill worker (chunk-prefills, publishes KV
chains to the prefix store, enqueues sealed handoff records) plus one
decode worker (hydrates chains on demand, decodes every tick).  Both
legs must be byte-identical to a direct-engine oracle with zero lost
requests; every request must travel the handoff path (published ==
admitted == n, zero fallbacks, zero seal rejects); the prefill pool
must never decode; and the decode pool's p99 TTFT (engine ticks from
admission to first token) and tokens-per-engine-tick must strictly beat
the monolith — all counter-derived and gated in smoke.  The >= 1.3x
TTFT-reduction margin runs full-mode only.

Reports tokens/sec and dispatches/token per engine to
``BENCH_serving.json``::

    PYTHONPATH=src python benchmarks/bench_serving.py            # full
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke    # tier-1 CI

Smoke mode shrinks the workload to seconds on CPU but keeps both
structures, so a regression in dispatch count, paged-cache accounting,
prefix hit rate, or paged-vs-dense token parity fails fast without
waiting on wall-clock noise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax


def ragged_requests(n_requests: int, max_new: int, seed: int = 0):
    """Mixed-length prompts: long/short interleaved to force position skew."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lengths = [int(rng.integers(1, 24)) if i % 2 else int(rng.integers(24, 64))
               for i in range(n_requests)]
    return [
        Request(
            uid=f"r{i}",
            prompt=[int(t) for t in rng.integers(1, 200, size=n)],
            max_new_tokens=max_new,
        )
        for i, n in enumerate(lengths)
    ]


def shared_prefix_requests(n_requests: int, max_new: int, *, prefix_len: int,
                           tail_len: int, seed: int = 1):
    """One shared system prompt + short distinct per-request tails."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=prefix_len)]
    return [
        Request(
            uid=f"s{i}",
            prompt=prefix + [int(t) for t in rng.integers(1, 200, size=tail_len)],
            max_new_tokens=max_new,
        )
        for i in range(n_requests)
    ], prefix


def midpage_requests(n_requests: int, max_new: int, *, prefix_len: int,
                     tail_len: int, page_size: int, seed: int = 4):
    """Prompts sharing a prefix that ends MID-page: page-aligned matching
    strands the partial page's tokens; sub-page matching recovers them.
    Returns (requests, priming prompt).  The priming prompt pads the
    shared prefix out to a whole page, so the partially-shared chunk is
    indexed as a FULL page later requests can partially match (only full
    chunks are ever published)."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=prefix_len)]
    pad = -prefix_len % page_size
    prime = prefix + [int(t) for t in rng.integers(1, 200, size=pad)]
    reqs = [
        Request(
            uid=f"m{i}",
            prompt=prefix + [int(t) for t in rng.integers(1, 200, size=tail_len)],
            max_new_tokens=max_new,
        )
        for i in range(n_requests)
    ]
    return reqs, prime


def decode_heavy_requests(n_requests: int, max_new: int, seed: int = 11):
    """Short prompts, long generations: the shape where speculative
    decoding matters.  Almost every dispatch is a decode tick, so
    accepted draft tokens translate ~1:1 into saved target dispatches."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=f"d{i}",
            prompt=[int(t) for t in rng.integers(1, 200,
                                                 size=int(rng.integers(4, 12)))],
            max_new_tokens=max_new,
        )
        for i in range(n_requests)
    ]


def staggered_requests(n_requests: int, max_new: int, seed: int = 7):
    """One long-running generation plus short requests trickling in: the
    head-of-line-blocking shape where continuous batching matters.  A
    drain-then-refill scheduler strands every later arrival behind the
    long request; continuous batching cycles them through the freed
    rows.  Returns (requests, arrival ticks)."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(uid="long",
                    prompt=[int(t) for t in rng.integers(1, 200, size=8)],
                    max_new_tokens=3 * n_requests)]
    for i in range(n_requests - 1):
        n = int(rng.integers(4, 13))
        reqs.append(Request(uid=f"s{i}",
                            prompt=[int(t) for t in rng.integers(1, 200, size=n)],
                            max_new_tokens=max_new))
    arrivals = [0] + [1 + i for i in range(n_requests - 1)]
    return reqs, arrivals


def churn_request_bodies(n_requests: int, max_new: int, *, prefix_len: int,
                         tail_len: int, seed: int = 21):
    """Queue message bodies for the elastic-churn drill: one shared
    page-sized system prefix (so survivors can hydrate it from the
    cross-host store after a revocation) plus short distinct tails."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=prefix_len)]
    return [
        {"uid": f"c{i}",
         "prompt": prefix + [int(t) for t in rng.integers(1, 200,
                                                          size=tail_len)],
         "max_new_tokens": max_new}
        for i in range(n_requests)
    ]


def disagg_request_bodies(n_requests: int, *, prefix_len: int, long_tail: int,
                          short_tail: int, long_new: int, short_new: int,
                          seed: int = 31):
    """Queue message bodies for the disaggregation drill: a shared
    page-sized system prefix, then an alternating mix of long-prompt /
    short-generation and short-prompt / long-generation requests — the
    workload shape where interleaved chunked prefill steals the most
    decode ticks from a monolithic worker."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=prefix_len)]
    bodies = []
    for i in range(n_requests):
        tail_len, max_new = ((long_tail, short_new) if i % 2 == 0
                             else (short_tail, long_new))
        bodies.append({
            "uid": f"g{i}",
            "prompt": prefix + [int(t) for t in rng.integers(1, 200,
                                                             size=tail_len)],
            "max_new_tokens": max_new,
        })
    return bodies


# lease robustness counters aggregated over every segment summary a churn
# run leaves behind (per-worker RESULTS-*.json + drained leases/*.json)
_CHURN_COUNTERS = (
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


def run_churn_fleet(*, label: str, autoscale: str, max_fleet: int, bodies,
                    serve_job: dict, arrivals: dict, chaos_seed: int,
                    workdir: str, tick_seconds: float = 30.0,
                    max_ticks: int = 600, flaky_duration: float = 0.0,
                    flaky_scope: str = "",
                    sabotage_checkpoints: bool = False) -> dict:
    """One simulated serving fleet under an arrival spike and a seeded
    spot-revocation drill: elastic serving leases stream requests from a
    shared DurableQueue, the chaos monkey revokes instances mid-spike
    (the victims drain gracefully and requeue their in-flight work), and
    survivors/replacements resume it — hydrating the shared prefix's KV
    page from the object store instead of re-prefilling.  All latency is
    virtual-clock, so the numbers are deterministic on any host.

    ``flaky_duration`` > 0 opens a transient storage+queue fault window
    alongside every revocation (``ChaosMonkey.recovery_drill``), so the
    drain/resume paths must survive first-attempt put/get/receive
    failures via retry.  ``sabotage_checkpoints`` makes every generation
    checkpoint unreadable (reads under ``checkpoints/`` raise), forcing
    resumes down the fallback ladder to prefix-hit full replay."""
    from repro.core import (
        DSConfig, DSRuntime, FleetFile, JobFile, SimRunner, VirtualClock,
    )
    from repro.core.chaos import ChaosMonkey
    from repro.core.queue import DurableQueue
    from repro.launch.serve import reset_serve_state
    from repro.serving.types import percentiles
    import repro.launch.serve  # noqa: F401  (registers distributed-serve)
    import repro.launch.train  # noqa: F401

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
        monitor_poll_seconds=tick_seconds,
        autoscale=autoscale,
        min_workers=1,
        max_workers=max_fleet,
        autoscale_queue_per_worker=3,
        autoscale_target_p99_ttft=6.0,
        autoscale_up_cooldown_seconds=tick_seconds,
        autoscale_down_cooldown_seconds=600.0,
        autoscale_max_step=2,
    )
    rt = DSRuntime(cfg, store_root=os.path.join(workdir, f"store_{label}"),
                   clock=clk)
    rt.setup()
    if sabotage_checkpoints:
        # total checkpoint loss: puts still land (the durable-before-ack
        # ordering is still exercised) but every read raises, so every
        # resume must fall back to prefix-hit full replay.  The chaos
        # monkey's flaky wrapper stacks on top of this one, so reads are
        # ALSO transiently faulted first — the full ladder in one leg.
        _orig_get = rt.store.get_bytes

        def _sabotaged_get(key, *a, **kw):
            if "/checkpoints/" in key:
                raise FileNotFoundError(f"chaos: checkpoint sabotaged {key!r}")
            return _orig_get(key, *a, **kw)

        rt.store.get_bytes = _sabotaged_get
    rq_path = os.path.join(workdir, f"requests_{label}.sqlite")
    rq = DurableQueue(
        rq_path,
        default_visibility=float(serve_job.get("request_visibility", 240.0)),
        max_receive_count=int(serve_job.get("request_max_receive_count", 6)),
        clock=clk,
    )
    job = dict(serve_job, request_queue=rq_path,
               expected_requests=len(bodies), output_prefix="serve/churn")
    # interchangeable lease permits, one per potential worker: any permit
    # a worker claims resumes that worker's own warm engine
    rt.submit_job(JobFile(shared=job, groups=[{} for _ in range(max_fleet)]))
    rt.start_cluster(FleetFile(startup_seconds=tick_seconds, market_seed=7))
    # first notice lands mid-spike (arrivals peak at tick 4); an event
    # whose victim pool is empty (everything already revoked) stays
    # pending and fires once a replacement is running, so the static
    # single-machine fleet eats both revocations back to back
    if flaky_duration > 0:
        chaos = ChaosMonkey.recovery_drill(
            rt.fleet, clk, seed=chaos_seed, n_revocations=2,
            start=3 * tick_seconds, spacing=3 * tick_seconds,
            notice_seconds=2 * tick_seconds,
            flaky_duration=flaky_duration, flaky_scope=flaky_scope,
            store=rt.store, logs=rt.logs, queue=rq,
        )
    else:
        chaos = ChaosMonkey.revocation_drill(
            rt.fleet, clk, seed=chaos_seed, n_revocations=2,
            start=3 * tick_seconds, spacing=3 * tick_seconds,
            notice_seconds=2 * tick_seconds, store=rt.store, logs=rt.logs,
        )
    submitted_at = {}

    def on_tick(t):
        for body in arrivals.get(t, ()):
            submitted_at[body["uid"]] = clk.now()
            rq.send(dict(body, submitted_at=clk.now()))

    runner = SimRunner(rt, tick_seconds=tick_seconds, chaos=chaos,
                       on_tick=on_tick)
    summary = runner.run(max_ticks=max_ticks)
    req_prefix = "serve/churn/requests/"
    records = {
        info.key[len(req_prefix):-len(".json")]: rt.store.get_json(info.key)
        for info in rt.store.list(req_prefix)
        if info.key.endswith(".json")
    }
    # one cumulative record per worker: the final RESULTS- summary where
    # the worker wrote one, else its last slice/drain record under
    # leases/ (summing both would double-count — each is cumulative)
    finals, slices = {}, {}
    for seg_prefix in ("serve/churn/RESULTS-", "serve/churn/leases/"):
        for info in rt.store.list(seg_prefix):
            if not info.key.endswith(".json"):
                continue
            base = info.key.rsplit("/", 1)[-1][:-len(".json")]
            if "/leases/" in info.key:
                slices[base] = rt.store.get_json(info.key)
            else:
                finals[base.split("RESULTS-", 1)[-1]] = (
                    rt.store.get_json(info.key))
    counters = {k: 0 for k in _CHURN_COUNTERS}
    for seg in {**slices, **finals}.values():
        for k in counters:
            # noop permit summaries carry no counter block
            counters[k] += int(seg.get(k, 0))
    # client-observed latency: submit (queue send) -> completion record,
    # in virtual seconds.  p99 over the request population is the
    # fleet-level SLO the autoscaler is being graded on.
    turnarounds = [rec["done_at"] - submitted_at[uid]
                   for uid, rec in records.items() if uid in submitted_at]
    sim_s = summary.wall_time
    tokens = sum(len(r["completion"]) for r in records.values())
    result = {
        "sim_seconds": round(sim_s, 1),
        "tokens_per_sim_s": round(tokens / max(sim_s, 1e-9), 4),
        "p99_ttft_s": percentiles(turnarounds)["p99"],
        "lost_requests": len(bodies) - len(records),
        "revocations_injected": chaos.counters["revocations"],
        "requests_requeued": counters["drain_requeued_requests"],
        "requests_resumed": counters["requests_resumed"],
        "revocation_notices": counters["revocation_notices"],
        "lease_slices": counters["lease_slices"],
        "lease_resumes": counters["lease_resumes"],
        "prefix_store_pages_hydrated": counters["prefix_store_pages_hydrated"],
        "prefix_store_pages_published": counters["prefix_store_pages_published"],
        "checkpoints_published": counters["checkpoints_published"],
        "checkpoint_resumes": counters["checkpoint_resumes"],
        "tokens_recovered": counters["tokens_recovered"],
        "checkpoint_fallbacks": counters["checkpoint_fallbacks"],
        "decode_tokens_discarded": counters["decode_tokens_discarded"],
        "publish_retries": counters["publish_retries"],
        "prefix_store_hash_mismatches": counters["prefix_store_hash_mismatches"],
        # tokens decode had to redo: everything rolled back at preemption
        # minus everything a checkpoint resume restored (the held-back
        # re-dispatch token stays, by design)
        "tokens_redecoded": (counters["decode_tokens_discarded"]
                             - counters["tokens_recovered"]),
        "storage_faults": chaos.counters.get("storage_faults", 0),
        "queue_faults": chaos.counters.get("queue_faults", 0),
        "workers_peak": max(
            (r.running_instances for r in runner.monitor.history), default=0),
        "ticks": summary.ticks,
        "outputs": {uid: r["completion"] for uid, r in records.items()},
    }
    rq.close()
    reset_serve_state()
    return result


# per-role counters aggregated over a disaggregated fleet's segment
# summaries, keyed by each role pool's output prefix
_DISAGG_COUNTERS = (
    "ticks", "tokens_emitted", "prompt_tokens_ingested",
    "prompt_tokens_skipped", "decode_dispatches", "prefill_dispatches",
    "handoffs_published", "handoffs_admitted",
    "handoff_fallbacks", "handoff_seal_rejects",
    "prefix_store_pages_hydrated", "prefix_store_pages_published",
    "hydration_fetch_ops", "prefix_store_bytes_fetched",
    "publish_dedup_hits",
)


def run_disagg_fleet(*, label: str, split: bool, bodies, serve_job: dict,
                     arrivals: dict, workdir: str,
                     tick_seconds: float = 30.0,
                     max_ticks: int = 600) -> dict:
    """One simulated serving fleet over the disaggregation workload, at
    fixed hardware (two machines, autoscaling off).  ``split=False``
    runs the monolithic baseline: two unified permits draining one
    request queue.  ``split=True`` runs the same two machines role-split
    — one prefill permit that chunk-prefills prompts, publishes their KV
    chains to the prefix store and enqueues sealed handoff records, and
    one decode permit that hydrates those chains on demand and decodes.
    All latency is virtual-clock, and the serving-side metrics (TTFT in
    engine ticks, tokens per engine tick) are counter-derived, so every
    number is deterministic on any host."""
    from repro.core import (
        DSConfig, DSRuntime, FleetFile, JobFile, SimRunner, VirtualClock,
    )
    from repro.core.queue import DurableQueue
    from repro.launch.serve import reset_serve_state
    from repro.serving.types import percentiles
    import repro.launch.serve  # noqa: F401  (registers distributed-serve)
    import repro.launch.train  # noqa: F401

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
        monitor_poll_seconds=tick_seconds,
        autoscale="off",
        min_workers=2,
        max_workers=2,
    )
    rt = DSRuntime(cfg, store_root=os.path.join(workdir, f"store_{label}"),
                   clock=clk)
    rt.setup()
    visibility = float(serve_job.get("request_visibility", 240.0))
    max_rc = int(serve_job.get("request_max_receive_count", 6))
    rq_path = os.path.join(workdir, f"requests_{label}.sqlite")
    rq = DurableQueue(rq_path, default_visibility=visibility,
                      max_receive_count=max_rc, clock=clk)
    n = len(bodies)
    if split:
        dq_path = os.path.join(workdir, f"decode_{label}.sqlite")
        dq = DurableQueue(dq_path, default_visibility=visibility,
                          max_receive_count=max_rc, clock=clk)
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
        serving_role = "decode"
        req_prefix = "serve/ddec/requests/"
    else:
        dq = None
        groups = [
            {"request_queue": rq_path, "expected_requests": n,
             "output_prefix": "serve/mono"}
            for _ in range(2)
        ]
        outs = {"unified": "serve/mono"}
        serving_role = "unified"
        req_prefix = "serve/mono/requests/"
    rt.submit_job(JobFile(shared=dict(serve_job), groups=groups))
    rt.start_cluster(FleetFile(startup_seconds=tick_seconds, market_seed=7))
    submitted_at = {}

    def on_tick(t):
        for body in arrivals.get(t, ()):
            submitted_at[body["uid"]] = clk.now()
            rq.send(dict(body, submitted_at=clk.now()))

    runner = SimRunner(rt, tick_seconds=tick_seconds, on_tick=on_tick)
    summary = runner.run(max_ticks=max_ticks)
    records = {
        info.key[len(req_prefix):-len(".json")]: rt.store.get_json(info.key)
        for info in rt.store.list(req_prefix)
        if info.key.endswith(".json")
    }
    # one cumulative record per worker per role pool (finals supersede
    # that worker's last lease slice, same as the churn aggregation)
    roles = {}
    for role, out in outs.items():
        finals, slices = {}, {}
        for seg_prefix in (f"{out}/RESULTS-", f"{out}/leases/"):
            for info in rt.store.list(seg_prefix):
                if not info.key.endswith(".json"):
                    continue
                base = info.key.rsplit("/", 1)[-1][:-len(".json")]
                if "/leases/" in info.key:
                    slices[base] = rt.store.get_json(info.key)
                else:
                    finals[base.split("RESULTS-", 1)[-1]] = (
                        rt.store.get_json(info.key))
        agg = {k: 0 for k in _DISAGG_COUNTERS}
        ttft = 0.0
        for seg in {**slices, **finals}.values():
            for k in _DISAGG_COUNTERS:
                agg[k] += int(seg.get(k, 0))
            t = seg.get("timing", {}).get("ttft_ticks", {})
            ttft = max(ttft, float(t.get("p99", 0.0)))
        # fleet-level serving latency: the worst worker's p99 TTFT, in
        # engine ticks from admission to first emitted token
        agg["ttft_ticks_p99"] = ttft
        agg["tokens_per_tick"] = round(
            agg["tokens_emitted"] / max(agg["ticks"], 1), 4)
        roles[role] = agg
    serving = roles[serving_role]
    turnarounds = [rec["done_at"] - submitted_at[uid]
                   for uid, rec in records.items() if uid in submitted_at]
    sim_s = summary.wall_time
    tokens = sum(len(r["completion"]) for r in records.values())
    dead = rq.counts()["dead"] + (dq.counts()["dead"] if dq else 0)
    result = {
        "sim_seconds": round(sim_s, 1),
        "tokens_per_sim_s": round(tokens / max(sim_s, 1e-9), 4),
        "p99_turnaround_s": percentiles(turnarounds)["p99"],
        "lost_requests": n - len(records),
        "dead_letters": dead,
        "workers_peak": max(
            (r.running_instances for r in runner.monitor.history), default=0),
        "ticks": summary.ticks,
        # serving-side (decode pool on the split leg, the whole fleet on
        # the monolith): what the role split is supposed to improve
        "ttft_ticks_p99": serving["ttft_ticks_p99"],
        "tokens_per_tick": serving["tokens_per_tick"],
        "prompt_tokens_ingested_serving_side": serving["prompt_tokens_ingested"],
        "prefix_store_pages_hydrated": serving["prefix_store_pages_hydrated"],
        "hydration_fetch_ops": serving["hydration_fetch_ops"],
        "prefix_store_bytes_fetched": serving["prefix_store_bytes_fetched"],
        "handoffs_admitted": serving["handoffs_admitted"],
        "handoff_fallbacks": serving["handoff_fallbacks"],
        "handoff_seal_rejects": serving["handoff_seal_rejects"],
        # handoffs are published by the prefill pool, dedup hits by
        # whichever pool published — sum across roles
        "handoffs_published": sum(r["handoffs_published"]
                                  for r in roles.values()),
        "publish_dedup_hits": sum(r["publish_dedup_hits"]
                                  for r in roles.values()),
        "roles": roles,
        "outputs": {uid: r["completion"] for uid, r in records.items()},
    }
    rq.close()
    if dq is not None:
        dq.close()
    reset_serve_state()
    return result


_COUNTERS = (
    "decode_dispatches", "prefill_dispatches", "dispatches",
    "tokens_emitted", "prompt_tokens_ingested",
    "prompt_tokens_skipped", "prefix_hit_tokens",
    "prefix_hit_tokens_partial", "cow_partial_stitches",
    "spec_dispatches", "draft_dispatches",
    "draft_tokens_proposed", "draft_tokens_accepted", "spec_tokens_emitted",
)


def run_engine(model, params, reqs, *, mode: str, max_batch: int, max_len: int,
               prefill_chunk: int, page_size: int = 0, total_pages: int = 0,
               prefix_cache: bool = False, prefix_match: str = "token",
               speculative: str = "off", spec_k: int = 4,
               draft_model=None, draft_params=None,
               prime=None) -> dict:
    from repro.serving.engine import Request, ServeEngine

    paged = mode.startswith("paged")
    engine = ServeEngine(
        model, params,
        max_batch=max_batch, max_len=max_len,
        prefill_chunk=prefill_chunk,
        dispatch_mode="fused" if paged else mode,
        cache_mode="paged" if paged else "dense",
        **(dict(page_size=page_size, total_pages=total_pages,
                prefix_cache=prefix_cache, prefix_match=prefix_match)
           if paged else {}),
        **(dict(speculative=speculative, spec_k=spec_k,
                draft_model=draft_model, draft_params=draft_params)
           if speculative != "off" else {}),
    )
    # compile both dispatch paths on a throwaway request OUTSIDE the timed
    # region, then measure the real workload from its very first step —
    # otherwise the fused engine's warm-up would silently perform the whole
    # initial prefill phase off the clock and inflate tokens/sec
    engine.submit([Request(uid="__warmup__",
                           prompt=[1] * max(2 * max(prefill_chunk, 1), 2),
                           max_new_tokens=2)])
    engine.run_to_completion()
    if prime is not None:
        # steady-state shared-prefix serving: a priming request populates
        # the radix cache (one prefill of the system prompt) before the
        # measured window — run on every engine so wall-clocks compare
        engine.submit([Request(uid="__prime__", prompt=list(prime),
                               max_new_tokens=2)])
        engine.run_to_completion()
    base = {k: getattr(engine, k, 0) for k in _COUNTERS}
    if paged:
        # re-baseline the page stats too: the warmup request's private
        # pages are freed by now (cached prefix pages stay resident), so
        # the measured window starts from live usage
        alloc_base = engine.page_allocs
        engine.peak_pages = engine.pages_in_use
    # scope the latency samples to the measured window (warmup/prime
    # requests recorded their own)
    waits0 = len(engine.scheduler.queue_waits)
    ttfts0 = len(engine.scheduler.ttfts)

    engine.submit(reqs)
    t0 = time.perf_counter()
    engine.run_to_completion()
    wall = time.perf_counter() - t0
    c = {k: getattr(engine, k, 0) - base[k] for k in _COUNTERS}
    total_tokens = c["tokens_emitted"] + c["prompt_tokens_ingested"]
    out = {
        "dispatch_mode": engine.dispatch_mode,  # paged runs the fused path
        "wall_s": round(wall, 3),
        **c,
        "tokens_per_sec": round(c["tokens_emitted"] / max(wall, 1e-9), 1),
        "dispatches_per_token": round(c["dispatches"] / max(total_tokens, 1), 4),
        # tokens landed per fused verify dispatch (>1 means speculation
        # is paying for itself; exactly the engine's accepted run + 1
        # bonus token per live row)
        "accepted_per_dispatch": round(
            c["spec_tokens_emitted"] / c["spec_dispatches"], 4
        ) if c["spec_dispatches"] else 0.0,
        "prompt_tokens_per_prefill_dispatch": round(
            c["prompt_tokens_ingested"] / max(c["prefill_dispatches"], 1), 2
        ),
        # queue-wait / time-to-first-token percentiles in engine ticks
        # (deterministic, unlike wall-clock) for the measured window
        "timing": engine.scheduler.timing(waits0, ttfts0),
        # emitted tokens per request, for the byte-identity gates
        "outputs": {r.uid: list(r.output) for r in engine.finished
                    if not r.uid.startswith("__")},
    }
    if paged:
        out.update(
            cache_mode="paged",
            prefix_cache=prefix_cache,
            prefix_match=engine.cache_mgr.prefix_match,
            page_size=engine.page_size,
            total_pages=engine.n_pages,
            pages_in_use_peak=engine.peak_pages,
            page_allocs=engine.page_allocs - alloc_base,
            peak_cache_bytes=engine.peak_cache_bytes,
            dense_cache_bytes=engine.dense_cache_bytes,
            pages_shared_peak=engine.pages_shared_peak,
            cow_copies=engine.cow_copies,
            prefix_evictions=engine.prefix_evictions,
            preemptions=engine.preemptions,
        )
    else:
        out.update(cache_mode="dense", peak_cache_bytes=engine.peak_cache_bytes)
    return out


def run_staggered(model, params, reqs, arrivals, *, refill_policy: str,
                  max_batch: int, max_len: int, prefill_chunk: int) -> dict:
    """Staggered-arrival scenario: requests are submitted at the tick
    ``arrivals[i]`` says, while the engine is already generating.  The
    ``continuous`` refill policy admits them into rows the moment one
    frees; the ``drain`` baseline only refills an empty batch, so late
    arrivals stack behind the whole in-flight batch.  TTFT/queue-wait
    are measured in engine ticks (deterministic on any host)."""
    from repro.serving.engine import Request, ServeEngine

    engine = ServeEngine(
        model, params, max_batch=max_batch, max_len=max_len,
        prefill_chunk=prefill_chunk, refill_policy=refill_policy,
    )
    engine.submit([Request(uid="__warmup__",
                           prompt=[1] * max(2 * max(prefill_chunk, 1), 2),
                           max_new_tokens=2)])
    engine.run_to_completion()
    waits0 = len(engine.scheduler.queue_waits)
    ttfts0 = len(engine.scheduler.ttfts)
    base_dispatches = engine.dispatches

    schedule = sorted(zip(arrivals, range(len(reqs))))
    t0 = time.perf_counter()
    i = 0
    tick = 0
    while i < len(schedule) or engine.pending or engine.scheduler.has_active():
        while i < len(schedule) and schedule[i][0] <= tick:
            engine.submit([reqs[schedule[i][1]]])
            i += 1
        engine.step()
        tick += 1
    wall = time.perf_counter() - t0
    timing = engine.scheduler.timing(waits0, ttfts0)
    return {
        "refill_policy": refill_policy,
        "wall_s": round(wall, 3),
        "ticks": tick,
        "dispatches": engine.dispatches - base_dispatches,
        "tokens_emitted": sum(
            len(r.output) for r in engine.finished if not r.uid.startswith("__")
        ),
        "timing": timing,
        "mean_ttft_ticks": timing["ttft_ticks"]["mean"],
        "outputs": {r.uid: list(r.output) for r in engine.finished
                    if not r.uid.startswith("__")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config / short run for tier-1 CI on CPU")
    ap.add_argument("--arch", default="ds-paper-100m")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    args = ap.parse_args(argv)

    from repro.configs import get_arch, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model, ModelRuntime

    enable_compile_cache()

    n_requests = args.requests or (6 if args.smoke else 24)
    max_new = args.max_new or (4 if args.smoke else 32)
    max_batch = 4 if args.smoke else 8
    max_len = 128
    prefill_chunk = 8 if args.smoke else 32

    cfg = reduced(get_arch(args.arch))
    model = Model(cfg, ModelRuntime())
    params = model.init(jax.random.PRNGKey(0))

    # page pool sized to the workload's actual demand: longest request
    # (prompt + generated tokens) rounded up to whole pages, per slot —
    # strictly below the dense max_len reservation
    page_size = 16
    longest = max(len(r.prompt) + r.max_new_tokens
                  for r in ragged_requests(n_requests, max_new))
    pages_per_req = -(-longest // page_size)
    total_pages = max_batch * pages_per_req

    modes = ["grouped", "fused"]
    if model.supports_paged_cache:
        modes.append("paged")
    else:
        print(f"[bench_serving] paged     skipped: arch {args.arch!r} has no "
              "pageable KV cache (rolling window / recurrent state / enc-dec)")

    results = {}
    for mode in modes:
        reqs = ragged_requests(n_requests, max_new)
        # the ragged paged run keeps prefix_cache=False: random prompts
        # share nothing, and this keeps it the PR 2 per-slot baseline the
        # schedule-equality gate below compares against
        results[mode] = run_engine(
            model, params, reqs, mode=mode,
            max_batch=max_batch, max_len=max_len, prefill_chunk=prefill_chunk,
            page_size=page_size, total_pages=total_pages,
        )
        r = results[mode]
        extra = ""
        if mode == "paged":
            extra = (f" peak_cache={r['peak_cache_bytes'] / 1024:.0f}KiB"
                     f"/{r['dense_cache_bytes'] / 1024:.0f}KiB dense"
                     f" pages={r['pages_in_use_peak']}/{r['total_pages']}")
        print(
            f"[bench_serving] {mode:8s} tokens/s={r['tokens_per_sec']:8.1f} "
            f"dispatches/token={r['dispatches_per_token']:.4f} "
            f"(decode={r['decode_dispatches']} prefill={r['prefill_dispatches']})"
            + extra
        )

    # ---------------------------------------------- shared-prefix scenario
    shared_results = {}
    shared_scenario = {}
    if model.supports_paged_cache:
        sp_requests = 6 if args.smoke else n_requests
        sp_batch = 2 if args.smoke else max_batch
        sp_prefix = 32 if args.smoke else 64
        sp_tail = 4 if args.smoke else 8
        _, sp_sys = shared_prefix_requests(
            sp_requests, max_new, prefix_len=sp_prefix, tail_len=sp_tail
        )
        sp_pages_per_req = -(-(sp_prefix + sp_tail + max_new) // page_size)
        sp_total_pages = sp_batch * sp_pages_per_req
        shared_scenario = {
            "n_requests": sp_requests, "max_new_tokens": max_new,
            "max_batch": sp_batch, "max_len": max_len,
            "prefill_chunk": prefill_chunk, "page_size": page_size,
            "total_pages": sp_total_pages,
            "prefix_len": sp_prefix, "tail_len": sp_tail, "primed": True,
        }
        for name, kwargs in (
            ("fused", {}),
            ("paged", dict(page_size=page_size, total_pages=sp_total_pages)),
            ("paged_prefix", dict(page_size=page_size, total_pages=sp_total_pages,
                                  prefix_cache=True)),
        ):
            # fresh Request objects per engine (outputs accumulate in place)
            reqs, _ = shared_prefix_requests(
                sp_requests, max_new, prefix_len=sp_prefix, tail_len=sp_tail
            )
            shared_results[name] = run_engine(
                model, params, reqs,
                mode="paged" if name.startswith("paged") else name,
                max_batch=sp_batch, max_len=max_len,
                prefill_chunk=prefill_chunk, prime=sp_sys, **kwargs,
            )
            r = shared_results[name]
            print(
                f"[bench_serving] shared/{name:12s} tokens/s="
                f"{r['tokens_per_sec']:8.1f} "
                f"prompt_tokens={r['prompt_tokens_ingested']} "
                f"skipped={r.get('prompt_tokens_skipped', 0)} "
                f"peak_cache={r['peak_cache_bytes'] / 1024:.0f}KiB"
                + (f" shared_pages_peak={r['pages_shared_peak']}"
                   if name == "paged_prefix" else "")
            )

    # --------------------------------------- mid-page-divergence scenario
    midpage_results = {}
    midpage_scenario = {}
    if model.supports_paged_cache:
        mp_requests = 6 if args.smoke else n_requests
        mp_batch = 2 if args.smoke else max_batch
        # shared prefix ends MID-page: page-aligned matching reuses only
        # the whole pages below it, sub-page matching recovers the rest
        mp_prefix = (2 * page_size + page_size // 2) if args.smoke \
            else (4 * page_size + page_size // 2)
        mp_tail = 4 if args.smoke else 8
        _, mp_prime = midpage_requests(
            mp_requests, max_new, prefix_len=mp_prefix, tail_len=mp_tail,
            page_size=page_size,
        )
        mp_pages_per_req = -(-(mp_prefix + mp_tail + max_new) // page_size)
        mp_total_pages = (mp_batch + 1) * mp_pages_per_req
        midpage_scenario = {
            "n_requests": mp_requests, "max_new_tokens": max_new,
            "max_batch": mp_batch, "max_len": max_len,
            "prefill_chunk": prefill_chunk, "page_size": page_size,
            "total_pages": mp_total_pages,
            "prefix_len": mp_prefix, "tail_len": mp_tail, "primed": True,
        }
        for name, kwargs in (
            ("fused", {}),
            ("paged_prefix_page", dict(page_size=page_size,
                                       total_pages=mp_total_pages,
                                       prefix_cache=True,
                                       prefix_match="page")),
            ("paged_prefix_token", dict(page_size=page_size,
                                        total_pages=mp_total_pages,
                                        prefix_cache=True,
                                        prefix_match="token")),
        ):
            reqs, _ = midpage_requests(
                mp_requests, max_new, prefix_len=mp_prefix, tail_len=mp_tail,
                page_size=page_size,
            )
            midpage_results[name] = run_engine(
                model, params, reqs,
                mode="paged" if name.startswith("paged") else name,
                max_batch=mp_batch, max_len=max_len,
                prefill_chunk=prefill_chunk, prime=mp_prime, **kwargs,
            )
            r = midpage_results[name]
            print(
                f"[bench_serving] midpage/{name:18s} tokens/s="
                f"{r['tokens_per_sec']:8.1f} "
                f"prompt_tokens={r['prompt_tokens_ingested']} "
                f"skipped={r.get('prompt_tokens_skipped', 0)} "
                f"partial_hits={r.get('prefix_hit_tokens_partial', 0)} "
                f"cow_partial={r.get('cow_partial_stitches', 0)}"
            )

    # ------------------------------------------ decode-heavy (speculative)
    # short prompts, long generations, low batch: the latency-bound
    # regime speculative decoding targets — almost every dispatch is a
    # decode tick and there is no batching to hide per-dispatch cost, so
    # accepted drafts translate directly into fewer target dispatches
    # and lower wall-clock per token.  "off" is the fused paged
    # baseline; "ngram" drafts by prompt-lookup over each request's own
    # history; "draft" runs a separately-initialised draft model (same
    # reduced arch here — a deliberately pessimal draft whose guesses
    # rarely land, demonstrating that byte parity and rollback hold even
    # when every draft is rejected; a real deployment pairs a small
    # draft arch with a large target)
    spec_results = {}
    spec_scenario = {}
    if model.supports_paged_cache:
        sd_requests = 4 if args.smoke else 8
        # speculation pays off where prompt-lookup finds structure, and
        # this model's greedy continuations only settle into repetitive
        # patterns a few dozen tokens in — so the full run generates
        # long and single-stream (the smoke run still gates parity +
        # dispatch reduction; it keeps two rows so the spec tick's
        # mixed live/parked row handling stays covered)
        sd_new = 24 if args.smoke else 640
        sd_batch = 2 if args.smoke else 1
        sd_max_len = max_len if args.smoke else 672
        sd_k = 8
        sd_longest = max(len(r.prompt) + r.max_new_tokens
                         for r in decode_heavy_requests(sd_requests, sd_new))
        sd_total_pages = sd_batch * (-(-sd_longest // page_size))
        spec_scenario = {
            "n_requests": sd_requests, "max_new_tokens": sd_new,
            "max_batch": sd_batch, "max_len": sd_max_len,
            "prefill_chunk": prefill_chunk, "page_size": page_size,
            "total_pages": sd_total_pages, "spec_k": sd_k,
            "draft_arch": args.arch, "draft_init_seed": 7,
        }
        draft_model = Model(cfg, ModelRuntime())
        draft_params = draft_model.init(jax.random.PRNGKey(7))
        for name, kwargs in (
            ("off", {}),
            ("ngram", dict(speculative="ngram", spec_k=sd_k)),
            ("draft", dict(speculative="draft", spec_k=sd_k,
                           draft_model=draft_model,
                           draft_params=draft_params)),
        ):
            reqs = decode_heavy_requests(sd_requests, sd_new)
            spec_results[name] = run_engine(
                model, params, reqs, mode="paged",
                max_batch=sd_batch, max_len=sd_max_len,
                prefill_chunk=prefill_chunk,
                page_size=page_size, total_pages=sd_total_pages, **kwargs,
            )
            r = spec_results[name]
            print(
                f"[bench_serving] spec/{name:6s} tokens/s="
                f"{r['tokens_per_sec']:8.1f} "
                f"dispatches/token={r['dispatches_per_token']:.4f} "
                f"accepted/dispatch={r['accepted_per_dispatch']:.2f} "
                f"(proposed={r['draft_tokens_proposed']} "
                f"accepted={r['draft_tokens_accepted']} "
                f"draft_dispatches={r['draft_dispatches']})"
            )

    # ------------------------------------------- staggered-arrival scenario
    # continuous batching vs the drain-then-refill baseline: one long
    # generation plus short requests arriving one per tick
    st_requests = 8 if args.smoke else 16
    st_batch = 2 if args.smoke else 4
    _, st_arrivals = staggered_requests(st_requests, max_new)
    staggered_results = {}
    staggered_scenario = {
        "n_requests": st_requests, "max_new_tokens": max_new,
        "long_max_new_tokens": 3 * st_requests,
        "max_batch": st_batch, "max_len": max_len,
        "prefill_chunk": prefill_chunk, "arrivals": st_arrivals,
    }
    for policy in ("continuous", "drain"):
        reqs, _ = staggered_requests(st_requests, max_new)
        staggered_results[policy] = run_staggered(
            model, params, reqs, st_arrivals, refill_policy=policy,
            max_batch=st_batch, max_len=max_len, prefill_chunk=prefill_chunk,
        )
        r = staggered_results[policy]
        print(
            f"[bench_serving] staggered/{policy:10s} "
            f"mean_ttft={r['mean_ttft_ticks']:6.2f} ticks "
            f"p90={r['timing']['ttft_ticks']['p90']:.0f} "
            f"queue_wait_p90={r['timing']['queue_wait_ticks']['p90']:.0f} "
            f"({r['ticks']} ticks total)"
        )

    # ------------------------------------------------ elastic churn drill
    # static fleet vs autoscaled fleet, both under the same arrival spike
    # and the same seeded revocation drill: robustness (zero lost
    # requests, byte-identical output) is the hard gate, the autoscaler's
    # p99 win and the survivors' prefix-store hydration are the payoff
    churn_results = {}
    churn_scenario = {}
    recovery_results = {}
    recovery_scenario = {}
    if model.supports_paged_cache:
        import tempfile

        # the decode tail is what keeps requests in flight while the
        # drill fires: one request costs ~(1 + max_new_tokens) engine
        # steps and a lease runs stream_slice_ticks steps per simulator
        # tick, so short completions would drain the spike before the
        # second revocation has a victim with anything to lose
        ch_requests = 10 if args.smoke else 20
        ch_new = 12 if args.smoke else 16
        ch_seed = 1234
        ch_bodies = churn_request_bodies(ch_requests, ch_new,
                                         prefix_len=page_size, tail_len=3)
        ch_job = {
            "arch": args.arch, "arch_overrides": "reduced",
            "max_new_tokens": ch_new, "max_len": 64, "max_batch": 2,
            "prefill_chunk": 8, "cache_mode": "paged",
            "page_size": page_size, "prefix_cache": True,
            "prefix_store": True,
            "stream_slice_ticks": 4, "stream_idle_polls": 60,
            "request_visibility": 240.0, "request_max_receive_count": 6,
        }
        # a trickle, then most of the load at once mid-run (ticks are
        # SimRunner ticks, 30 virtual seconds each)
        ch_arrivals = {2: ch_bodies[:3], 4: ch_bodies[3:]}
        churn_scenario = {
            "n_requests": ch_requests, "max_new_tokens": ch_new,
            "max_batch": 2, "prefill_chunk": 8, "page_size": page_size,
            "prefix_len": page_size, "stream_slice_ticks": 4,
            "chaos_seed": ch_seed, "n_revocations": 2,
            "notice_seconds": 60.0, "tick_seconds": 30.0,
            "min_workers": 1, "max_workers": 3,
            "arrivals_by_tick": {str(k): len(v)
                                 for k, v in ch_arrivals.items()},
        }
        # undisturbed oracle: the same requests through one direct engine
        # (greedy sampling streams are submit-order keyed, so output is
        # scheduling- and fleet-invariant)
        from repro.serving.engine import Request, ServeEngine

        oracle_eng = ServeEngine(model, params, max_batch=2, max_len=64,
                                 prefill_chunk=8)
        oracle_eng.submit([
            Request(uid=b["uid"], prompt=list(b["prompt"]),
                    max_new_tokens=ch_new)
            for b in ch_bodies
        ])
        oracle_eng.run_to_completion()
        oracle = {r.uid: list(r.output) for r in oracle_eng.finished}
        with tempfile.TemporaryDirectory() as ch_dir:
            for name, auto, fleet_cap in (("static", "off", 1),
                                          ("autoscaled", "slo", 3)):
                r = run_churn_fleet(
                    label=name, autoscale=auto, max_fleet=fleet_cap,
                    bodies=ch_bodies, serve_job=ch_job,
                    arrivals=ch_arrivals, chaos_seed=ch_seed,
                    workdir=ch_dir,
                )
                r["byte_identical"] = r["outputs"] == oracle
                churn_results[name] = r
                print(
                    f"[bench_serving] churn/{name:10s} "
                    f"p99_turnaround={r['p99_ttft_s']:6.0f}s "
                    f"lost={r['lost_requests']} "
                    f"revocations={r['revocations_injected']} "
                    f"requeued={r['requests_requeued']} "
                    f"resumed={r['requests_resumed']} "
                    f"hydrated={r['prefix_store_pages_hydrated']} "
                    f"workers_peak={r['workers_peak']} "
                    f"identical={r['byte_identical']}"
                )

        # ------------------------------------------- recovery drill
        # the same spike and seeded revocations, now with transient
        # storage/queue fault windows riding along every notice.  Three
        # fleets, identical chaos: generation checkpoints OFF (every
        # drained request replays its decode from token zero),
        # checkpoints ON (drained requests resume mid-generation and
        # continue pure decode), and checkpoints ON but sabotaged (every
        # record unreadable, so resumes walk the fallback ladder down to
        # prefix-hit full replay).  All three must be byte-identical to
        # the undisturbed oracle and lose nothing; the checkpoint fleet
        # must re-decode a small fraction of the replay fleet's tokens.
        rc_requests = 8 if args.smoke else 16
        rc_new = 14 if args.smoke else 16
        rc_seed = 4321
        rc_flaky = 120.0  # covers notice -> drain -> early resume
        rc_bodies = churn_request_bodies(rc_requests, rc_new,
                                         prefix_len=page_size, tail_len=3,
                                         seed=33)
        rc_job = dict(ch_job, max_new_tokens=rc_new)
        rc_arrivals = {2: rc_bodies[:3], 4: rc_bodies[3:]}
        recovery_scenario = {
            "n_requests": rc_requests, "max_new_tokens": rc_new,
            "max_batch": 2, "prefill_chunk": 8, "page_size": page_size,
            "prefix_len": page_size, "stream_slice_ticks": 4,
            "chaos_seed": rc_seed, "n_revocations": 2,
            "notice_seconds": 60.0, "tick_seconds": 30.0,
            "flaky_duration": rc_flaky,
            "min_workers": 1, "max_workers": 3,
            "arrivals_by_tick": {str(k): len(v)
                                 for k, v in rc_arrivals.items()},
        }
        rc_oracle_eng = ServeEngine(model, params, max_batch=2, max_len=64,
                                    prefill_chunk=8)
        rc_oracle_eng.submit([
            Request(uid=b["uid"], prompt=list(b["prompt"]),
                    max_new_tokens=rc_new)
            for b in rc_bodies
        ])
        rc_oracle_eng.run_to_completion()
        rc_oracle = {r.uid: list(r.output) for r in rc_oracle_eng.finished}
        with tempfile.TemporaryDirectory() as rc_dir:
            for name, job_over, sab in (
                    ("replay", {"generation_checkpoints": False}, False),
                    ("checkpoint", {}, False),
                    ("sabotage", {}, True)):
                r = run_churn_fleet(
                    label=name, autoscale="slo", max_fleet=3,
                    bodies=rc_bodies, serve_job=dict(rc_job, **job_over),
                    arrivals=rc_arrivals, chaos_seed=rc_seed,
                    workdir=rc_dir, flaky_duration=rc_flaky,
                    flaky_scope="serve/churn/,kvprefix/",
                    sabotage_checkpoints=sab,
                )
                r["byte_identical"] = r["outputs"] == rc_oracle
                recovery_results[name] = r
                print(
                    f"[bench_serving] recovery/{name:10s} "
                    f"lost={r['lost_requests']} "
                    f"ckpts={r['checkpoints_published']} "
                    f"resumes={r['checkpoint_resumes']} "
                    f"recovered={r['tokens_recovered']} "
                    f"redecoded={r['tokens_redecoded']} "
                    f"fallbacks={r['checkpoint_fallbacks']} "
                    f"storage_faults={r['storage_faults']} "
                    f"queue_faults={r['queue_faults']} "
                    f"identical={r['byte_identical']}"
                )

    # ------------------------------------ disaggregated prefill/decode
    # monolithic vs role-split serving at equal total hardware (two
    # machines each): prefill workers chunk-prefill and publish KV
    # chains + sealed handoff records, decode workers hydrate on demand
    # and spend every engine tick decoding.  Byte identity against the
    # undisturbed single-engine oracle is the hard gate; the payoff is
    # decode-side TTFT and tokens-per-tick beating the monolith, whose
    # interleaved chunked prefill steals decode ticks.
    disagg_results = {}
    disagg_scenario = {}
    if model.supports_paged_cache:
        import tempfile

        from repro.serving.engine import Request, ServeEngine

        dg_requests = 6 if args.smoke else 12
        dg_long_new, dg_short_new = 16, 6
        dg_long_tail, dg_short_tail = 24, 4
        dg_bodies = disagg_request_bodies(
            dg_requests, prefix_len=page_size,
            long_tail=dg_long_tail, short_tail=dg_short_tail,
            long_new=dg_long_new, short_new=dg_short_new,
        )
        dg_job = {
            "arch": args.arch, "arch_overrides": "reduced",
            "max_len": 64, "max_batch": 2,
            "prefill_chunk": 8, "cache_mode": "paged",
            "page_size": page_size, "prefix_cache": True,
            "prefix_store": True,
            # one chunk per engine tick: without the per-tick ingest cap
            # a whole prompt lands in a single step and prefill never
            # contends with decode, which is exactly the interference
            # the role split exists to remove (a decode worker's
            # hydrated admissions ingest only the one-token frontier)
            "prefill_token_budget": 8,
            "stream_slice_ticks": 4, "stream_idle_polls": 60,
            "request_visibility": 240.0, "request_max_receive_count": 6,
        }
        # paced arrivals (one per tick): the admission backlog stays
        # shallow, so TTFT measures prefill latency — the thing the role
        # split changes — instead of burst queueing, which is identical
        # for both legs
        dg_arrivals = {2 + i: [b] for i, b in enumerate(dg_bodies)}
        disagg_scenario = {
            "n_requests": dg_requests,
            "long_max_new_tokens": dg_long_new,
            "short_max_new_tokens": dg_short_new,
            "long_tail": dg_long_tail, "short_tail": dg_short_tail,
            "max_batch": 2, "prefill_chunk": 8, "page_size": page_size,
            "prefill_token_budget": 8,
            "prefix_len": page_size, "stream_slice_ticks": 4,
            "tick_seconds": 30.0, "machines_per_leg": 2,
            "arrivals_by_tick": {str(k): len(v)
                                 for k, v in dg_arrivals.items()},
        }
        # undisturbed oracle: one direct unified engine (greedy bodies,
        # so output is scheduling- and fleet-topology-invariant)
        dg_oracle_eng = ServeEngine(model, params, max_batch=2, max_len=64,
                                    prefill_chunk=8)
        dg_oracle_eng.submit([
            Request(uid=b["uid"], prompt=list(b["prompt"]),
                    max_new_tokens=b["max_new_tokens"])
            for b in dg_bodies
        ])
        dg_oracle_eng.run_to_completion()
        dg_oracle = {r.uid: list(r.output) for r in dg_oracle_eng.finished}
        with tempfile.TemporaryDirectory() as dg_dir:
            for name, split_flag in (("monolith", False), ("split", True)):
                r = run_disagg_fleet(
                    label=name, split=split_flag, bodies=dg_bodies,
                    serve_job=dg_job, arrivals=dg_arrivals, workdir=dg_dir,
                )
                r["byte_identical"] = r["outputs"] == dg_oracle
                disagg_results[name] = r
                print(
                    f"[bench_serving] disagg/{name:8s} "
                    f"ttft_p99={r['ttft_ticks_p99']:5.1f} ticks "
                    f"tokens/tick={r['tokens_per_tick']:.3f} "
                    f"lost={r['lost_requests']} "
                    f"handoffs={r['handoffs_published']}/"
                    f"{r['handoffs_admitted']} "
                    f"hydrated={r['prefix_store_pages_hydrated']} "
                    f"fallbacks={r['handoff_fallbacks']} "
                    f"identical={r['byte_identical']}"
                )

    report = {
        "arch": args.arch,
        "smoke": args.smoke,
        "scenario": {
            "n_requests": n_requests, "max_new_tokens": max_new,
            "max_batch": max_batch, "max_len": max_len,
            "prefill_chunk": prefill_chunk,
            "page_size": page_size, "total_pages": total_pages,
        },
        "engines": results,
        "dispatch_reduction": round(
            results["grouped"]["dispatches_per_token"]
            / max(results["fused"]["dispatches_per_token"], 1e-9),
            2,
        ),
    }
    paged_speed = 1.0
    if "paged" in results:
        paged_speed = (results["paged"]["tokens_per_sec"]
                       / max(results["fused"]["tokens_per_sec"], 1e-9))
        report["paged_cache_reduction"] = round(
            results["paged"]["dense_cache_bytes"]
            / max(results["paged"]["peak_cache_bytes"], 1), 2
        )
        report["paged_tokens_per_sec_vs_fused"] = round(paged_speed, 3)
    if staggered_results:
        report["continuous_batching"] = {
            "scenario": staggered_scenario,
            "engines": staggered_results,
            "ttft_reduction": round(
                staggered_results["drain"]["mean_ttft_ticks"]
                / max(staggered_results["continuous"]["mean_ttft_ticks"], 1e-9),
                2,
            ),
        }
    if shared_results:
        sp, spp = shared_results["paged"], shared_results["paged_prefix"]
        report["shared_prefix"] = {
            "scenario": shared_scenario,
            "engines": shared_results,
            "prefill_reduction": round(
                sp["prompt_tokens_ingested"]
                / max(spp["prompt_tokens_ingested"], 1), 2
            ),
            "peak_reduction_vs_paged": round(
                sp["peak_cache_bytes"] / max(spp["peak_cache_bytes"], 1), 2
            ),
        }
    if spec_results:
        off = spec_results["off"]
        report["speculative"] = {
            "scenario": spec_scenario,
            "engines": spec_results,
            "best_proposer": max(
                ("ngram", "draft"),
                key=lambda n: spec_results[n]["tokens_per_sec"],
            ),
            "tokens_per_sec_vs_off": {
                n: round(spec_results[n]["tokens_per_sec"]
                         / max(off["tokens_per_sec"], 1e-9), 3)
                for n in ("ngram", "draft")
            },
            "dispatch_reduction_vs_off": {
                n: round(off["dispatches_per_token"]
                         / max(spec_results[n]["dispatches_per_token"], 1e-9), 2)
                for n in ("ngram", "draft")
            },
        }
    if churn_results:
        report["elastic_churn"] = {
            "scenario": churn_scenario,
            "engines": churn_results,
            "p99_ttft_reduction": round(
                churn_results["static"]["p99_ttft_s"]
                / max(churn_results["autoscaled"]["p99_ttft_s"], 1e-9), 2
            ),
        }
    if recovery_results:
        report["recovery_drill"] = {
            "scenario": recovery_scenario,
            "engines": recovery_results,
            # how many fewer tokens the checkpointing fleet had to decode
            # twice, vs replaying every drained generation from scratch
            "redecode_reduction": round(
                recovery_results["replay"]["tokens_redecoded"]
                / max(recovery_results["checkpoint"]["tokens_redecoded"], 1),
                2,
            ),
        }
    if disagg_results:
        dg_mono = disagg_results["monolith"]
        dg_split = disagg_results["split"]
        report["disaggregation"] = {
            "scenario": disagg_scenario,
            "engines": disagg_results,
            # decode-side admission-to-first-token, vs the monolith whose
            # chunked prefill interleaves into the same engine ticks
            "decode_ttft_p99_reduction": round(
                dg_mono["ttft_ticks_p99"]
                / max(dg_split["ttft_ticks_p99"], 1e-9), 2
            ),
            "decode_tokens_per_tick_vs_monolith": round(
                dg_split["tokens_per_tick"]
                / max(dg_mono["tokens_per_tick"], 1e-9), 3
            ),
        }
    if midpage_results:
        mp_page = midpage_results["paged_prefix_page"]
        mp_tok = midpage_results["paged_prefix_token"]
        report["midpage_divergence"] = {
            "scenario": midpage_scenario,
            "engines": midpage_results,
            # prompt tokens the sub-page stitch recovers beyond whole pages
            "prefill_reduction_vs_page_aligned": round(
                mp_page["prompt_tokens_ingested"]
                / max(mp_tok["prompt_tokens_ingested"], 1), 2
            ),
        }

    # the byte-identity gates compare full output dicts; keep them out of
    # the written report (per-request token lists, not metrics)
    outputs = {}
    for prefix, group in (("", results), ("shared/", shared_results),
                          ("midpage/", midpage_results),
                          ("spec/", spec_results),
                          ("staggered/", staggered_results),
                          ("churn/", churn_results),
                          ("recovery/", recovery_results),
                          ("disagg/", disagg_results)):
        for name, r in group.items():
            outputs[prefix + name] = r.pop("outputs")
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[bench_serving] wrote {args.out} "
          f"(dispatch reduction {report['dispatch_reduction']}x"
          + (f", paged cache reduction {report['paged_cache_reduction']}x, "
             f"paged speed {paged_speed:.2f}x fused" if "paged" in results else "")
          + (f", shared-prefix prefill reduction "
             f"{report['shared_prefix']['prefill_reduction']}x"
             if shared_results else "")
          + (f", mid-page prefill reduction "
             f"{report['midpage_divergence']['prefill_reduction_vs_page_aligned']}x"
             f" vs page-aligned"
             if midpage_results else "")
          + (f", continuous-batching TTFT reduction "
             f"{report['continuous_batching']['ttft_reduction']}x"
             if staggered_results else "")
          + (f", speculative dispatch reduction "
             f"{max(report['speculative']['dispatch_reduction_vs_off'].values())}x"
             if spec_results else "")
          + (f", churn p99 reduction "
             f"{report['elastic_churn']['p99_ttft_reduction']}x"
             if churn_results else "")
          + (f", recovery re-decode reduction "
             f"{report['recovery_drill']['redecode_reduction']}x"
             if recovery_results else "")
          + (f", disagg decode TTFT reduction "
             f"{report['disaggregation']['decode_ttft_p99_reduction']}x"
             if disagg_results else "")
          + ")")

    # the whole point of the fused engine: strictly fewer dispatches/token
    if results["fused"]["dispatches_per_token"] >= results["grouped"]["dispatches_per_token"]:
        print("[bench_serving] REGRESSION: fused engine not below grouped dispatch rate")
        return 1
    if "paged" in results:
        # and of the paged cache: peak bytes strictly below the dense reservation
        if results["paged"]["peak_cache_bytes"] >= results["paged"]["dense_cache_bytes"]:
            print("[bench_serving] REGRESSION: paged peak not below dense reservation")
            return 1
        # parity in output quality: paged must emit byte-identical tokens
        # on the same dispatch schedule
        if (results["paged"]["dispatches_per_token"] != results["fused"]["dispatches_per_token"]
                or results["paged"]["dispatches"] != results["fused"]["dispatches"]
                or outputs["paged"] != outputs["fused"]):
            print("[bench_serving] REGRESSION: paged schedule/output diverged from fused")
            return 1
        # wall-clock gate only outside smoke (CI boxes are too noisy)
        if not args.smoke and paged_speed < 0.9:
            print(f"[bench_serving] REGRESSION: paged tokens/sec {paged_speed:.2f}x "
                  "fused (< 0.9)")
            return 1
    if shared_results:
        sp = report["shared_prefix"]
        # prefix sharing must never change emitted tokens...
        if not (outputs["shared/fused"] == outputs["shared/paged"]
                == outputs["shared/paged_prefix"]):
            print("[bench_serving] REGRESSION: shared-prefix outputs diverged "
                  "from the dense fused engine")
            return 1
        # ...must actually hit (and skip) the shared system prompt...
        if (shared_results["paged_prefix"]["prompt_tokens_skipped"] <= 0
                or shared_results["paged_prefix"]["prefix_hit_tokens"] <= 0):
            print("[bench_serving] REGRESSION: shared-prefix scenario had a "
                  "0% prefix hit rate")
            return 1
        # ...>= 2x fewer prompt tokens prefilled than the per-slot paged
        # engine, at a strictly lower cache peak (pages stored once)
        if sp["prefill_reduction"] < 2.0:
            print(f"[bench_serving] REGRESSION: prefill reduction "
                  f"{sp['prefill_reduction']}x < 2x")
            return 1
        if (shared_results["paged_prefix"]["peak_cache_bytes"]
                >= shared_results["paged"]["peak_cache_bytes"]):
            print("[bench_serving] REGRESSION: prefix-cache peak not below "
                  "the per-slot paged peak")
            return 1
    if midpage_results:
        mp = report["midpage_divergence"]
        mp_page = midpage_results["paged_prefix_page"]
        mp_tok = midpage_results["paged_prefix_token"]
        # sub-page reuse must never change emitted tokens...
        if not (outputs["midpage/fused"] == outputs["midpage/paged_prefix_page"]
                == outputs["midpage/paged_prefix_token"]):
            print("[bench_serving] REGRESSION: mid-page-divergence outputs "
                  "diverged from the dense fused engine")
            return 1
        # ...must actually reuse tokens INSIDE the first divergent page...
        if (mp_tok["prefix_hit_tokens_partial"] <= 0
                or mp_tok["cow_partial_stitches"] <= 0):
            print("[bench_serving] REGRESSION: mid-page scenario never "
                  "stitched a partial page")
            return 1
        # ...and prefill strictly fewer prompt tokens than page-aligned
        # matching at the same page size
        if mp_tok["prompt_tokens_ingested"] >= mp_page["prompt_tokens_ingested"]:
            print("[bench_serving] REGRESSION: sub-page matching did not "
                  "reduce prompt tokens prefilled vs page-aligned")
            return 1
        if mp_page["prefix_hit_tokens_partial"] != 0:
            print("[bench_serving] REGRESSION: page-aligned engine reported "
                  "partial hits")
            return 1
    if spec_results:
        # the tentpole's hard gate: speculation must never change emitted
        # tokens — both proposers byte-identical to the plain fused engine
        if not (outputs["spec/off"] == outputs["spec/ngram"]
                == outputs["spec/draft"]):
            print("[bench_serving] REGRESSION: speculative outputs diverged "
                  "from the non-speculative engine")
            return 1
        for n in ("ngram", "draft"):
            if spec_results[n]["spec_dispatches"] <= 0:
                print(f"[bench_serving] REGRESSION: spec/{n} never ran a "
                      "verify dispatch")
                return 1
        # at least one proposer must land >= 2 tokens per verify dispatch
        # and strictly cut target dispatches per token (both counter-based
        # and deterministic, so gated in smoke too)
        best_acc = max(spec_results[n]["accepted_per_dispatch"]
                       for n in ("ngram", "draft"))
        if best_acc < 2.0:
            print(f"[bench_serving] REGRESSION: best accepted/dispatch "
                  f"{best_acc:.2f} < 2.0")
            return 1
        off_dpt = spec_results["off"]["dispatches_per_token"]
        if min(spec_results[n]["dispatches_per_token"]
               for n in ("ngram", "draft")) >= off_dpt:
            print("[bench_serving] REGRESSION: no proposer reduced "
                  "dispatches/token below the non-speculative engine")
            return 1
        # wall-clock gate only outside smoke (CI boxes are too noisy)
        best_speed = max(
            report["speculative"]["tokens_per_sec_vs_off"].values())
        if not args.smoke and best_speed < 1.5:
            print(f"[bench_serving] REGRESSION: best speculative tokens/sec "
                  f"{best_speed:.2f}x off (< 1.5)")
            return 1
    if staggered_results:
        # scheduling must never change tokens: both policies draw from the
        # same submit-order sampling streams
        if outputs["staggered/continuous"] != outputs["staggered/drain"]:
            print("[bench_serving] REGRESSION: refill policy changed emitted "
                  "tokens")
            return 1
        # the point of continuous batching: staggered arrivals reach their
        # first token strictly sooner than under drain-then-refill
        if (staggered_results["continuous"]["mean_ttft_ticks"]
                >= staggered_results["drain"]["mean_ttft_ticks"]):
            print("[bench_serving] REGRESSION: continuous batching did not "
                  "beat drain-then-refill mean TTFT")
            return 1
    if churn_results:
        for name in ("static", "autoscaled"):
            r = churn_results[name]
            # the robustness tentpole's hard gates: a revocation drill
            # must lose NOTHING and change NOTHING
            if r["lost_requests"] != 0 or not r["byte_identical"]:
                print(f"[bench_serving] REGRESSION: churn/{name} lost "
                      f"{r['lost_requests']} request(s) or diverged from "
                      "the undisturbed run")
                return 1
            if r["revocations_injected"] < 2:
                print(f"[bench_serving] REGRESSION: churn/{name} injected "
                      f"only {r['revocations_injected']} revocation(s)")
                return 1
        # survivors/replacements must warm up from the cross-host prefix
        # store, not re-prefill (that is what makes churn cheap)
        if churn_results["autoscaled"]["prefix_store_pages_hydrated"] <= 0:
            print("[bench_serving] REGRESSION: no prefix-store hydration "
                  "on post-revocation reruns")
            return 1
        # and the autoscaler's reason to exist: the spike's p99
        # turnaround must beat the static fleet's
        if (churn_results["autoscaled"]["p99_ttft_s"]
                >= churn_results["static"]["p99_ttft_s"]):
            print("[bench_serving] REGRESSION: autoscaled fleet did not "
                  "beat the static fleet's p99 turnaround under churn")
            return 1
    if recovery_results:
        for name in ("replay", "checkpoint", "sabotage"):
            r = recovery_results[name]
            # same hard gates as churn: revocations + flaky storage must
            # lose NOTHING and change NOTHING, whichever rung of the
            # fallback ladder the fleet lands on
            if r["lost_requests"] != 0 or not r["byte_identical"]:
                print(f"[bench_serving] REGRESSION: recovery/{name} lost "
                      f"{r['lost_requests']} request(s) or diverged from "
                      "the undisturbed run")
                return 1
            if r["revocations_injected"] < 2:
                print(f"[bench_serving] REGRESSION: recovery/{name} injected "
                      f"only {r['revocations_injected']} revocation(s)")
                return 1
            # the flaky windows must actually have injected faults the
            # retry/backoff discipline then survived
            if r["storage_faults"] <= 0 or r["queue_faults"] <= 0:
                print(f"[bench_serving] REGRESSION: recovery/{name} saw no "
                      f"injected storage ({r['storage_faults']}) or queue "
                      f"({r['queue_faults']}) faults")
                return 1
        rr = recovery_results["replay"]
        rc = recovery_results["checkpoint"]
        rs = recovery_results["sabotage"]
        # the baseline must really be checkpoint-free and really have had
        # decode progress to lose, or the comparison is vacuous
        if rr["checkpoints_published"] != 0 or rr["tokens_recovered"] != 0:
            print("[bench_serving] REGRESSION: recovery/replay leg wrote "
                  "checkpoints despite generation_checkpoints=false")
            return 1
        if rr["tokens_redecoded"] <= 0:
            print("[bench_serving] REGRESSION: recovery drill never "
                  "interrupted a generation mid-decode")
            return 1
        # the tentpole's payoff: checkpointed drains hand their emitted
        # tail to the resuming worker instead of re-decoding it
        if (rc["checkpoints_published"] <= 0 or rc["checkpoint_resumes"] <= 0
                or rc["tokens_recovered"] <= 0):
            print("[bench_serving] REGRESSION: recovery/checkpoint leg never "
                  "resumed from a generation checkpoint")
            return 1
        if report["recovery_drill"]["redecode_reduction"] < 3.0:
            print(f"[bench_serving] REGRESSION: re-decode reduction "
                  f"{report['recovery_drill']['redecode_reduction']}x < 3x")
            return 1
        # fallback ladder: with every checkpoint unreadable the fleet must
        # degrade to full replay (counted), never resume from a checkpoint,
        # and still change nothing
        if rs["checkpoint_fallbacks"] <= 0 or rs["checkpoint_resumes"] != 0:
            print("[bench_serving] REGRESSION: recovery/sabotage leg did not "
                  "walk the checkpoint fallback ladder "
                  f"(fallbacks={rs['checkpoint_fallbacks']}, "
                  f"resumes={rs['checkpoint_resumes']})")
            return 1
    if disagg_results:
        dg_mono = disagg_results["monolith"]
        dg_split = disagg_results["split"]
        for name in ("monolith", "split"):
            r = disagg_results[name]
            # the hard gates: a fleet topology change must lose NOTHING
            # and change NOTHING, and every queue must drain clean
            if r["lost_requests"] != 0 or not r["byte_identical"]:
                print(f"[bench_serving] REGRESSION: disagg/{name} lost "
                      f"{r['lost_requests']} request(s) or diverged from "
                      "the undisturbed run")
                return 1
            if r["dead_letters"] != 0:
                print(f"[bench_serving] REGRESSION: disagg/{name} left "
                      f"{r['dead_letters']} dead-lettered message(s)")
                return 1
        # every request must travel the storage-mediated handoff path —
        # published once, admitted once, no replay fallbacks needed on a
        # healthy store, no seal rejects
        dg_n = disagg_scenario["n_requests"]
        if not (dg_split["handoffs_published"] == dg_split["handoffs_admitted"]
                == dg_n):
            print(f"[bench_serving] REGRESSION: disagg/split handoffs "
                  f"published={dg_split['handoffs_published']} "
                  f"admitted={dg_split['handoffs_admitted']} != {dg_n}")
            return 1
        if dg_split["handoff_fallbacks"] != 0 or dg_split["handoff_seal_rejects"] != 0:
            print(f"[bench_serving] REGRESSION: disagg/split walked the "
                  f"replay ladder on a healthy store "
                  f"(fallbacks={dg_split['handoff_fallbacks']}, "
                  f"seal_rejects={dg_split['handoff_seal_rejects']})")
            return 1
        if dg_mono["handoffs_published"] != 0:
            print("[bench_serving] REGRESSION: disagg/monolith published "
                  "handoff records from unified workers")
            return 1
        # role purity: the prefill pool never decodes a token
        dg_pre = dg_split["roles"]["prefill"]
        if dg_pre["tokens_emitted"] != 0 or dg_pre["decode_dispatches"] != 0:
            print(f"[bench_serving] REGRESSION: disagg prefill pool decoded "
                  f"(tokens={dg_pre['tokens_emitted']}, "
                  f"decode_dispatches={dg_pre['decode_dispatches']})")
            return 1
        # the decode pool must really hydrate its KV from the store, not
        # re-prefill the prompts the prefill pool already processed
        if (dg_split["prefix_store_pages_hydrated"] <= 0
                or dg_split["hydration_fetch_ops"] <= 0
                or dg_split["prefix_store_bytes_fetched"] <= 0):
            print("[bench_serving] REGRESSION: disagg decode pool never "
                  "hydrated from the prefix store")
            return 1
        # the payoff, both counter-derived and deterministic: decode-side
        # p99 TTFT and tokens-per-tick strictly beat the monolith
        if dg_split["ttft_ticks_p99"] >= dg_mono["ttft_ticks_p99"]:
            print(f"[bench_serving] REGRESSION: disagg decode p99 TTFT "
                  f"{dg_split['ttft_ticks_p99']:.1f} ticks not below "
                  f"monolith {dg_mono['ttft_ticks_p99']:.1f}")
            return 1
        if dg_split["tokens_per_tick"] <= dg_mono["tokens_per_tick"]:
            print(f"[bench_serving] REGRESSION: disagg decode tokens/tick "
                  f"{dg_split['tokens_per_tick']:.3f} not above monolith "
                  f"{dg_mono['tokens_per_tick']:.3f}")
            return 1
        # margin gate only outside smoke (the full workload is big enough
        # to demand a real win, not a tie-breaker)
        dg_ratio = report["disaggregation"]["decode_ttft_p99_reduction"]
        if not args.smoke and dg_ratio < 1.3:
            print(f"[bench_serving] REGRESSION: disagg decode TTFT reduction "
                  f"{dg_ratio}x < 1.3x")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Queue-fed serving through the distributed tier: the
``distributed-serve`` payload streaming requests from a DurableQueue on
``SimRunner`` (submit -> stream -> per-request ack -> teardown sweep),
and a two-worker cross-host prefix hit through the ObjectStore."""

import os

os.environ.setdefault("DS_DEBUG_INVARIANTS", "1")

import jax
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
from repro.core.queue import DurableQueue
from repro.launch.serve import _build_engine
from repro.launch.train import build_model
from repro.serving.engine import Request, ServeEngine

SHARED = {
    "arch": "ds-paper-100m",
    "arch_overrides": "reduced",
    "max_new_tokens": 4,
    "max_len": 32,
    "max_batch": 2,
    "prefill_chunk": 4,
}


def _runtime(tmp_path, clk, *, machines=1, **cfg_kwargs):
    kwargs = dict(
        app_name="Stream",
        payload="distributed-serve",
        cluster_machines=machines,
        tasks_per_machine=1,
        machine_type=["sim.large"],
        machine_price=1.0,
        sqs_message_visibility=240.0,
        check_if_done=False,
    )
    kwargs.update(cfg_kwargs)
    cfg = DSConfig(**kwargs)
    rt = DSRuntime(cfg, store_root=str(tmp_path / "store"), clock=clk)
    rt.setup()
    return rt


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


@pytest.mark.parametrize("key,value", [
    ("dispatch_mode", "grouped"), ("sample_on_device", False),
    ("refill_policy", "drain"), ("prefix_match", "page"),
])
def test_job_with_retired_engine_option_is_refused(key, value):
    """A job dict is outside input: an option the engine no longer has is
    refused by name, not silently served by the one remaining path."""
    with pytest.raises(ValueError, match=key):
        _build_engine(dict(SHARED, **{key: value}), ctx=None)


def test_stream_payload_serves_acks_and_drains(tmp_path):
    """Tier-1 smoke of the queue-fed serving tier: request messages are
    streamed into the scheduler, acked per completion, the request queue
    drains to zero, the monitor tears the fleet down, and every
    completion is byte-identical to the one-shot static batch."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk)
    prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 10], [11], [12, 13]]
    rq_path = str(tmp_path / "requests.sqlite")
    rq = DurableQueue(rq_path, clock=clk)
    rq.send_batch([
        {"uid": f"q{i}", "prompt": p, "max_new_tokens": 4}
        for i, p in enumerate(prompts)
    ])
    rt.submit_job(JobFile(
        shared=dict(SHARED),
        groups=[{
            "request_queue": rq_path,
            "expected_requests": len(prompts),
            "output_prefix": "serve/stream0",
        }],
    ))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=200)
    assert summary.jobs_done == 1, f"{summary}"
    # every request message individually acknowledged; nothing dead
    counts = rq.counts()
    assert counts == {"visible": 0, "in_flight": 0, "dead": 0}, counts
    res = rt.store.get_json("serve/stream0/RESULTS.json")
    assert len(res["requests"]) == len(prompts)
    # durable-before-ack: each completion was persisted individually
    # BEFORE its message was deleted, so a crash after an ack can never
    # lose a served request
    for i in range(len(prompts)):
        rec = rt.store.get_json(f"serve/stream0/requests/q{i}.json")
        assert rec == res["requests"][f"q{i}"]
    want = _reference_outputs(SHARED, prompts, 4)
    got = {uid: r["completion"] for uid, r in res["requests"].items()}
    assert got == want, "streamed completions diverged from the static batch"
    # the full scheduler/cache snapshot reaches RESULTS.json
    assert res["admissions"] >= len(prompts)
    assert res["ticks"] > 0 and res["dispatches"] > 0
    assert res["timing"]["ttft_ticks"]["n"] == len(prompts)
    assert res["timing"]["queue_wait_ticks"]["mean"] >= 0.0


def test_stream_payload_idle_exit_without_expected_count(tmp_path):
    """Without ``expected_requests`` the stream lease exits after N idle
    polls once the queue runs dry (workers shut themselves down)."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk)
    rq_path = str(tmp_path / "requests.sqlite")
    rq = DurableQueue(rq_path, clock=clk)
    rq.send({"uid": "only", "prompt": [1, 2, 3]})
    rt.submit_job(JobFile(
        shared=dict(SHARED),
        groups=[{"request_queue": rq_path, "stream_idle_polls": 2,
                 "output_prefix": "serve/stream1"}],
    ))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=200)
    assert summary.jobs_done == 1
    res = rt.store.get_json("serve/stream1/RESULTS.json")
    assert set(res["requests"]) == {"only"}
    assert rq.counts()["visible"] == 0


def test_stream_uid_collision_serves_both_prompts(tmp_path):
    """Two DIFFERENT prompts under one client-supplied uid must both be
    served (the second under a disambiguated uid), never silently
    conflated into one completion with both messages acked."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk)
    rq_path = str(tmp_path / "requests.sqlite")
    rq = DurableQueue(rq_path, clock=clk)
    rq.send_batch([
        {"uid": "dup", "prompt": [1, 2, 3]},
        {"uid": "dup", "prompt": [9, 9]},  # distinct prompt, same uid
    ])
    rt.submit_job(JobFile(
        shared=dict(SHARED),
        groups=[{"request_queue": rq_path, "expected_requests": 2,
                 "output_prefix": "serve/stream3"}],
    ))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=200)
    assert summary.jobs_done == 1, f"{summary}"
    res = rt.store.get_json("serve/stream3/RESULTS.json")
    assert len(res["requests"]) == 2
    prompts_served = sorted(r["prompt"] for r in res["requests"].values())
    assert prompts_served == [[1, 2, 3], [9, 9]]
    assert rq.counts() == {"visible": 0, "in_flight": 0, "dead": 0}


def test_stream_lease_resume_merges_previous_holders_completions(tmp_path):
    """A retried lease (previous holder crashed after acking some
    requests but before its summary) must fold the persisted per-request
    records into its own RESULTS.json and count them toward
    ``expected_requests`` — otherwise the summary under-reports and the
    lease can only exit through the idle path."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk)
    # the crashed holder served q0 and durably recorded it pre-ack
    pre = {"prompt": [9, 9], "completion": [1, 2, 3, 4]}
    rt.store.put_json("serve/stream2/requests/q0.json", pre)
    rq_path = str(tmp_path / "requests.sqlite")
    rq = DurableQueue(rq_path, clock=clk)
    rq.send({"uid": "q1", "prompt": [1, 2, 3]})  # the resurfaced remainder
    rt.submit_job(JobFile(
        shared=dict(SHARED),
        groups=[{"request_queue": rq_path, "expected_requests": 2,
                 "output_prefix": "serve/stream2"}],
    ))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=200)
    assert summary.jobs_done == 1, f"{summary}"
    res = rt.store.get_json("serve/stream2/RESULTS.json")
    assert set(res["requests"]) == {"q0", "q1"}
    assert res["requests"]["q0"] == pre  # pre-crash completion preserved
    assert rq.counts() == {"visible": 0, "in_flight": 0, "dead": 0}


def test_two_workers_share_prefix_pages_through_object_store(tmp_path):
    """Cross-host prefix cache: worker A serves a batch carrying a
    system prompt and publishes its KV pages to the ObjectStore; worker
    B (a different task on a different machine, cold radix cache) must
    hydrate those pages and skip the shared prefill — byte-identically."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk, machines=2)
    sys_prompt = [11, 12, 13, 14, 15, 16, 17, 18,
                  21, 22, 23, 24, 25, 26, 27, 28]
    shared = dict(
        SHARED,
        cache_mode="paged",
        page_size=8,
        prefix_cache=True,
        prefix_store=True,
    )
    jobs = [
        {"prompts": [sys_prompt + [31], sys_prompt + [32]],
         "output_prefix": "serve/w0"},
        {"prompts": [sys_prompt + [41], sys_prompt + [42]],
         "output_prefix": "serve/w1"},
    ]
    rt.submit_job(JobFile(shared=shared, groups=jobs))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=200)
    assert summary.jobs_done == 2, f"{summary}"
    res = [rt.store.get_json(f"serve/w{i}/RESULTS.json") for i in range(2)]
    # SimRunner gives no ordering guarantee over which worker's prompt
    # becomes resident first, so assert the ROLES symmetrically: exactly
    # one worker published the two prefix pages from scratch, and the
    # other hydrated both from the store instead of prefilling
    pubs = [r["prefix_store_pages_published"] for r in res]
    hyds = [r["prefix_store_pages_hydrated"] for r in res]
    assert sorted(pubs) == [0, 2], (pubs, hyds)
    assert sorted(hyds) == [0, 2], (pubs, hyds)
    publisher = pubs.index(2)
    hydrator = 1 - publisher
    assert hyds[publisher] == 0 and pubs[hydrator] == 0
    # the hydrator skipped the whole system prompt without dispatching it
    assert res[hydrator]["prompt_tokens_skipped"] >= len(sys_prompt)
    assert (res[hydrator]["prompt_tokens_ingested"]
            < res[publisher]["prompt_tokens_ingested"])
    # hydrated pages must be byte-equivalent to local prefill: BOTH
    # workers' completions match a dense engine computing from scratch
    for w, r in enumerate(res):
        want = _reference_outputs(shared, jobs[w]["prompts"], 4)
        # payload uids are req<i>, oracle uids q<i>: compare by position
        for i in range(2):
            assert r["requests"][f"req{i}"]["completion"] == want[f"q{i}"], (
                f"worker {w} request {i} diverged"
            )


def _worker_counters(rt, out):
    """Per-worker counter records under one output prefix, final
    RESULTS- summaries superseding slice-cumulative leases/ records
    (same merge rule the serving benchmarks use)."""
    recs = {}
    for info in rt.store.list(f"{out}/leases/"):
        wid = info.key.rsplit("/", 1)[-1][: -len(".json")]
        recs[wid] = rt.store.get_json(info.key)
    for info in rt.store.list(f"{out}/"):
        name = info.key[len(out) + 1:]
        if name.startswith("RESULTS-") and name.endswith(".json"):
            wid = name[len("RESULTS-"): -len(".json")]
            recs[wid] = rt.store.get_json(info.key)
    return list(recs.values())


def test_disaggregated_prefill_decode_roles_split_the_pipeline(tmp_path):
    """Role-split serving end to end: a prefill-role permit leases the
    request queue, publishes each prompt's KV chain through the prefix
    store and enqueues sealed handoff records; a decode-role permit
    leases those records, demand-hydrates exactly the chained pages and
    decodes to completion — byte-identical to a dense monolith, with
    the prefill side never emitting a token."""
    clk = VirtualClock()
    rt = _runtime(tmp_path, clk, machines=2)
    prompts = [
        [1, 2, 3, 4, 5, 6, 7, 8, 9, 10],
        [1, 2, 3, 4, 5, 6, 7, 8, 11, 12, 13],
        [21, 22, 23],
        [4, 5],
    ]
    n = len(prompts)
    rq_path = str(tmp_path / "requests.sqlite")
    dq_path = str(tmp_path / "decode.sqlite")
    rq = DurableQueue(rq_path, clock=clk)
    rq.send_batch([
        {"uid": f"d{i}", "prompt": p, "max_new_tokens": 4}
        for i, p in enumerate(prompts)
    ])
    shared = dict(
        SHARED,
        cache_mode="paged",
        page_size=8,
        prefix_cache=True,
        prefix_store=True,
        stream_slice_ticks=4,
        stream_idle_polls=200,
    )
    rt.submit_job(JobFile(shared=shared, groups=[
        {"worker_role": "prefill", "request_queue": rq_path,
         "decode_queue": dq_path, "expected_requests": n,
         "output_prefix": "serve/dpre"},
        {"worker_role": "decode", "request_queue": dq_path,
         "expected_requests": n, "output_prefix": "serve/ddec"},
    ]))
    rt.start_cluster(FleetFile(startup_seconds=0.0))
    summary = SimRunner(rt, tick_seconds=30.0).run(max_ticks=400)
    assert summary.jobs_done == 2, f"{summary}"
    # both queues fully drained: every request handed off and acked,
    # every handoff admitted and acked, nothing dead
    assert rq.counts() == {"visible": 0, "in_flight": 0, "dead": 0}
    dq = DurableQueue(dq_path, clock=clk)
    assert dq.counts() == {"visible": 0, "in_flight": 0, "dead": 0}
    # one sealed handoff marker per prompt on the prefill side
    from repro.launch.serve import _handoff_valid
    for i in range(n):
        marker = rt.store.get_json(f"serve/dpre/handoffs/d{i}.json")
        assert _handoff_valid(marker), marker
        assert marker["prompt"] == prompts[i] and marker["output"] == []
    # completions land on the decode side, byte-identical to a dense
    # monolithic engine computing everything from scratch
    want = _reference_outputs(SHARED, prompts, 4)
    for i in range(n):
        rec = rt.store.get_json(f"serve/ddec/requests/d{i}.json")
        assert rec["prompt"] == prompts[i]
        assert rec["completion"] == want[f"q{i}"], f"request d{i} diverged"
    pre = _worker_counters(rt, "serve/dpre")
    dec = _worker_counters(rt, "serve/ddec")
    # the split of labor: prefill published every handoff and decoded
    # nothing; decode admitted every handoff without a single fallback
    # and pulled real KV bytes out of the store to do it
    assert sum(r.get("handoffs_published", 0) for r in pre) == n
    assert sum(r.get("tokens_emitted", 0) for r in pre) == 0
    assert sum(r.get("handoffs_admitted", 0) for r in dec) == n
    assert sum(r.get("handoff_fallbacks", 0) for r in dec) == 0
    assert sum(r.get("prefix_store_pages_hydrated", 0) for r in dec) > 0
    assert sum(r.get("hydration_fetch_ops", 0) for r in dec) > 0
    assert sum(r.get("prefix_store_bytes_fetched", 0) for r in dec) > 0

"""The reduction from a profiler trace to busy time, idle share,
per-dispatch device time and kernel time: on events worked by hand, and
on a small trace recorded on a TPU v5e and committed beside this file."""

import gzip
import json
import os

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401 - puts the harness on the path
from chipbench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_ds100m_short.json.gz")
MS = 1_000_000  # ns


def hand_events():
    ev = tracing.Events()
    # host: a prefill dispatch at 0, a decode dispatch at 10 ms, inside two steps
    ev.host = [(0, 9 * MS, "bench.step"), (0, 1 * MS, "bench.dispatch.prefill"),
               (10 * MS, 16 * MS, "bench.step"), (10 * MS, 11 * MS, "bench.dispatch.decode")]
    # device: the prefill program runs 1-6 ms, the decode program 11-14 ms
    ev.modules = [(1 * MS, 6 * MS, "jit_step(1)"), (11 * MS, 14 * MS, "jit_step(2)")]
    kernel = '%closed_call.{} = f32[8,2,128]{{2,1,0}} custom-call(s32[8]), custom_call_target="tpu_custom_call"'
    ev.ops = [(1 * MS, 3 * MS, "%fusion.1 = f32[8]{0} fusion()"), (2 * MS, 4 * MS, kernel.format(1)),
              (5 * MS, 6 * MS, "%fusion.2 = f32[8]{0} fusion()"), (11 * MS, 12 * MS, kernel.format(2)),
              (12 * MS, 14 * MS, "%fusion.1 = f32[8]{0} fusion()")]
    return ev


def test_hand_worked_trace():
    s = tracing.summarize(hand_events(), window_s=0.016)
    # ops cover 1-4, 5-6 and 11-14 ms: 7 ms busy
    assert s.busy_s == pytest.approx(0.007)
    assert s.kernel_s == pytest.approx(0.003)
    assert s.dispatch_s == {"prefill": [pytest.approx(0.005)], "decode": [pytest.approx(0.003)]}
    assert s.device_ops[0] == ["fusion.1 f32[8]", pytest.approx(0.004)]
    assert ["kernel closed_call.1 f32[8,2,128]", pytest.approx(0.002)] in s.device_ops
    # idle 0-1, 4-5, 6-11 and 14-16 ms; the gap at 6-11 ms is 3 ms in the
    # first step and 2 ms between steps (host), the rest inside steps
    idle = dict(s.idle_gaps)
    assert sum(idle.values()) == pytest.approx(0.016 - 0.007)
    assert set(idle) <= {"bench.step", "bench.dispatch.prefill", "bench.dispatch.decode", "host"}


def test_self_time_leaves_out_nested_ops():
    ops = [(0, 10, "loop"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"), (12, 13, "a")]
    own = tracing.self_times(ops)
    assert own == pytest.approx({"loop": 4e-9, "a": 3e-9, "b": 3e-9, "c": 1e-9})


def test_union_merges_overlaps():
    assert tracing.union([(5, 7, "a"), (1, 3, "b"), (2, 4, "c"), (7, 8, "d")]) == [(1, 4), (5, 8)]


def load_recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    ev = tracing.Events(ops=[tuple(x) for x in d["ops"]], modules=[tuple(x) for x in d["modules"]],
                        host=[tuple(x) for x in d["host"]])
    return ev, d


def test_recorded_trace():
    """A 0.9 s trace of a ds-paper-100m short-prompt run on one TPU v5e
    (``tools/record_trace.py``; operation names shortened)."""
    ev, d = load_recorded()
    s = tracing.summarize(ev, d["window_s"])
    # busy by an independent sweep over a millisecond-free integer timeline
    edges = sorted({t for a, b, _ in ev.ops for t in (a, b)})
    covered = 0
    for lo, hi in zip(edges, edges[1:]):
        if any(a <= lo and hi <= b for a, b, _ in ev.ops):
            covered += hi - lo
    assert s.busy_s == pytest.approx(covered / 1e9)
    assert 0 < s.busy_s <= d["window_s"]
    n_host = {k: sum(1 for h in ev.host if h[2] == f"bench.dispatch.{k}") for k in ("prefill", "decode")}
    for kind in ("prefill", "decode"):
        assert len(s.dispatch_s[kind]) == pytest.approx(n_host[kind], abs=1)
    assert s.kernel_s > 0
    assert s.kernel_s == pytest.approx(sum(b - a for a, b, n in ev.ops if tracing.KERNEL in n) / 1e9)
    # decode dispatches are shorter than prefill dispatches at these shapes
    assert np.mean(s.dispatch_s["decode"]) < np.mean(s.dispatch_s["prefill"])
    for key, want in d["expected"].items():
        assert getattr(s, key) == pytest.approx(want)

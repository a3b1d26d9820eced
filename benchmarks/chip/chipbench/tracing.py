"""The profiler trace of a run's traced part, and its reduction to
device busy time, per-dispatch device time, kernel time and idle gaps.

The trace is the JAX profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData``.  Device events come from the first TPU
plane: its ``XLA Ops`` line (one event per operation run on the chip)
and its ``XLA Modules`` line (one event per program execution).  Host
events are the benchmark's own spans (``bench.*``, see
:mod:`chipbench.driver`), on the host plane, on the same clock.

- busy: the union of the operation intervals; idle share is 1 minus
  busy over the traced window (host clock, start to stop).
- per-dispatch device time: the first program execution that starts
  after a ``bench.dispatch.<kind>`` span begins is that dispatch's
  program (the engine waits for each dispatch's tokens before its next
  host work, so the copy-on-write copies and the next dispatch start
  later).
- kernel time: the operations whose text contains ``KERNEL`` (the paged
  attention kernel's Mosaic call).
- device ops: time by operation, each operation's own time (less the
  operations nested in it, as a loop's body is in the loop), named by
  its instruction name and result type.
- idle gaps: each gap between operations is put down to the innermost
  ``bench.*`` host span covering its midpoint ("host" where none does).
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List

# the paged attention kernel is the serving path's only Mosaic call today
# (its pallas_call carries no name into the trace): its op is the one
# custom call whose target is the TPU's custom-call target
KERNEL = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclass
class Events:
    """The parts of a trace the reduction reads, times in ns."""

    ops: List[tuple] = field(default_factory=list)  # (start, end, name)
    modules: List[tuple] = field(default_factory=list)  # (start, end, name)
    host: List[tuple] = field(default_factory=list)  # (start, end, name)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: float
    dispatch_s: Dict[str, List[float]]
    device_ops: List[list]
    idle_gaps: List[list]


class Tracer:
    """Starts and stops the profiler at planned times of the window."""

    def __init__(self, log_dir: str, start_s: float, stop_s: float):
        self.log_dir, self.start_s, self.stop_s = log_dir, start_s, stop_s

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no Python call tracing: it slows the host
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


def load(log_dir: str) -> Events:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ev = Events()
    device = next((p for p in data.planes if p.name.startswith("/device:TPU:")), None)
    if device is None:
        raise ValueError("the trace has no TPU device plane")
    for line in device.lines:
        if line.name == OPS_LINE:
            ev.ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
        elif line.name == MODULES_LINE:
            ev.modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                ev.host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name.startswith(HOST_PREFIX)]
    ev.ops.sort()
    ev.modules.sort()
    ev.host.sort()
    return ev


def union(intervals) -> List[tuple]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[list] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def dispatch_kinds(ev: Events) -> Dict[str, List[float]]:
    """Device seconds of each dispatch, by kind: the first program
    execution to start after a ``bench.dispatch.<kind>`` span begins (and
    before the next one) is that dispatch's program."""
    spans = [(s, n.rsplit(".", 1)[-1]) for s, _, n in ev.host if n.startswith("bench.dispatch.")]
    starts = [m[0] for m in ev.modules]
    out: Dict[str, List[float]] = defaultdict(list)
    for i, (s, kind) in enumerate(spans):
        j = bisect.bisect_left(starts, s)
        nxt = spans[i + 1][0] if i + 1 < len(spans) else float("inf")
        if j < len(starts) and starts[j] < nxt:
            out[kind].append((ev.modules[j][1] - ev.modules[j][0]) / 1e9)
    return dict(out)


def idle_gaps(ev: Events, busy: List[tuple], lo: int, hi: int) -> List[list]:
    """Idle time inside ``[lo, hi]`` by the innermost host span at each gap."""
    by: Counter = Counter()
    host_starts = [h[0] for h in ev.host]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(host_starts, mid)
        # spans nest (a dispatch inside a step), so the covering ones are
        # among the last few that began before the midpoint
        cover = [h for h in ev.host[max(0, i - 32):i] if h[1] >= mid]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "host"
        by[name] += (b - a) / 1e9
    return [[n, s] for n, s in by.most_common(10)]


def short_name(op: str) -> str:
    """``%fusion.12 = f32[8,128]{...} fusion(...)`` -> ``fusion.12 f32[8,128]``."""
    name, _, rest = op.partition(" = ")
    kind = "kernel " if KERNEL in op else ""
    return f"{kind}{name.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"[:120]


def self_times(ops) -> Counter:
    """Each operation's time less the time of the operations nested in it."""
    own: Counter = Counter()
    stack: List[list] = []  # [end, name]
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            own[stack[-1][1]] -= (e - s) / 1e9
        own[n] += (e - s) / 1e9
        stack.append([e, n])
    return own


def summarize(ev: Events, window_s: float) -> TraceSummary:
    busy = union(ev.ops)
    busy_s = sum(e - s for s, e in busy) / 1e9
    kernel_s = sum(e - s for s, e, n in ev.ops if KERNEL in n) / 1e9
    per_op: Counter = Counter()
    for n, t in self_times(ev.ops).items():
        per_op[short_name(n)] += t
    lo = min([h[0] for h in ev.host] + [b[0] for b in busy], default=0)
    hi = max([h[1] for h in ev.host] + [b[1] for b in busy], default=0)
    return TraceSummary(
        window_s=window_s,
        busy_s=busy_s,
        kernel_s=kernel_s,
        dispatch_s=dispatch_kinds(ev),
        device_ops=[[n, s] for n, s in per_op.most_common(10)],
        idle_gaps=idle_gaps(ev, busy, lo, hi),
    )


def read(log_dir: str, host_window: tuple) -> TraceSummary:
    return summarize(load(log_dir), host_window[1] - host_window[0])

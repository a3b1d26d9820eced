"""The serving engine's own spans (``serve.*``) in a traced run, and their
reduction: the device's idle time put down to what the engine's host
code was doing, and how full its prefill dispatches were.

The engine wraps each activity of a tick in a
``jax.profiler.TraceAnnotation`` (``docs/serving.md``, Tracing): the tick
(``serve.step``), admission, page reservation, building the inputs, the
page-table upload, each dispatch (``serve.dispatch.<kind>``, with its
``rows`` and ``tokens``), the read-back, per-row accept, prefix-cache and
store work.  They land on the profiler's host plane, on the clock of the
device plane, with their arguments as event stats.  Device operations
come from :func:`chipbench.tracing.load`.

- host gap: device-idle time inside ``serve.step`` spans, per
  ``serve.dispatch.*`` span inside them.
- prefill occupancy: the prompt tokens the prefill dispatches carried
  (their ``tokens``) over the slots they offered (``max_batch`` x
  ``prefill_chunk`` each).
- idle by span: each idle stretch of the device split among the
  innermost ``serve.*`` spans covering it; what no span covers is
  "outside the engine".

A run without a trace, or a trace that holds no ``serve.*`` span (a
program without them), reads ``None``.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
from collections import Counter
from dataclasses import dataclass, field
from typing import List, Optional

from chipbench import cells, tracing

PREFIX = "serve."
OUTSIDE = "outside the engine"


@dataclass
class Span:
    start: float  # ns
    end: float
    name: str
    args: dict = field(default_factory=dict)


@dataclass
class ProgramTrace:
    """``serve.*`` spans sorted by start (a parent before its children),
    the union of device-op intervals, and the traced part's bounds (ns)."""

    spans: List[Span]
    busy: List[tuple]
    lo: float
    hi: float


def xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_spans(path: str) -> List[Span]:
    """The ``serve.*`` events of a ``.xplane.pb``'s host planes, with their
    arguments."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [Span(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                          for e in line.events if e.name.startswith(PREFIX)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


@functools.lru_cache(maxsize=1)
def _load(log_dir: str, path: str) -> Optional[ProgramTrace]:
    spans = read_spans(path)
    if not spans:
        return None
    ev = tracing.load(log_dir)
    busy = tracing.union(ev.ops)
    edges = [s.start for s in spans] + [h[0] for h in ev.host] + [b[0] for b in busy]
    ends = [s.end for s in spans] + [h[1] for h in ev.host] + [b[1] for b in busy]
    return ProgramTrace(spans, busy, min(edges), max(ends))


def load(log_dir: str) -> Optional[ProgramTrace]:
    """The trace under ``log_dir``, read once and kept by its file's path."""
    path = xplane(log_dir)
    return _load(log_dir, path) if path else None


def of_run(run) -> Optional[ProgramTrace]:
    """The trace ``harness.main`` wrote for the run's traced part."""
    if run.trace is None:
        return None
    return load(os.path.join(cells.ROOT, ".bench_trace", run.cell.name))


# ------------------------------------------------------------------ reduction
def idle_within(busy: List[tuple], a: float, b: float) -> float:
    """Time in ``[a, b]`` that no busy interval covers."""
    i = max(0, bisect.bisect_right(busy, (a, float("inf"))) - 1)
    covered = 0.0
    while i < len(busy) and busy[i][0] < b:
        covered += max(0.0, min(busy[i][1], b) - max(busy[i][0], a))
        i += 1
    return (b - a) - covered


def host_gap_ns(pt: ProgramTrace) -> Optional[float]:
    """Device-idle ns inside ``serve.step`` spans per dispatch made in them."""
    steps = [(s.start, s.end) for s in pt.spans if s.name == "serve.step"]
    starts = [a for a, _ in steps]
    n = 0
    for s in pt.spans:
        if s.name.startswith("serve.dispatch."):
            i = bisect.bisect_right(starts, s.start) - 1
            if i >= 0 and s.start <= steps[i][1]:
                n += 1
    if not n:
        return None
    return sum(idle_within(pt.busy, a, b) for a, b in steps) / n


def occupancy(pt: ProgramTrace, max_batch: int, chunk: int) -> Optional[float]:
    """Prompt tokens over the ``max_batch x chunk`` slots of the prefill
    dispatches, in percent."""
    tokens = [s.args["tokens"] for s in pt.spans if s.name == "serve.dispatch.prefill"]
    if not tokens:
        return None
    return 100.0 * sum(tokens) / (len(tokens) * max_batch * chunk)


def innermost(spans: List[Span]) -> List[tuple]:
    """Non-overlapping ``(start, end, name)`` pieces in time order: at each
    instant some span covers, the innermost one.  Spans nest; one that
    outlives its parent is cut at its parent's end."""
    out: List[tuple] = []
    stack: List[tuple] = []  # (end, name)
    t = 0.0
    for sp in spans:
        while stack and stack[-1][0] <= sp.start:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and sp.start > t:
            out.append((t, sp.start, stack[-1][1]))
        t = sp.start
        stack.append((min(sp.end, stack[-1][0]) if stack else sp.end, sp.name))
    while stack:
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
            t = end
    return out


def idle_by_span(pt: ProgramTrace) -> List[list]:
    """Idle seconds of the traced part by the innermost ``serve.*`` span
    covering them, largest first."""
    pieces = innermost(pt.spans)
    edges = [pt.lo] + [x for iv in pt.busy for x in iv] + [pt.hi]
    by: Counter = Counter()
    j = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, pt.lo), min(b, pt.hi)
        if b <= a:
            continue
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        left = b - a
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            overlap = min(pieces[k][1], b) - max(pieces[k][0], a)
            if overlap > 0:
                by[pieces[k][2]] += overlap
                left -= overlap
            k += 1
        if left > 0:
            by[OUTSIDE] += left
    return [[name, ns / 1e9] for name, ns in by.most_common()]


# -------------------------------------------------------------------- metrics
def host_gap_ms(run) -> Optional[float]:
    pt = of_run(run)
    gap = host_gap_ns(pt) if pt is not None else None
    return gap / 1e6 if gap is not None else None


def prefill_occupancy(run) -> Optional[float]:
    pt = of_run(run)
    if pt is None:
        return None
    eng = run.cell.engine
    return occupancy(pt, eng["max_batch"], eng["prefill_chunk"])

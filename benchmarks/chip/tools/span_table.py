"""The device's idle time in a traced run, put down to the engine's spans.

    python3 benchmarks/chip/tools/span_table.py --workload <name>

Reads the trace that a ``--trace 1`` run of ``run.py`` left under
``.bench_trace/<name>`` and prints one JSON object: idle seconds and host
seconds by the innermost ``serve.*`` span, the read-back's idle time
before the program starts and after it ends, the idle time inside
``serve.step`` spans, the host gap per dispatch, the prefill occupancy,
the counts of steps and dispatches, and, beside them, the idle time
inside the benchmark's own ``bench.step`` spans and its idle gaps by
``bench.*`` span (each gap whole to the span covering its midpoint).
"""

import argparse
import bisect
import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, program_spans, tracing  # noqa: E402


def readback_edges(pt) -> tuple:
    """Idle seconds inside ``serve.readback`` spans before the device's
    first operation in the span (the program not yet started) and after
    its last (the ids on their way to the host)."""
    starts = [b[0] for b in pt.busy]
    before = after = 0.0
    for s in pt.spans:
        if s.name != "serve.readback":
            continue
        k = bisect.bisect_right(starts, s.start) - 1
        if k < 0 or pt.busy[k][1] <= s.start:
            k += 1
        m = bisect.bisect_left(starts, s.end) - 1
        if k <= m:
            before += max(0.0, pt.busy[k][0] - s.start)
            after += max(0.0, s.end - pt.busy[m][1])
    return before / 1e9, after / 1e9


def table(log_dir: str, engine: dict) -> dict:
    pt = program_spans.load(log_dir)
    if pt is None:
        return {"serve_spans": 0}
    steps = [(s.start, s.end) for s in pt.spans if s.name == "serve.step"]
    host = Counter()
    for a, b, name in program_spans.innermost(pt.spans):
        host[name] += (b - a) / 1e9
    ev = tracing.load(log_dir)
    bench_steps = [(a, b) for a, b, name in ev.host if name == "bench.step"]
    gap = program_spans.host_gap_ns(pt)
    return {
        "serve_spans": len(pt.spans),
        "steps": len(steps),
        "dispatches": dict(Counter(s.name for s in pt.spans if s.name.startswith("serve.dispatch."))),
        "traced_s": (pt.hi - pt.lo) / 1e9,
        "idle_in_steps_s": sum(program_spans.idle_within(pt.busy, a, b) for a, b in steps) / 1e9,
        "idle_in_bench_steps_s": sum(program_spans.idle_within(pt.busy, a, b)
                                     for a, b in bench_steps) / 1e9,
        "host_gap_ms": gap / 1e6 if gap is not None else None,
        "prefill_occupancy": program_spans.occupancy(pt, engine["max_batch"], engine["prefill_chunk"]),
        "idle_by_span": program_spans.idle_by_span(pt),
        "readback_idle_before_after_s": readback_edges(pt),
        "host_s_by_span": [[n, s] for n, s in host.most_common()],
        "bench_idle_gaps": tracing.idle_gaps(ev, pt.busy, pt.lo, pt.hi),
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    args = p.parse_args()
    cell = cells.load(args.workload)
    out = table(os.path.join(cells.ROOT, ".bench_trace", args.workload), cell.engine)
    print(json.dumps(out))
    return 0 if out["serve_spans"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Record a short traced window of a cell and keep its reduced events, for
the trace reduction's test (``tests/data``).

    python3 benchmarks/chip/tools/record_trace.py --workload <name> --seed <n> --out <file.json.gz>
"""

import argparse
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, harness, tracing  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--trace-seconds", type=float, default=0.6)
    p.add_argument("--out", required=True)
    args = p.parse_args()
    cell = cells.load(args.workload)
    ready = harness.prepare(cell)
    if isinstance(ready, int):
        return ready
    peak, counter = ready
    trace_dir = os.path.join(cells.ROOT, ".bench_trace", "record")
    run = harness.run_cell(cell, args.seed, args.seconds, trace=True, t_start=time.perf_counter(),
                           compile_count=counter, peak=peak, trace_dir=trace_dir,
                           trace_seconds=args.trace_seconds, check_outputs=False)
    ev = tracing.load(trace_dir)

    def slim(name):
        return tracing.short_name(name) + (" " + tracing.KERNEL if tracing.KERNEL in name else "")

    s = run.trace
    out = {"workload": cell.name, "device": run.device, "window_s": s.window_s,
           "ops": [(a, b, slim(n)) for a, b, n in ev.ops], "modules": ev.modules, "host": ev.host,
           "expected": {"busy_s": s.busy_s, "kernel_s": s.kernel_s}}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f)
    print(json.dumps({"ops": len(ev.ops), "modules": len(ev.modules), "host": len(ev.host),
                      "busy_s": s.busy_s, "kernel_s": s.kernel_s, "window_s": s.window_s,
                      "dispatches": {k: len(v) for k, v in s.dispatch_s.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings for a cell's correctness limit, in one process: the widest
logit gap of the program as configured, and of the control, on many
seeds at the cell's own size and load.

    python3 benchmarks/chip/tools/control.py --workload <name> --seeds 1,2,3 --seconds 20 --dtype float32,bfloat16

The control is the program with its own lower-precision path switched
on (``ModelRuntime(dtype=bfloat16)``), the step that would tempt a later
change; ``--fault`` plants faults of ``chipbench.faults`` instead, one
after another (``--fault cache_unchanged,token_altered``).  One line per run: dtype, seed, the widest gap and the gap of
each sampled request.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, faults, harness  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--dtype", default="float32,bfloat16")
    p.add_argument("--fault", default="", help="faults of chipbench.faults, comma-separated")
    args = p.parse_args()
    cell = cells.load(args.workload)
    ready = harness.prepare(cell)
    if isinstance(ready, int):
        return ready
    peak, counter = ready
    runs = [(d, f) for d in args.dtype.split(",") for f in args.fault.split(",")]
    for dtype, fault in runs:
        for seed in (int(x) for x in args.seeds.split(",")):
            t0 = time.perf_counter()
            run = harness.run_cell(cell, seed, args.seconds, trace=False, t_start=t0,
                                   compile_count=counter, peak=peak, dtype=dtype,
                                   fault=faults.FAULTS[fault] if fault else None)
            print(json.dumps({"workload": cell.name, "dtype": dtype, "fault": fault, "seed": seed,
                              **run.gap, "checks": run.checks, "failed": harness.failed(run),
                              "secs": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

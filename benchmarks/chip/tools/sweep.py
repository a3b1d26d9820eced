"""Find a cell's knee: serve its traffic at several rates in one process.

    python3 benchmarks/chip/tools/sweep.py --workload <name> --scales 0.5,1,1.5 --seconds 20 --seed <n>

Each scale multiplies the mix's ``rate_per_s``.  One line per rate: the
offered rate, the tails, how long the queue took to drain after the
window closed, and the admitted requests' queue wait.  The knee is the
highest rate whose queue does not grow through the window: every request
due in it got its first token within the drain allowance, and the median time to
first token of the window's second half is under 1.5 x that of its first
half plus 0.5 s.  The driver's runs never sweep; the mix holds the rate
found here.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, harness, readers  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--scales", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--drain-cap", type=float, default=30.0)
    args = p.parse_args()
    cell = cells.load(args.workload)
    ready = harness.prepare(cell)
    if isinstance(ready, int):
        return ready
    peak, counter = ready
    knee = None
    for scale in (float(x) for x in args.scales.split(",")):
        run = harness.run_cell(cell, args.seed, args.seconds, trace=False, t_start=time.perf_counter(),
                               compile_count=counter, peak=peak, rate_scale=scale, check_outputs=False,
                               drain_cap_s=args.drain_cap)
        w = run.window
        steps = w.steps
        half = [r for r in w.records if r.spec.due_s < w.seconds / 2]
        late = [r for r in w.records if r.spec.due_s >= w.seconds / 2]
        ttft = lambda rs: readers.percentile([((r.token_t[0] if r.token_t else w.measured_s) - r.spec.due_s) * 1e3 for r in rs], 50)  # noqa: E731
        print(json.dumps({
            "workload": cell.name, "scale": scale, "rate_per_s": cell.mix["rate_per_s"] * scale,
            "requests": len(w.records), "drain_s": w.measured_s - w.seconds,
            "ttft_p50_ms": readers.percentile(readers.ttft_ms(run), 50),
            "ttft_p95_ms": readers.percentile(readers.ttft_ms(run), 95),
            "itl_p50_ms": readers.percentile(readers.itl_ms(run), 50),
            "itl_p95_ms": readers.percentile(readers.itl_ms(run), 95),
            "queue_wait_p95_ms": readers.percentile(readers.queue_wait_ms(run), 95),
            "ttft_p50_first_half_ms": ttft(half), "ttft_p50_second_half_ms": ttft(late),
            "no_token": harness.failed(run),
            "step_ms_p50": readers.percentile([(s.t1 - s.t0) * 1e3 for s in steps], 50),
            "tokens_per_s": readers.output_tokens_per_s(run),
            "compiles_in_window": w.compiles,
        }), flush=True)
        first, second = ttft(half), ttft(late)
        held = (harness.failed(run) == 0 and first is not None and second is not None
                and second <= 1.5 * first + 500.0)
        if not held:
            break
        knee = cell.mix["rate_per_s"] * scale
    print(json.dumps({"workload": cell.name, "knee_rate_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Tiny copies of the benchmark's cells, for runs of the whole harness on
the CPU: the same code paths, at widths a test can hold."""

import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells, harness, peaks  # noqa: E402

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128, vocab_size=2048)
TINY_ENGINE = dict(max_batch=4, max_len=256, prefill_chunk=32, prefill_token_budget=64,
                   total_pages=64)


def shrink(cell: cells.Cell) -> cells.Cell:
    """The cell at tiny widths and lengths (the key/value head count keeps
    its kind: one shared head stays one)."""
    cell = copy.deepcopy(cell)
    m = cell.config["model"]
    m.update(TINY_MODEL, n_kv_heads=1 if m["n_kv_heads"] == 1 else 4)
    cell.config["engine"].update(TINY_ENGINE)
    mix = cell.mix
    mix.pop("engine", None)
    for key in ("prompt", "max_new_tokens"):
        if key in mix:
            mix[key].update(median=24, low=4, high=60)
            if mix[key]["dist"] == "uniform":
                mix[key].update(low=8, high=40)
    if "sessions" in mix:
        mix["sessions"]["system_prompts"]["length"].update(low=40, high=80)
        for key in ("user", "assistant"):
            mix["sessions"][key].update(median=10, low=4, high=30)
        mix["sessions"]["think_s"]["mean"] = 0.5
        mix["rate_per_s"] = 4.0
    elif mix["mode"] == "open_loop":
        mix["rate_per_s"] = 12.0
    return cell


def run(workload: str, seed: int = 2**31 + 5, seconds: float = 2.0, **kw) -> harness.Run:
    cell = shrink(cells.load(workload))
    return harness.run_cell(cell, seed, seconds, trace=False, t_start=time.perf_counter(),
                            compile_count=lambda: 0, peak=peaks.peak_for("TPU v5 lite"), **kw)

"""The harness finds a new configuration, traffic mix and metric by their
names in ``BENCHMARK.json`` and their files alone."""

import json
import os
import shutil
import time

import chipbench_tiny as tiny
from chipbench import cells, harness, peaks


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "references"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(tiny.BENCH, "metrics", "setup_s.py"), bench / "metrics")
    shutil.copy(os.path.join(tiny.BENCH, "references", "dense_transformer.py"), bench / "references")
    with open(os.path.join(tiny.BENCH, "configs", "granite-34b-l4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-dense"
    conf["model"].update(tiny.TINY_MODEL, n_kv_heads=2)
    conf["engine"].update(tiny.TINY_ENGINE)
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(conf))
    (bench / "traffic" / "burst_mix.json").write_text(json.dumps({
        "mode": "open_loop", "rate_per_s": 10.0,
        "prompt": {"dist": "uniform", "low": 8, "high": 40},
        "max_new_tokens": {"dist": "constant", "value": 12}}))
    (bench / "metrics" / "served_tokens.py").write_text(
        "def read(run):\n    return sum(len(r.token_t) for r in run.window.records)\n")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 2,
        "configs": [{"name": "tiny-dense", "source": "test", "file": "bench/configs/tiny-dense.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny-dense", "traffic": "burst_mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "served_tokens", "unit": "tokens", "better": "higher", "bound": 0.05,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load("tiny.burst", str(tmp_path))
    assert cell.config["name"] == "tiny-dense" and cell.mix["rate_per_s"] == 10.0
    run = harness.run_cell(cell, 12345, 2.0, trace=False, t_start=time.perf_counter(),
                           compile_count=lambda: 0, peak=peaks.peak_for("TPU v5 lite"))
    assert harness.is_correct(run)
    served = sum(len(r.token_t) for r in run.window.records)
    assert served > 0 and all(len(r.token_t) == 12 for r in run.window.records if r.done)
    assert run.metrics["served_tokens"] == {"value": float(served), "unit": "tokens"}
    assert set(run.metrics) == {"served_tokens", "setup_s"}

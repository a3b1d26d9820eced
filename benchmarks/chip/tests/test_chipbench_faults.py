"""A whole run of the harness on the CPU at tiny widths, past its look for
a chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.  The faults a one-chip serving
cell can have: a dispatch that returns its cache unchanged, half of the
batch left out of the prefill dispatch, and a token altered where the
dispatch produces it.  (No cell spans chips, so no exchange between
chips can be left out.)"""

import json
import os
import subprocess
import sys

import pytest

import chipbench_tiny as tiny
from chipbench import check, harness
from chipbench.faults import cache_unchanged, half_batch, token_altered

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    run = tiny.run(workload)
    assert harness.is_correct(run), run.gap
    served = sum(len(r.req.output) for r in run.window.records if r.done)
    assert run.checks["kv_bf16_exact_share"]["value"] < 0.01
    assert run.gap["tokens"] >= min(harness.MIN_CHECK_TOKENS, served) > 0
    line = harness.result_line(run, trace=False)
    assert list(line)[-1] == "checks" and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in run.cell.end_to_end}


@pytest.mark.parametrize("fault", [cache_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault):
    run = tiny.run(workload, fault=fault)
    assert not harness.is_correct(run), run.gap


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    """The program kept in bfloat16, the control: every key and value in
    its cache is a bfloat16 number."""
    run = tiny.run(workload, dtype="bfloat16")
    assert run.checks["kv_bf16_exact_share"]["value"] == 1.0
    assert not harness.is_correct(run)


def test_sample_covers_every_row():
    """The sample holds a request from every batch row in use: one the row
    finished, or, in a row that finished none, the one it was serving
    when the window closed; so a fault confined to one row is compared."""
    from types import SimpleNamespace as NS

    def rec(i, row, done):
        return NS(done=done, row=row, hit_partial=0, hit_tokens=0, spec=NS(prompt=[1] * (8 + i)),
                  req=NS(output=[2] * (100 + (i * 37) % 50)))

    recs = [rec(i, i % 16, True) for i in range(80) if i % 16 not in (0, 5)]
    recs += [rec(90, 0, False), rec(91, 5, False), rec(92, 3, False), rec(93, -1, False)]
    recs[-1].req.output = []  # queued, never admitted
    for seed in (1, 2**31 + 7):
        picks = check.choose(recs, seed, min_tokens=50)
        assert {r.row for r in picks} == set(range(16))
        assert sorted((r.row, r.done) for r in picks if r.row in (0, 3, 5)) == \
            [(0, False), (3, True), (5, False)]
        assert max(recs[:-4], key=lambda r: len(r.spec.prompt) + len(r.req.output)) in picks


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=tiny.ROOT, timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == "", p.stderr


def test_without_the_program_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files: the
    run stops before any result, even past the look for a chip."""
    import shutil

    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    code = ("import sys, time; sys.path.insert(0, 'benchmarks/chip'); "
            "from chipbench import harness; "
            f"sys.exit(harness.main({CELLS[0]!r}, 1, 1.0, False, time.perf_counter(), "
            "require_chip=False))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, cwd=tmp_path, timeout=300)
    assert p.returncode == 4 and p.stdout.strip() == "", p.stderr

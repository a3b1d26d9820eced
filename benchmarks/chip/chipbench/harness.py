"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``.  ``run.py`` is its command line;
``tools/`` drive it in-process for the knee sweep and the control."""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from chipbench import cells, check, driver, peaks, traffic, weights

DTYPES = ("float32", "bfloat16")
MIN_CHECK_TOKENS = 320
# the traced part: long enough that sparse open-loop traffic (about half a
# request a second) still brings prefill and decode dispatches into it
TRACE_SECONDS = 16.0


class CompileCounter:
    """Counts XLA compilations in this process (JAX's monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1

    def __call__(self) -> int:
        return self.n


@dataclass
class Run:
    cell: cells.Cell
    seed: int
    window: driver.Window
    setup_s: float
    peak: peaks.Peak
    trace: object = None
    metrics: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    gap: dict = field(default_factory=dict)


def build_arch(config: dict):
    """The program's ArchConfig for a configuration file, checked key by key."""
    from repro.configs import get_arch

    model = config["model"]
    arch = dataclasses.replace(get_arch(config["arch"]), **{
        k: v for k, v in model.items() if k not in ("family", "head_dim")})
    for k, v in model.items():
        got = arch.resolved_head_dim if k == "head_dim" else getattr(arch, k)
        if got != v:
            raise ValueError(f"{config['name']}: {k} is {got!r} in the program, {v!r} in the file")
    return arch


def warmup(engine, make_request, vocab: int, chunk: int, seed: int) -> None:
    """Warm-up traffic over exactly the shapes the window uses: a prompt of
    more than one prefill chunk, then two that reuse it from the prefix
    cache (a partial-page copy-on-write stitch, and a full hit whose
    held-back token rewrites a shared page), then decode steps."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    a = rng.integers(0, vocab, size=chunk + chunk // 2 + 5).tolist()
    b = a[: chunk + 7] + rng.integers(0, vocab, size=13).tolist()
    spec = traffic.RequestSpec
    engine.submit([make_request(spec("warm-a", 0.0, a, 4))])
    while not engine.scheduler.slots[0].req or engine.scheduler.slots[0].remaining_prompt:
        engine.step()
    engine.submit([make_request(spec("warm-b", 0.0, b, 4)), make_request(spec("warm-c", 0.0, a, 4))])
    while engine.pending or engine.scheduler.has_active():
        engine.step()
    engine.scheduler.drain_finished()


def run_cell(cell: cells.Cell, seed: int, seconds: float, *, trace: bool, t_start: float,
             compile_count, peak: peaks.Peak, dtype: Optional[str] = None,
             trace_dir: Optional[str] = None,
             rate_scale: float = 1.0, fault=None, check_outputs: bool = True,
             drain_cap_s: float = 120.0, trace_seconds: float = TRACE_SECONDS) -> Run:
    """Set up, run the window and check one cell.  ``dtype`` overrides the
    configuration's type (the control runs the program in bfloat16);
    ``rate_scale`` scales the mix's rate (the knee sweep); ``fault``
    breaks the engine for the fault tests."""
    import jax

    from repro.models import Model, ModelRuntime
    from repro.serving.engine import ServeEngine
    from repro.serving.types import Request

    from chipbench import tracing

    config, mix = cell.config, dict(cell.mix)
    if rate_scale != 1.0:
        mix["rate_per_s"] = mix["rate_per_s"] * rate_scale
    dtype = dtype or config["dtype"]
    if dtype not in DTYPES:
        raise ValueError(f"dtype {dtype!r} not in {DTYPES}")
    jdt = {"float32": jax.numpy.float32, "bfloat16": jax.numpy.bfloat16}[dtype]
    eng_cfg = cell.engine
    model_cfg = config["model"]
    arch = build_arch(config)
    model = Model(arch, ModelRuntime(dtype=jdt))
    spec = weights.spec_of(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    params = weights.make(spec, model_cfg, seed, jdt)
    jax.block_until_ready(params)
    engine = ServeEngine(
        model, params, max_batch=eng_cfg["max_batch"], max_len=eng_cfg["max_len"],
        prefill_chunk=eng_cfg["prefill_chunk"], cache_mode="paged",
        page_size=eng_cfg["page_size"], total_pages=eng_cfg["total_pages"],
        prefill_token_budget=eng_cfg["prefill_token_budget"], rng_seed=0,
    )
    if fault is not None:
        fault(engine)
    recorder = driver.Recorder(engine, model_cfg, dtype)

    def make_request(s):
        return Request(uid=s.uid, prompt=list(s.prompt), max_new_tokens=s.max_new_tokens,
                       temperature=0.0)

    warmup(engine, make_request, model_cfg["vocab_size"], eng_cfg["prefill_chunk"], seed)
    specs = traffic.generate(mix, seed=seed, seconds=seconds, vocab=model_cfg["vocab_size"],
                             max_len=eng_cfg["max_len"])
    tracer = None
    if trace:
        start = 0.3 * seconds
        tracer = tracing.Tracer(trace_dir, start, min(seconds, start + trace_seconds))
    setup_s = time.perf_counter() - t_start
    win = driver.run_window(engine, recorder, specs, mix, seconds=seconds,
                            max_batch=eng_cfg["max_batch"], make_request=make_request,
                            tracer=tracer, compile_count=compile_count, drain_cap_s=drain_cap_s)
    dev = jax.devices()[0]
    run = Run(cell, seed, win, setup_s, peak)
    run.device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
                  "memory_peak_bytes": int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))}
    if trace and win.trace_window is not None:
        run.trace = tracing.read(trace_dir, win.trace_window)
        run.device["busy_s"] = run.trace.busy_s
        run.device["window_s"] = run.trace.window_s
    table = cells.metrics_table(cell, per_layer=trace)
    for name, m in table.items():
        value = cells.reader(name, cell.bench_dir)(run)
        if value is not None:
            run.metrics[name] = {"value": float(value), "unit": m["unit"]}
    # the comparison: host copies of the sample, then free the program's state
    sample = check.served(check.choose(win.records, seed, MIN_CHECK_TOKENS))
    kv_share = check.kv_bf16_exact_share(engine)
    del engine, params, recorder, model
    gc.collect()
    if not check_outputs:
        return run
    ref_mod = cells.reference_module(config["reference"], cell.bench_dir)
    ref_w = weights.make({tuple(k): tuple(v) for k, v in ref_mod.weight_spec(model_cfg).items()},
                         model_cfg, seed, jax.numpy.float32)
    ref = ref_mod.Reference(model_cfg, ref_w, eng_cfg["max_len"])
    run.gap = check.widest_gap(ref, sample) if sample else {"max_logit_gap": float("inf"), "tokens": 0}
    del ref, ref_w
    gc.collect()
    limits = config["checks"]
    run.checks = {
        "max_logit_gap": {"value": run.gap["max_logit_gap"], "limit": limits["max_logit_gap"]["limit"]},
        "kv_bf16_exact_share": {"value": kv_share, "limit": limits["kv_bf16_exact_share"]["limit"]},
    }
    return run


def failed(run: Run) -> int:
    """Requests due in the window that never produced a token."""
    return sum(1 for r in run.window.records if not r.token_t) if run.window.mode != "offline" else 0


def is_correct(run: Run) -> bool:
    return failed(run) == 0 and all(c["value"] <= c["limit"] for c in run.checks.values())


def result_line(run: Run, trace: bool) -> dict:
    out = {
        "correct": is_correct(run),
        "attempted": len(run.window.records),
        "failed": failed(run),
        "metrics": run.metrics,
        "device": run.device,
    }
    if trace and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace.device_ops, "idle_gaps": run.trace.idle_gaps}
    out["checks"] = run.checks
    return out


def report(run: Run, trace: bool, out=sys.stdout, err=sys.stderr) -> None:
    w = run.window
    late = np.asarray(w.lateness_s or [0.0]) * 1e3
    print(f"cell {run.cell.name} seed {run.seed} window_s {w.measured_s} requests {len(w.records)} "
          f"steps {len(w.steps)} dispatches {len(w.dispatches)}", file=out)
    print(f"compiles_in_window {w.compiles}", file=out)
    print(f"generator_lateness_ms p50 {float(np.median(late))} p95 {float(np.percentile(late, 95))} "
          f"max {float(late.max())}", file=out)
    print(f"counters {json.dumps(w.counters)}", file=out)
    print(f"check_sample {json.dumps(run.gap.get('requests', []))}", file=out)
    print(json.dumps(result_line(run, trace)), file=out, flush=True)
    for name, c in run.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=err, flush=True)


def prepare(cell: cells.Cell, require_chip: bool = True):
    """Check the device, put the program on the path, switch the compile
    cache on.  Returns ``(peak, compile counter)``, or an exit code."""
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        print(f"benchmark: cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    try:
        peak = peaks.peak_for(devices[0].device_kind if require_chip else "TPU v5 lite")
    except KeyError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})", file=sys.stderr)
        return 4
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return peak, CompileCounter()


def main(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
         require_chip: bool = True) -> int:
    try:
        cell = cells.load(workload)
    except (KeyError, OSError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    ready = prepare(cell, require_chip)
    if isinstance(ready, int):
        return ready
    peak, counter = ready
    trace_dir = os.path.join(cells.ROOT, ".bench_trace", workload)
    run = run_cell(cell, seed, seconds, trace=trace, t_start=t_start, compile_count=counter,
                   peak=peak, trace_dir=trace_dir)
    report(run, trace)
    return 0

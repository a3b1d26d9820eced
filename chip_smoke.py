"""Smoke run of the serving main path on one TPU chip.

    python chip_smoke.py

It needs a TPU: on any other backend it prints the device it found and
exits non-zero without a result.  It never falls back to the CPU or to
the Pallas interpreter.  Everything runs in this one process, which
holds the chip; the serving workers are threads of it.

Phase 1 - kernel parity at ds-paper-100m widths.  The paged attention
kernel compiled by Mosaic (``ops.paged_attention(..., interpret=False)``)
against the plain-jnp oracle (``ref.paged_attention_reference``, run at
``highest`` matmul precision) at decode (T=1), prefill-chunk (T=16) and
verify (T=spec_k+1) shapes, through a partly filled page table in which
rows share (alias) their first pages and one row is parked.

Phase 2 - the main path at full width (no ``arch_overrides``).  A
``DSRuntime`` + ``ThreadRunner`` fleet runs the ``distributed-serve``
payload, the way ``launch/worker_host.py`` does: queue -> worker ->
``ServeEngine`` -> paged ``KVCacheManager`` with the prefix cache ->
fused prefill / decode / verify dispatches -> the compiled paged kernel.
Two jobs serve the same requests, with speculation off and with the
n-gram proposer.  The run fails unless every request comes back with all
its tokens, no job failed or dead-lettered, each dispatch kind ran, and
the prefix cache served prompt tokens.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The seconds it prints are timings of one cold smoke run, not
measurements.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "ds-paper-100m"
# phase 1: max |kernel - oracle|.  The oracle runs at highest precision;
# the kernel's f32 dots run at Mosaic's default precision, whose bf16
# rounding stays well inside this.  A wrong page, head or mask is O(0.1).
KERNEL_TOL = 2e-2
SPEC_K = 4  # the serve job's default; verify scores spec_k + 1 positions
PREFILL_CHUNK = 16  # the serve job's default chunk


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ phase 1
def _aliased_table(rng, batch, P, n_pages, frontiers, ps, n_shared):
    """Page table backing each row's positions ``[0, frontier)``: rows
    with a frontier past ``n_shared`` pages alias the same first
    ``n_shared`` physical pages (a shared prefix), the rest are private;
    a zero frontier is a parked row (all sentinel)."""
    perm = [int(p) for p in rng.permutation(n_pages)]
    shared = [perm.pop() for _ in range(n_shared)]
    table = np.full((batch, P), n_pages, np.int32)
    for b, frontier in enumerate(frontiers):
        need = -(-int(frontier) // ps)
        for j in range(need):
            share = j < n_shared and need > n_shared
            table[b, j] = shared[j] if share else perm.pop()
    return table


def kernel_parity(*, n_kv_heads, head_dim, n_heads=None, page_size=16,
                  max_len=1024, batch=8, tokens=(1, PREFILL_CHUNK, SPEC_K + 1),
                  interpret=False, seed=0):
    """Max abs error of the paged kernel against its oracle, per T."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.flash_decode import heads_per_row

    n_heads = n_heads or n_kv_heads
    r = heads_per_row(n_kv_heads, head_dim)
    P = max_len // page_size
    n_pages = batch * P // 2
    rng = np.random.default_rng(seed)
    shape = (n_pages, n_kv_heads // r, page_size, r * head_dim)
    k_pages = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    kernel = jax.jit(lambda *a: ops.paged_attention(*a, interpret=interpret))
    oracle = jax.jit(ref.paged_attention_reference)
    errors = {}
    for T in tokens:
        # frontiers up to a quarter of max_len; the last row is parked
        offsets = rng.integers(0, max_len // 4 - T, size=batch).astype(np.int32)
        frontiers = offsets + T
        frontiers[-1] = 0
        table = jnp.asarray(_aliased_table(
            rng, batch, P, n_pages, frontiers, page_size, n_shared=2))
        # scaled queries give peaked softmaxes, so outputs are O(1)
        q = jnp.asarray(
            2.0 * rng.standard_normal((batch, T, n_heads, head_dim)), jnp.float32)
        args = (q, k_pages, v_pages, table, jnp.asarray(offsets))
        out = np.asarray(kernel(*args))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(*args))
        check(out.shape == want.shape, f"kernel shape {out.shape} != {want.shape}")
        check(bool(np.isfinite(out).all()), f"T={T}: kernel output not finite")
        errors[T] = (float(np.max(np.abs(out - want))), float(np.max(np.abs(want))))
    return errors


# ------------------------------------------------------------------ phase 2
def make_prompts(rng, *, n, vocab, lengths, sys_len):
    """``n`` prompts of the given lengths.  Prompts longer than the system
    prefix start with it (shared pages for the prefix cache); every body
    repeats a short phrase, so the n-gram proposer has drafts to verify."""
    system = [int(t) for t in rng.integers(1, vocab, size=sys_len)]
    prompts = []
    for i in range(n):
        length = int(lengths[i])
        head = system if length > sys_len + PREFILL_CHUNK else []
        phrase = [int(t) for t in rng.integers(1, vocab, size=8)]
        body = (phrase * (length // 8 + 1))[: length - len(head)]
        prompts.append(head + body)
    return prompts


def serve_main_path(workdir, prompts, *, arch_overrides=None, max_batch=8,
                    max_len=1024, page_size=16, new_tokens=32):
    """Serve ``prompts`` twice through the DS control plane: one job with
    speculation off, one with the n-gram proposer.  Returns the run
    summary, each job's RESULTS.json, and the worker log events."""
    import repro.launch.serve  # noqa: F401 - registers distributed-serve
    from repro.core import DSConfig, DSRuntime, FleetFile, JobFile, ThreadRunner

    shared = {
        "arch": ARCH,
        "cache_mode": "paged",
        "page_size": page_size,
        "max_batch": max_batch,
        "max_len": max_len,
        "prefill_chunk": PREFILL_CHUNK,
        "max_new_tokens": new_tokens,
        "spec_k": SPEC_K,
        "prompts": prompts,
    }
    if arch_overrides:
        shared["arch_overrides"] = arch_overrides
    groups = [
        {"speculative": "off", "output_prefix": "smoke/off"},
        {"speculative": "ngram", "output_prefix": "smoke/ngram"},
    ]
    cfg = DSConfig(
        app_name="ChipSmoke",
        payload="distributed-serve",
        cluster_machines=1,  # one worker: the jobs take turns on the chip
        machine_type=["sim.large"],
        machine_price=1.0,
        sqs_message_visibility=1800.0,
        max_receive_count=1,  # a failed job dead-letters, never recompiles
        check_if_done=False,
    )
    rt = DSRuntime(cfg, store_root=os.path.join(workdir, "store"))
    rt.setup()
    rt.submit_job(JobFile(shared=shared, groups=groups))
    rt.start_cluster(FleetFile(startup_seconds=0.1))
    summary = ThreadRunner(rt).run()
    results = {}
    for g in groups:
        key = f"{g['output_prefix']}/RESULTS.json"
        results[g["speculative"]] = rt.store.get_json(key) if rt.store.exists(key) else None
    events = [e for s in rt.logs.streams() for e in rt.logs.events(s)]
    return summary, results, events


def check_main_path(summary, results, prompts, new_tokens):
    """Every acceptance check of phase 2; returns (per-job counts, share
    of tokens identical between the two jobs)."""
    check(summary.jobs_failed == 0, f"jobs_failed={summary.jobs_failed}")
    check(summary.dead_letters == 0, f"dead_letters={summary.dead_letters}")
    check(summary.jobs_done == 2, f"jobs_done={summary.jobs_done}, want 2")
    counts = {}
    for mode, res in results.items():
        check(res is not None, f"{mode}: no RESULTS.json")
        reqs = res["requests"]
        for i, p in enumerate(prompts):
            r = reqs.get(f"req{i}")
            check(r is not None, f"{mode}: req{i} has no RESULTS record")
            check(r["prompt"] == p, f"{mode}: req{i} prompt differs")
            check(len(r["completion"]) == new_tokens,
                  f"{mode}: req{i} got {len(r['completion'])}/{new_tokens} tokens")
        counts[mode] = {
            "prefill": res["prefill_dispatches"],
            "decode": res["decode_dispatches"],
            "verify": res["spec_dispatches"],
            "prefix_hit_tokens": res["prefix_hit_tokens"],
            "accepted_per_dispatch": res.get("accepted_per_dispatch"),
        }
        check(res["prefill_dispatches"] > 0, f"{mode}: no prefill dispatch")
        check(res["decode_dispatches"] > 0, f"{mode}: no decode dispatch")
        check(res["prefix_hit_tokens"] > 0, f"{mode}: prefix cache served nothing")
    check(results["ngram"]["spec_dispatches"] > 0, "ngram: no verify dispatch")
    same = total = 0
    for i in range(len(prompts)):
        a = results["off"]["requests"][f"req{i}"]["completion"]
        b = results["ngram"]["requests"][f"req{i}"]["completion"]
        same += sum(x == y for x, y in zip(a, b))
        total += len(a)
    return counts, same / max(total, 1)


# --------------------------------------------------------------------- main
def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: needs a TPU; JAX found none", file=sys.stderr)
        return 2
    try:
        from repro.configs import get_arch
        from repro.kernels import ops
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import ModelRuntime
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {HERE}/src: {e}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    # the serving path must reach the Mosaic kernel, never the interpreter
    check(ModelRuntime().resolve_paged_attn() == "kernel", "paged attention is not the kernel")
    check(ops._default_interpret(None) is False, "kernels would run interpreted")
    cfg = get_arch(ARCH)
    t0 = time.perf_counter()

    errors = kernel_parity(n_kv_heads=cfg.n_kv_heads, n_heads=cfg.n_heads,
                           head_dim=cfg.resolved_head_dim)
    for T, (err, scale) in errors.items():
        print(f"phase 1 T={T}: max_abs_err={err:.3e} (max |oracle|={scale:.3f}, "
              f"tol={KERNEL_TOL:.0e})", flush=True)
    for T, (err, _) in errors.items():
        check(err <= KERNEL_TOL, f"phase 1 T={T}: error {err:.3e} > {KERNEL_TOL:.0e}")

    rng = np.random.default_rng(0)
    new_tokens = 32
    prompts = make_prompts(rng, n=10, vocab=cfg.vocab_size,
                           lengths=np.linspace(64, 512, 10).astype(int), sys_len=128)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        summary, results, events = serve_main_path(workdir, prompts, new_tokens=new_tokens)
    try:
        counts, agree = check_main_path(summary, results, prompts, new_tokens)
    except SmokeFailure:
        for e in events:
            if "traceback" in e:
                print(e["message"], e["traceback"], file=sys.stderr)
        raise
    wall_s = time.perf_counter() - t0
    for mode, c in counts.items():
        print(f"phase 2 speculative={mode}: requests={len(prompts)} "
              f"tokens={len(prompts) * new_tokens} prefill_dispatches={c['prefill']} "
              f"decode_dispatches={c['decode']} verify_dispatches={c['verify']} "
              f"prefix_hit_tokens={c['prefix_hit_tokens']} "
              f"accepted_per_dispatch={c['accepted_per_dispatch']}", flush=True)
    print(f"phase 2 jobs: done={summary.jobs_done} failed={summary.jobs_failed} "
          f"dead_letters={summary.dead_letters}")
    print(f"phase 2 tokens identical between speculative=off and ngram: {agree:.4f} "
          "(reported, not gated)")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"smoke timings (one cold run, not measurements): compile_s={compile_s[0]:.1f} "
          f"wall_s={wall_s:.1f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        raise SystemExit(1)

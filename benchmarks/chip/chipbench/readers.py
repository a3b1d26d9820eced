"""What the metric files under ``metrics/`` compute.  Each ``read(run)``
returns a number, or ``None`` where the run holds nothing to read (the
metric is then left out of the result line); a share of a peak or a
roofline is never returned as 0 for want of data.

``run`` is a :class:`chipbench.harness.Run`: ``run.window`` (host-clock
records of the measured window, :mod:`chipbench.driver`), ``run.trace``
(the reduced device trace of the traced part, or ``None``),
``run.peak``, ``run.setup_s``.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# -------------------------------------------------------------- host clock
def ttft_ms(run):
    """Due time to first token, per request due in the window; a request
    that never produced a token counts with the time until the run ended."""
    w = run.window
    return [((r.token_t[0] if r.token_t else w.measured_s) - r.spec.due_s) * 1e3
            for r in w.records]


def itl_ms(run):
    gaps = [np.diff(r.token_t) for r in run.window.records if len(r.token_t) > 1]
    return np.concatenate(gaps) * 1e3 if gaps else []


def queue_wait_ms(run):
    return [(r.admit_t - r.spec.due_s) * 1e3 for r in run.window.records if r.admit_t is not None]


def output_tokens_per_s(run):
    w = run.window
    return sum(len(r.token_t) for r in w.records) / w.measured_s


def mfu(run):
    """Model operations the window's dispatches required, over the wall
    time of the steps that dispatched work, over the chip's matmul peak."""
    w = run.window
    busy = sum(s.t1 - s.t0 for s in w.steps if s.dispatches)
    work = sum(d.work.model_flops for d in w.dispatches)
    if busy <= 0 or work <= 0:
        return None
    return 100.0 * work / (busy * run.peak.matmul_flops_per_s)


# ------------------------------------------------------------------ counters
def prefix_hit_share(run):
    w = run.window
    submitted = sum(len(r.spec.prompt) for r in w.records)
    hits = w.counters["prefix_hit_tokens"] + w.counters["prefix_hit_tokens_partial"]
    return 100.0 * hits / submitted if submitted else None


def batch_occupancy(run):
    rows = [d.work.tokens for d in run.window.dispatches if d.kind == "decode"]
    return 100.0 * float(np.mean(rows)) / run.window.max_batch if rows else None


# ------------------------------------------------------------- device trace
def dispatch_ms(run, kind: str):
    t = run.trace
    if t is None or not t.dispatch_s.get(kind):
        return None
    return 1e3 * float(np.mean(t.dispatch_s[kind]))


def paged_attn_roofline(run):
    """Least time of the paged attention kernel's calls in the traced part,
    from their logical shapes and the chip's peaks, over the kernel's
    device time in the trace."""
    t = run.trace
    if t is None or t.kernel_s <= 0:
        return None
    lo, hi = run.window.trace_window
    least = sum(d.work.attn_least_s(run.peak.matmul_flops_per_s, run.peak.hbm_bytes_per_s)
                for d in run.window.dispatches if lo <= d.t <= hi)
    return 100.0 * least / t.kernel_s if least > 0 else None


def idle_share(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

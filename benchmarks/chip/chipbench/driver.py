"""Drives a ``ServeEngine`` through ``submit()`` / ``step()`` for one
measured window, and records what the host clock sees.

Open loop: each request is submitted once its due time has passed,
whatever the server is doing, and its time to first token runs from
that due time.  After the last arrival the window runs on until every
request due in it has its first token; the gaps between tokens are those
emitted until then.  Offline: a backlog of queued requests is kept topped up.
``step()`` returns once the tick's token ids have been read back to the
host, so a token's time is the host clock right after the step that
produced it.  A step's admissions happen at its start, so a request's
admission time is the start of the step whose tick admitted it.

The engine's two jitted dispatches are wrapped, from outside, to record
each call's logical work (a ``flops.DispatchWork``, counted by the model
family's ``extend_work`` and ``decode_work``) and to put a named span
around it in the profiler's trace; the program itself is not changed.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax

from chipbench import flops
from chipbench.traffic import RequestSpec

clock = time.perf_counter
COUNTERS = ("tokens_emitted", "prompt_tokens_ingested", "prefix_hit_tokens",
            "prefix_hit_tokens_partial", "prompt_tokens_skipped", "decode_dispatches",
            "prefill_dispatches", "preemptions", "cow_copies", "cow_partial_stitches",
            "prefix_evictions")


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


@dataclass
class ReqRecord:
    spec: RequestSpec
    req: object = None  # the program's Request
    admit_tick: int = -1
    admit_t: Optional[float] = None
    hit_tokens: int = 0
    hit_partial: int = 0
    row: int = -1  # the batch row it was (last) admitted into
    token_t: List[float] = field(default_factory=list)
    done: bool = False


@dataclass
class StepRecord:
    t0: float
    t1: float
    dispatches: int  # dispatches the step made


@dataclass
class DispatchRecord:
    t: float
    kind: str  # prefill | decode
    work: flops.DispatchWork


@dataclass
class Window:
    mode: str
    seconds: float  # the window's planned length
    measured_s: float = 0.0  # open loop: from opening to the last completion; offline: the window
    records: List[ReqRecord] = field(default_factory=list)
    steps: List[StepRecord] = field(default_factory=list)
    dispatches: List[DispatchRecord] = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    lateness_s: List[float] = field(default_factory=list)
    compiles: int = 0
    trace_window: Optional[tuple] = None  # host-clock (start, stop) of the traced part
    max_batch: int = 0


class Recorder:
    """Wraps the engine's dispatches and watches its slots after each step.
    ``extend_work(model, dtype, offsets, lengths, completes)`` and
    ``decode_work(model, dtype, positions)`` count a dispatch's work: those
    of the reference file the configuration names."""

    def __init__(self, engine, model: dict, dtype: str, extend_work: Callable,
                 decode_work: Callable):
        self.engine = engine
        self.t0 = clock()
        self.dispatches: List[DispatchRecord] = []
        slots = engine.scheduler.slots
        prefill, decode = engine._prefill, engine._decode

        def traced_prefill(params, cache, tokens, offsets, lengths, *rest):
            done = [int(lengths[i]) > 0 and int(lengths[i]) == len(slots[i].remaining_prompt)
                    for i in range(len(slots))]
            work = extend_work(model, dtype, offsets, lengths, done)
            self.dispatches.append(DispatchRecord(clock() - self.t0, "prefill", work))
            with span("bench.dispatch.prefill"):
                return prefill(params, cache, tokens, offsets, lengths, *rest)

        def traced_decode(params, cache, tokens, pos, *rest):
            rows = [i for i, s in enumerate(slots) if s.req is not None and not s.remaining_prompt]
            work = decode_work(model, dtype, [int(pos[i]) for i in rows])
            self.dispatches.append(DispatchRecord(clock() - self.t0, "decode", work))
            with span("bench.dispatch.decode"):
                return decode(params, cache, tokens, pos, *rest)

        engine._prefill, engine._decode = traced_prefill, traced_decode

    def counters(self) -> dict:
        return {k: getattr(self.engine.stats, k) for k in COUNTERS}

    def observe(self, live: dict, step: StepRecord) -> None:
        """Admissions and new tokens after one step; retire finished requests."""
        for row, slot in enumerate(self.engine.scheduler.slots):
            req = slot.req
            if req is None:
                continue
            rec = live[req.uid]
            if rec.admit_tick != req.admit_tick:  # admitted (again, after a preemption)
                rec.admit_tick, rec.admit_t, rec.row = req.admit_tick, step.t0, row
                rec.hit_tokens, rec.hit_partial = slot.hit_tokens, slot.hit_tokens_partial
            while len(rec.token_t) < len(req.output):
                rec.token_t.append(step.t1)
        for req in self.engine.scheduler.drain_finished():
            rec = live.pop(req.uid)
            if rec.admit_t is None:
                rec.admit_tick, rec.admit_t = req.admit_tick, step.t0
            while len(rec.token_t) < len(req.output):
                rec.token_t.append(step.t1)
            rec.done = True


def run_window(engine, recorder: Recorder, specs: List[RequestSpec], mix: dict, *,
               seconds: float, max_batch: int, make_request: Callable,
               tracer=None, drain_cap_s: float = 120.0, compile_count: Callable = lambda: 0) -> Window:
    """Run one measured window.  ``tracer`` (optional) has ``start()`` and
    ``stop()`` and a planned ``(start_s, stop_s)`` inside the window."""
    mode = mix["mode"]
    win = Window(mode=mode, seconds=seconds, max_batch=max_batch)
    recs = [ReqRecord(s) for s in specs]
    live: dict = {}
    c0, compiles0 = recorder.counters(), compile_count()
    n_disp0 = len(recorder.dispatches)
    backlog = int(mix.get("backlog", 0) * max_batch)
    gc.collect()
    gc.disable()
    try:
        recorder.t0 = t_open = clock()
        i, tracing = 0, False
        while True:
            now = clock() - t_open
            if tracer is not None:
                if not tracing and win.trace_window is None and now >= tracer.start_s:
                    tracer.start()
                    tracing, tr_start = True, clock() - t_open
                elif tracing and now >= tracer.stop_s:
                    win.trace_window = (tr_start, clock() - t_open)
                    tracer.stop()
                    tracing = False
            due = []
            if mode == "offline":
                if now >= seconds:
                    break
                while i < len(recs) and len(engine.pending) + len(due) < backlog:
                    due.append(recs[i])
                    i += 1
            else:
                while i < len(recs) and recs[i].spec.due_s <= now:
                    win.lateness_s.append(now - recs[i].spec.due_s)
                    due.append(recs[i])
                    i += 1
            if due:
                for rec in due:
                    rec.req = make_request(rec.spec)
                    live[rec.spec.uid] = rec
                with span("bench.submit"):
                    engine.submit([rec.req for rec in due])
            if engine.pending or engine.scheduler.has_active():
                t0, n_disp = clock() - t_open, len(recorder.dispatches)
                with span("bench.step"):
                    engine.step()
                t1 = clock() - t_open
                step = StepRecord(t0, t1, len(recorder.dispatches) - n_disp)
                win.steps.append(step)
                recorder.observe(live, step)
                if i >= len(recs) and mode != "offline" and (
                        all(r.token_t for r in recs) or now > seconds + drain_cap_s):
                    break  # every request due has its first token (or never will)
            elif i >= len(recs):
                if mode == "offline":
                    raise RuntimeError("the offline pool ran dry inside the window; raise the mix's pool")
                break
            elif mode != "offline":
                with span("bench.wait"):
                    time.sleep(max(0.0, min(recs[i].spec.due_s - (clock() - t_open), 0.05)))
        if tracing:
            win.trace_window = (tr_start, clock() - t_open)
            tracer.stop()
        end = clock() - t_open
    finally:
        gc.enable()
    win.measured_s = end if mode == "offline" else max(seconds, end)
    win.compiles = compile_count() - compiles0
    c1 = recorder.counters()
    win.counters = {k: c1[k] - c0[k] for k in c0}
    win.records = recs[:i]
    win.dispatches = recorder.dispatches[n_disp0:]
    return win

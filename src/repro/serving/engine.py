"""Serving executor: the device-dispatch layer of the three-layer
serving tier.

The serving engine is split along explicit seams (``docs/serving.md``
has the architecture diagram):

- :class:`repro.serving.scheduler.RequestScheduler` — admission queue,
  continuous batching (freed rows refilled every tick, ``drain``
  baseline policy, per-tick prefill token budget), preemption/requeue
  policy, queue-wait/TTFT accounting;
- :class:`repro.serving.cache_manager.KVCacheManager` — dense or paged
  device cache, refcounted free-list allocator, copy-on-write, adaptive
  pool sizing, radix prefix cache, optional cross-host prefix store;
- :class:`ServeEngine` (this module) — owns ONLY the jitted dispatches
  and sampling: it asks the scheduler what to run, asks the cache
  manager to back the positions it will write, dispatches, and feeds
  accepted tokens back to the scheduler.

Hot-path structure (this is the whole point — throughput limited by the
hardware, not by dispatch count):

- **decode**: ONE jitted dispatch per tick for any mix of slot positions.
  ``Model.decode_step`` takes a per-row position vector ``[B]``, so rows
  at different depths advance together; the seed engine's one-dispatch-
  per-distinct-position loop (up to B sequential device calls per token)
  is retained only as ``dispatch_mode="grouped"`` for benchmarking.
- **prefill**: prompts are ingested through ``Model.prefill_chunk`` in
  ``prefill_chunk``-token slices — the KV/SSM cache for a whole chunk is
  written in one dispatch instead of token-at-a-time through the decode
  path.  Architectures without fused-prefill support (enc-dec, VLM, MoE
  capacity routing, rolling sliding-window caches) fall back to decode-
  path ingestion, still at one dispatch per tick.  Over a paged cache a
  prefill dispatch runs only ``prefill_rows`` rows (``PREFILL_SLOTS``):
  its program gathers the rows that prefill from the slot-indexed inputs
  and scatters their outputs back, so the host interface keeps its
  ``(max_batch, ...)`` arrays.
- **sampling**: greedy/temperature sampling runs on-device inside the
  same dispatch (``repro.serving.sampling``); only ``B`` token ids cross
  the host boundary per tick instead of ``(B, vocab)`` logits.
  ``sample_on_device=False`` restores the host path (numerically
  stable: max-subtracted softmax).
- **stop tokens**: the fused dispatches return a done mask computed on
  device (``repro.serving.sampling.done_mask``); the host finalizes rows
  straight off the mask, and finished rows are parked (pages freed)
  before the next tick's dispatch.

Cache behaviour (paged pool, copy-on-write, shared prefixes, adaptive
sizing) is documented on :class:`KVCacheManager`; scheduling behaviour
(continuous batching, budgets, preemption) on :class:`RequestScheduler`.

Dispatch accounting: ``decode_dispatches`` / ``prefill_dispatches`` /
``dispatches`` (their sum) and ``tokens_emitted`` /
``prompt_tokens_ingested`` feed ``benchmarks/bench_serving.py``'s
dispatches-per-token metric.  ``steps_executed`` keeps its seed meaning
(number of jitted decode calls).  All counters live in one shared
:class:`repro.serving.types.EngineStats` block (``engine.stats``); the
flat attribute aliases below (``engine.tokens_emitted`` and friends)
are kept as the stable public surface.

Tracing: each activity of a tick (admission, page reservation, input
building, the page-table upload, each dispatch, its read-back, per-row
accept) runs inside a ``serve.*`` ``jax.profiler.TraceAnnotation``, so
a profiler trace puts each stretch of device idle time down to the host
activity beside it (``docs/serving.md``, "Tracing").
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import Model
from repro.serving.cache_manager import KVCacheManager
from repro.serving.prefix_store import PrefixStore
from repro.serving.sampling import (
    make_decode_step,
    make_prefill_step,
    make_verify_step,
)
from repro.serving.scheduler import RequestScheduler
from repro.serving.speculate import DraftProposer, NgramProposer
from repro.serving.types import EngineStats, Request, Slot

__all__ = ["Request", "ServeEngine", "Slot"]

# back-compat alias: _Slot predates the layer split
_Slot = Slot


# A fused prefill dispatch on a paged cache runs at most this many prompt
# slots (rows x prefill_chunk).  A dispatch pays a fixed cost (the weight
# read) plus matmul time per slot.  Chosen from a sweep on a TPU v5e at
# granite-34b widths and 4 layers, made while the prefill program still
# converted its f32 weight stacks to bf16 on every dispatch: 22.8-23.9 ms
# at 64-256 slots, 29.1 ms at 512, 40.5 ms at 1,024 and 110.7 ms at 4,096,
# so up to 512 slots stayed within ~1.3x of that floor, and a tick whose
# 2048-token prefill budget is full of prompt tokens cost four (8 x 64)
# dispatches, about what one (64 x 64) dispatch did.  The conversions are
# gone (``Model._maybe_scan``); the lower floor has not been swept again.
PREFILL_SLOTS = 512


def prefill_rows(prefill_chunk: int, max_batch: int) -> int:
    """Rows of a fused prefill dispatch over a paged cache: the largest
    power of two whose ``prefill_chunk``-token rows fit ``PREFILL_SLOTS``,
    clamped to ``[1, max_batch]``."""
    fit = max(1, PREFILL_SLOTS // max(1, prefill_chunk))
    return min(1 << (fit.bit_length() - 1), max_batch)


def _jit_cached(model: Model, key: tuple, builder: Callable) -> Callable:
    """Memoize a jitted dispatch on the *model* instance.

    The jit targets close over (model, seed, sample_on_device, and the
    prefill's row count) only —
    params are call arguments — so every engine built on the same model
    with the same key can share one compiled function.  Elastic serving
    rebuilds engines after every lease takeover/revocation; without this
    each rebuild would retrace and recompile the same program."""
    memo = model.__dict__.setdefault("_jit_memo", {})
    if key not in memo:
        memo[key] = jax.jit(builder())
    return memo[key]


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params,
        *,
        max_batch: int = 4,
        max_len: int = 256,
        rng_seed: int = 0,
        heartbeat: Callable[[], None] = lambda: None,
        prefill_chunk: int = 16,
        dispatch_mode: str = "fused",
        sample_on_device: bool = True,
        cache_mode: str = "dense",
        page_size: int = 16,
        total_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_match: str = "token",
        prefix_store: Optional[PrefixStore] = None,
        refill_policy: str = "continuous",
        prefill_token_budget: Optional[int] = None,
        worker_role: str = "unified",
        speculative: str = "off",
        spec_k: int = 4,
        draft_model: Optional[Model] = None,
        draft_params=None,
    ):
        if dispatch_mode not in ("fused", "grouped"):
            raise ValueError(f"dispatch_mode must be fused|grouped, got {dispatch_mode!r}")
        if cache_mode not in ("dense", "paged"):
            raise ValueError(f"cache_mode must be dense|paged, got {cache_mode!r}")
        if cache_mode == "paged" and not model.supports_paged_cache:
            raise ValueError(
                "cache_mode='paged' unsupported for arch "
                f"{model.cfg.name!r} (no pageable KV cache)"
            )
        if prefix_store is not None and (cache_mode != "paged" or not prefix_cache):
            # same refuse-inert-knob policy as prefill_token_budget below:
            # the store publishes/hydrates through the radix cache over
            # paged pool pages, so without both it can never move a byte
            raise ValueError(
                "prefix_store requires cache_mode='paged' with "
                "prefix_cache=True; it would be silently inert here"
            )
        if worker_role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"worker_role must be unified|prefill|decode, got {worker_role!r}"
            )
        if worker_role != "unified" and prefix_store is None:
            # the handoff travels THROUGH the prefix store: a prefill
            # worker publishes the prompt's chained pages there and a
            # decode worker demand-hydrates them back.  Without a store
            # the roles would be silently inert (prefill work unreachable)
            raise ValueError(
                "worker_role='prefill'/'decode' requires a prefix_store "
                "(the KV handoff is storage-mediated); it would be "
                "silently inert here"
            )
        if dispatch_mode == "grouped" and model.cfg.family in ("ssm", "hybrid"):
            # per-group re-dispatch re-advances recurrent state every extra
            # call per tick (KV writes are idempotent, recurrences are not):
            # grouped output would be silently wrong, so refuse up front
            raise ValueError(
                "dispatch_mode='grouped' corrupts recurrent SSM/hybrid state; "
                "use the fused engine for family "
                f"{model.cfg.family!r}"
            )
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.heartbeat = heartbeat
        self.prefill_chunk = int(prefill_chunk)
        self.dispatch_mode = dispatch_mode
        self.sample_on_device = sample_on_device
        self.cache_mode = cache_mode

        # the three layers share one counter block and are cross-wired at
        # exactly two points: admission (scheduler -> cache: reset +
        # stitch) and pool-pressure preemption (cache -> scheduler)
        self.stats = EngineStats()
        self.cache_mgr = KVCacheManager(
            model,
            max_batch=max_batch,
            max_len=max_len,
            stats=self.stats,
            cache_mode=cache_mode,
            page_size=page_size,
            total_pages=total_pages,
            prefix_cache=prefix_cache,
            prefix_match=prefix_match,
            prefix_store=prefix_store,
        )
        self.worker_role = worker_role
        self.scheduler = RequestScheduler(
            max_batch,
            self.stats,
            refill_policy=refill_policy,
            prefill_token_budget=prefill_token_budget,
            role=worker_role,
        )
        self.scheduler.cache = self.cache_mgr
        self.cache_mgr.preempt_for = self.scheduler.preempt_for
        # the yield seam: the allocator requeues the youngest (requesting)
        # row only after its allocation loop unwound; skip when the slot
        # was already emptied by a direct preemption
        self.cache_mgr.preempt_row = (
            lambda row: self.scheduler.preempt(row)
            if self.scheduler.slots[row].req is not None else None
        )

        self.rng = np.random.default_rng(rng_seed)
        self._rng_seed = rng_seed
        self._decode = _jit_cached(
            model, ("decode", rng_seed, sample_on_device),
            lambda: make_decode_step(model, rng_seed, sample_on_device),
        )
        self._use_prefill = (
            dispatch_mode == "fused"
            and self.prefill_chunk > 0
            and model.supports_fused_prefill
            and not self.cache_mgr.cache_is_rolling()
        )
        # rows the prefill program runs: over a paged cache only the rows
        # that prefill are gathered into a smaller (R, C) program; a dense
        # cache's rows are its cache rows, and SSM state is per row, so
        # there every dispatch runs all max_batch rows
        self.prefill_rows = (
            prefill_rows(self.prefill_chunk, max_batch)
            if cache_mode == "paged" and self._use_prefill else max_batch
        )
        self._prefill = (
            _jit_cached(
                model, ("prefill", rng_seed, sample_on_device, self.prefill_rows),
                lambda: make_prefill_step(model, rng_seed, sample_on_device,
                                          self.prefill_rows),
            )
            if self._use_prefill
            else None
        )
        if worker_role == "prefill" and not self._use_prefill:
            # a prefill-role worker never runs a decode tick, so prompts
            # MUST ingest through the chunked-prefill path; without it
            # every admitted request would sit in its slot forever
            raise ValueError(
                "worker_role='prefill' requires the fused chunked-prefill "
                "path (dispatch_mode='fused', prefill_chunk > 0, a "
                "prefill-capable non-rolling arch)"
            )
        if worker_role == "prefill" and speculative != "off":
            raise ValueError(
                "speculative decoding never runs on a prefill-role worker "
                "(it has no decode ticks); it would be silently inert here"
            )
        self.speculative = speculative
        self.spec_k = int(spec_k)
        self.proposer = None
        self._verify = None
        if speculative not in ("off", "ngram", "draft"):
            raise ValueError(
                f"speculative must be off|ngram|draft, got {speculative!r}"
            )
        if speculative == "off" and (draft_model is not None or draft_params is not None):
            raise ValueError(
                "draft_model/draft_params require speculative='draft'; they "
                "would be silently inert here"
            )
        if speculative != "off":
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if not sample_on_device:
                raise ValueError(
                    "speculative decoding verifies and accepts on device; "
                    "sample_on_device=False would make the verify dispatch "
                    "round-trip (B, k+1, vocab) logits — unsupported"
                )
            if model.cfg.family in ("ssm", "hybrid"):
                # KV rollback is dropping pages past the frontier; a
                # recurrent state advanced by rejected tokens cannot be
                # rewound without checkpointing every position
                raise ValueError(
                    "speculative decoding cannot roll back recurrent "
                    f"(family {model.cfg.family!r}) state; rejected draft "
                    "tokens would corrupt the recurrence"
                )
            if not self._use_prefill:
                raise ValueError(
                    "speculative decoding verifies k+1 positions through the "
                    "fused chunk-extend path (dispatch_mode='fused', "
                    "prefill_chunk > 0, fused-prefill-capable arch, "
                    "non-rolling cache); it cannot run here"
                )
            self._verify = _jit_cached(
                model, ("verify", rng_seed),
                lambda: make_verify_step(model, rng_seed),
            )
            if speculative == "ngram":
                self.proposer = NgramProposer()
            else:
                if draft_model is None or draft_params is None:
                    raise ValueError(
                        "speculative='draft' needs draft_model and draft_params"
                    )
                self.proposer = DraftProposer(
                    draft_model, draft_params,
                    max_batch=max_batch, max_len=max_len, spec_k=self.spec_k,
                    page_size=page_size, stats=self.stats,
                )
        if prefill_token_budget is not None:
            # a finite budget holds rows mid-prefill across decode ticks.
            # For recurrent state that is corruption, not a schedule: the
            # batch-wide decode dispatch advances EVERY row's recurrence,
            # including the held row's, with its garbage token (KV writes
            # are idempotent, recurrences are not).  And without the
            # fused prefill path the knob would be silently inert —
            # refuse both up front rather than mislead.
            if model.cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "prefill_token_budget is unsupported for recurrent "
                    f"(family {model.cfg.family!r}) models: a mid-prefill "
                    "row's recurrence would be advanced by the decode "
                    "dispatch it sits out"
                )
            if not self._use_prefill:
                raise ValueError(
                    "prefill_token_budget requires the fused prefill path "
                    "(dispatch_mode='fused', prefill_chunk > 0, fused-"
                    "prefill-capable arch); it would be silently inert here"
                )

    # ---------------------------------------------- layer-delegation surface
    # The flat attribute API predates the layer split and is the stable
    # public surface (tests, benchmarks, payloads); everything below is
    # a view onto the layers, writable where the benchmark re-baselines.
    @property
    def cache(self):
        return self.cache_mgr.cache

    @cache.setter
    def cache(self, value):
        self.cache_mgr.cache = value

    @property
    def prefix(self):
        return self.cache_mgr.prefix

    @property
    def pending(self) -> List[Request]:
        return self.scheduler.pending

    @property
    def finished(self) -> List[Request]:
        return self.scheduler.finished

    @property
    def slots(self) -> List[Slot]:
        return self.scheduler.slots

    @property
    def peak_cache_bytes(self) -> int:
        return self.cache_mgr.peak_cache_bytes

    def snapshot(self) -> Dict:
        """Full counter + timing snapshot (what the ``distributed-serve``
        payload publishes next to the completions)."""
        snap = self.stats.snapshot()
        snap["peak_cache_bytes"] = self.peak_cache_bytes
        snap["timing"] = self.scheduler.timing()
        if self.cache_mode == "paged":
            snap["total_pages"] = self.cache_mgr.n_pages
            snap["page_size"] = self.cache_mgr.page_size
        return snap

    # ------------------------------------------------------------- intake
    def submit(self, reqs: List[Request]) -> None:
        with TraceAnnotation("serve.submit", n=len(reqs)):
            self.scheduler.submit(reqs)
            # adaptive pool sizing sees the queue depth at submit (the
            # caller no longer guesses total_pages)
            self.cache_mgr.on_submit(self.scheduler.pending)

    # ------------------------------------- work-preserving recovery seam
    def checkpoint_slot(self, row: int) -> Optional[Dict]:
        """Capture a resumable generation checkpoint for an active slot.

        Called by a draining lease BEFORE it preempts the row, while the
        slot's KV pages are still resident.  Publishes the pages covering
        ``prompt + output[:-1]`` (everything the cache actually holds —
        the last emitted token's KV has not been written yet) through the
        prefix store, including the sub-page tail under an extended
        content key, and returns a plain-dict record the caller persists
        durably.  Returns ``None`` when there is nothing worth saving
        (empty row, prompt still ingesting, or no tokens emitted yet —
        full replay costs the same as a resume there)."""
        slot = self.scheduler.slots[row]
        req = slot.req
        if (req is None or slot.remaining_prompt
                or len(req.output) <= req.resume_base):
            return None
        # a request that is itself a resume carries resume_base pre-seeded
        # output tokens duplicated in its extended prompt — the record
        # always stores the ORIGINAL prompt and the FULL output, so
        # chained resumes never double-extend
        base = len(req.prompt) - req.resume_base
        resident = list(req.prompt[:base]) + req.output[:-1]
        with TraceAnnotation("serve.prefix_insert"):
            self.cache_mgr.publish_generation(row, resident)
        self.stats.checkpoints_published += 1
        return {
            "uid": req.uid,
            "prompt": list(req.prompt[:base]),
            "output": list(req.output),
            "sample_stream": int(req.sample_stream),
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "stop_token": req.stop_token,
        }

    def submit_resume(self, ckpt: Dict) -> Request:
        """Admit a checkpointed generation for byte-identical continuation.

        The resumed request re-enters through the NORMAL admission path
        with an *extended prompt* of ``prompt + output[:-1]`` and its
        output pre-seeded to ``output[:-1]``: the prefix stitch gets a
        guaranteed full-chunk hit over tokens the dying worker published
        (sub-page tail included), and the prefill-completion sample at
        the frontier re-derives ``output[-1]`` from the same stream key
        ``(stream, len(output)-1)`` the original emission used — so the
        final output is token-for-token identical to an uninterrupted
        run, and only the frontier token is ever re-decoded.  A partial
        (or zero) store hit degrades gracefully: the un-hit extended-
        prompt tokens are chunk-prefilled, writing the same KV bytes."""
        output = [int(t) for t in ckpt["output"]]
        req = Request(
            uid=ckpt["uid"],
            prompt=[int(t) for t in ckpt["prompt"]] + output[:-1],
            max_new_tokens=int(ckpt["max_new_tokens"]),
            temperature=float(ckpt["temperature"]),
            stop_token=ckpt.get("stop_token"),
        )
        req.output = output[:-1]
        req.resume_base = len(output) - 1
        req.sample_stream = int(ckpt["sample_stream"])
        self.scheduler.submit_resume(req)
        self.cache_mgr.on_submit(self.scheduler.pending)
        self.stats.checkpoint_resumes += 1
        self.stats.tokens_recovered += len(output) - 1
        return req

    # -------------------------------------- disaggregated prefill/decode
    def submit_handoff(self, rec: Dict) -> Request:
        """Admit a prefill worker's sealed handoff record on a decode
        worker.

        A handoff record is the checkpoint format with ``output == []``:
        the prompt was ingested and its full KV chain (full pages plus
        the sub-page tail under an extended content key) published by a
        prefill-role worker, but nothing has been decoded yet — so it
        cannot go through :meth:`submit_resume` (there is no emitted
        frontier token to re-derive).  Admission flags the request as a
        handoff, which routes the prefix stitch through the
        demand-driven hydration path: the cache manager fetches exactly
        the chained pages (pinned against eviction while in flight)
        instead of stopping at free-pool pressure, and counts a fallback
        if the store cannot cover the prompt (the slot then replays
        through the normal chunk-prefill ladder, byte-identically).  The
        first sample draws from the record's preserved stream at step 0,
        so output matches a monolithic worker token-for-token."""
        req = Request(
            uid=rec["uid"],
            prompt=[int(t) for t in rec["prompt"]],
            max_new_tokens=int(rec["max_new_tokens"]),
            temperature=float(rec["temperature"]),
            stop_token=rec.get("stop_token"),
        )
        req.sample_stream = int(rec["sample_stream"])
        req.handoff = True
        self.scheduler.submit_handoff(req)
        self.cache_mgr.on_submit(self.scheduler.pending)
        self.stats.handoffs_admitted += 1
        return req

    # ------------------------------------------------------------- stepping
    def step(self) -> int:
        """One engine tick.

        The scheduler admits queued requests into freed rows (continuous
        batching), then: fused mode ingests pending prompt chunks
        (>= chunk-size tokens per prefill dispatch, bounded by the
        scheduler's per-tick prefill token budget) and advances every
        decode-ready slot one token in a SINGLE decode dispatch
        regardless of position mix.  Grouped mode reproduces the seed's
        per-position-group dispatching (with its cross-row KV corruption
        fixed) for comparison.  NOTE: grouped dispatching is inherently
        wrong for recurrent (SSM / hybrid) state — every extra per-tick
        dispatch re-advances all rows' recurrences (KV writes are
        idempotent, recurrences are not).  That unfixable property is
        part of why the fused path exists; use grouped mode only on
        attention-family models.
        """
        with TraceAnnotation("serve.step", tick=self.scheduler.tick + 1):
            self.scheduler.begin_tick()
            if not self.scheduler.has_active():
                return 0
            emitted = 0
            if self._use_prefill:
                emitted += self._ingest_prompts()
            if self.worker_role == "prefill":
                # prefill-role tick: ingest only.  Each prompt finishes at
                # ingest completion (published + handed off, zero tokens
                # sampled) so a decode dispatch here could only be a no-op
                if os.environ.get("DS_DEBUG_INVARIANTS") == "1":
                    self.cache_mgr.check_invariants()
                return emitted
            if self.dispatch_mode == "grouped":
                emitted += self._decode_tick_grouped()
            elif self.speculative != "off":
                emitted += self._decode_tick_spec()
            else:
                emitted += self._decode_tick_fused()
            if os.environ.get("DS_DEBUG_INVARIANTS") == "1":
                self.cache_mgr.check_invariants()
            return emitted

    # -- prompt ingestion (fused chunked prefill) ---------------------------
    def _ingest_prompts(self) -> int:
        emitted = 0
        B, C = self.max_batch, self.prefill_chunk
        slots = self.scheduler.slots
        left = self.scheduler.prefill_token_budget  # None = unbounded
        while True:
            with TraceAnnotation("serve.inputs"):
                plan, left = self._plan_prefill(left)
            if not plan:
                return emitted
            if self.cache_mode == "paged":
                # reservation pass BEFORE building dispatch inputs: CoW /
                # eviction / preemption all mutate slot state, and a later
                # row's allocation may preempt (or yield) an earlier one —
                # the rows list below is computed only after every
                # survivor holds pages.  A dropped row's slot.req is
                # None: yielded and preempted rows alike are requeued at
                # the clean seam and rerun byte-identically
                with TraceAnnotation("serve.pages"):
                    for i, n in plan.items():
                        s = slots[i]
                        if s.req is not None and s.remaining_prompt:
                            self.cache_mgr.ensure_pages(i, s.pos + n,
                                                        write_start=s.pos)
            rows = [
                i for i in plan
                if slots[i].req is not None and slots[i].remaining_prompt
            ]
            if left is not None:
                # refund tokens planned for rows the reservation pass
                # dropped (preempted/yielded): the tick budget promises
                # tokens INGESTED, not tokens planned
                left += sum(plan[i] for i in plan if i not in rows)
            if not rows:
                return emitted
            with TraceAnnotation("serve.inputs"):
                tokens = np.zeros((B, C), np.int32)
                offsets = np.zeros((B,), np.int32)
                lengths = np.zeros((B,), np.int32)
                temps = np.zeros((B,), np.float32)
                streams = np.zeros((B,), np.int32)
                steps = np.zeros((B,), np.int32)
                stops = np.full((B,), -1, np.int32)
                max_news = np.full((B,), 1 << 30, np.int32)
                n_tokens = 0
                for i in rows:
                    slot = slots[i]
                    n = min(plan[i], len(slot.remaining_prompt))
                    tokens[i, :n] = slot.remaining_prompt[:n]
                    offsets[i] = slot.pos
                    lengths[i] = n
                    n_tokens += n
                    temps[i] = slot.req.temperature
                    streams[i] = slot.req.sample_stream
                    # a checkpoint resume admits with pre-seeded output, so
                    # the prefill-completion sample/done mask must start at
                    # the emission count, not 0 (no-op for fresh requests)
                    steps[i] = len(slot.req.output)
                    if slot.req.stop_token is not None:
                        stops[i] = slot.req.stop_token
                    max_news[i] = slot.req.max_new_tokens
            self.cache_mgr.push_table()
            slots_run = self.prefill_rows * C
            with TraceAnnotation("serve.dispatch.prefill", rows=len(rows), tokens=n_tokens,
                                 slots=slots_run):
                if self.sample_on_device:
                    nxt, done, self.cache_mgr.cache = self._prefill(
                        self.params, self.cache_mgr.cache, tokens, offsets, lengths,
                        temps, streams, steps, stops, max_news,
                    )
                else:
                    logits, self.cache_mgr.cache = self._prefill(
                        self.params, self.cache_mgr.cache, tokens, offsets, lengths
                    )
            with TraceAnnotation("serve.readback", kind="prefill"):
                if self.sample_on_device:
                    nxt, done, lg = np.asarray(nxt), np.asarray(done), None
                else:
                    nxt, done, lg = None, None, np.asarray(logits)
            self.stats.prefill_dispatches += 1
            self.stats.prefill_slots += slots_run
            self.stats.dispatches += 1
            self.heartbeat()
            with TraceAnnotation("serve.accept"):
                emitted += self._accept_prefill(rows, lengths, nxt, done, lg)

    def _plan_prefill(self, left: Optional[int]) -> Tuple[Dict[int, int], Optional[int]]:
        """Per-row prompt tokens for the next prefill dispatch under the
        tick's remaining budget ``left`` (None = unbounded: drain every
        prompt this tick), fixed BEFORE the reservation pass.  A plan holds
        at most ``prefill_rows`` rows; :meth:`_ingest_prompts` dispatches
        again while budget and prefilling rows remain.  An empty plan ends
        the tick's ingestion.  Returns ``(plan, left)``."""
        C, R = self.prefill_chunk, self.prefill_rows
        slots = self.scheduler.slots
        plan: Dict[int, int] = {}
        prefilling = [
            i for i, s in enumerate(slots)
            if s.req is not None and s.remaining_prompt
        ]
        if not prefilling or (left is not None and left <= 0):
            return plan, left
        if left is None:
            for i in prefilling[:R]:
                plan[i] = min(C, len(slots[i].remaining_prompt))
            return plan, left
        # fair-share the remaining budget across the plan's rows (ceil of
        # an even split each), rotating the head row by tick so a budget
        # smaller than the row count cannot pin-starve the same rows
        # forever — lowest-index-first would hold a short prompt hostage
        # behind a long one
        start = self.scheduler.tick % len(prefilling)
        order = (prefilling[start:] + prefilling[:start])[:R]
        for idx, i in enumerate(order):
            share = -(-left // (len(order) - idx))
            n = min(C, len(slots[i].remaining_prompt), share)
            if n > 0:
                plan[i] = n
                left -= n
        return plan, left

    def _accept_prefill(self, rows, lengths, nxt, done, lg) -> int:
        """Advance each row of a prefill dispatch past its ingested chunk;
        a row whose prompt is now resident publishes it and takes its
        first token."""
        emitted = 0
        slots = self.scheduler.slots
        for i in rows:
            slot = slots[i]
            n = int(lengths[i])
            del slot.remaining_prompt[:n]
            slot.pos += n
            self.stats.prompt_tokens_ingested += n
            if slot.remaining_prompt:
                continue
            # prompt fully resident: publish its full pages to the prefix
            # cache BEFORE accept (which may finish the row and drop its
            # references)
            self.cache_mgr.prefix_insert(i, slot.req.prompt)
            if self.worker_role == "prefill":
                # disaggregated prefill: the full prompt's KV — full pages
                # plus the sub-page tail under its extended content key —
                # is published while the row still holds its pages, then
                # the request finishes WITHOUT sampling.  Sampling streams
                # are (seed, stream, step)-keyed, so skipping the draw
                # consumes no state: the decode worker's frontier sample
                # at (stream, 0) is the same token a monolith would have
                # emitted here
                with TraceAnnotation("serve.prefix_insert"):
                    self.cache_mgr.publish_generation(i, slot.req.prompt)
                with TraceAnnotation("serve.store.publish"):
                    self.cache_mgr.ensure_chain_published(i, slot.req.prompt)
                self.scheduler.finish(i)
                continue
            # the chunk's last-token logits seed generation
            tok = (
                int(nxt[i])
                if nxt is not None
                else self._host_sample(
                    lg[i], slot.req.temperature,
                    stream=slot.req.sample_stream,
                    step=len(slot.req.output),
                )
            )
            self._accept_token(i, tok, bool(done[i]) if done is not None else None)
            emitted += 1
        return emitted

    # -- decode -------------------------------------------------------------
    def _build_decode_inputs(self):
        B = self.max_batch
        slots = self.scheduler.slots
        if self.cache_mode == "paged":
            # reservation pass first (see _ingest_prompts): allocation may
            # CoW a shared page or preempt/yield a slot, so inputs are
            # built only from the rows that still hold their pages
            # afterwards (a False return means the row was requeued —
            # slot.req is None and its released table row is all OOB
            # sentinel, so the batch-wide scatter at its stale position
            # is dropped on device).  Rows held mid-prefill by the tick
            # budget are covered too: the batch-wide dispatch still
            # writes (garbage) KV at their pos through their LIVE page
            # table, so a shared prefix page in that position must be
            # privatized first — the row itself overwrites the position
            # when its prefill resumes
            with TraceAnnotation("serve.pages"):
                for i, s in enumerate(slots):
                    if s.req is not None:
                        self.cache_mgr.ensure_pages(i, s.pos + 1, write_start=s.pos)
        with TraceAnnotation("serve.inputs"):
            tokens = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            streams = np.zeros((B,), np.int32)
            steps = np.zeros((B,), np.int32)
            stops = np.full((B,), -1, np.int32)
            max_news = np.full((B,), 1 << 30, np.int32)
            active = []
            for i, slot in enumerate(slots):
                # parked rows keep their stale pos: dense mode confines the
                # write to their own (dead) row, which is zeroed at refill;
                # paged mode drops it on the OOB page-table sentinel
                pos[i] = slot.pos
                if slot.req is None or self._mid_prefill(slot):
                    continue
                active.append(i)
                if slot.remaining_prompt:  # decode-path ingestion fallback
                    tokens[i, 0] = slot.remaining_prompt[0]
                elif slot.req.output:
                    tokens[i, 0] = slot.req.output[-1]
                else:
                    tokens[i, 0] = slot.req.prompt[-1]
                temps[i] = slot.req.temperature
                streams[i] = slot.req.sample_stream
                steps[i] = len(slot.req.output)
                if slot.req.stop_token is not None:
                    stops[i] = slot.req.stop_token
                max_news[i] = slot.req.max_new_tokens
        return active, tokens, pos, temps, streams, steps, stops, max_news

    def _mid_prefill(self, slot: Slot) -> bool:
        """Under a finite prefill budget a fused-prefill row can reach the
        decode tick with prompt tokens still pending; it sits the decode
        out and resumes chunked prefill next tick.  (Without fused
        prefill, remaining_prompt rows ARE the decode-path ingestion.)"""
        return bool(self._use_prefill and slot.remaining_prompt)

    def _decode_dispatch(
        self, n_rows, tokens, pos, temps, streams, steps, stops, max_news
    ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray], Optional[np.ndarray]]:
        """One decode dispatch over the whole batch; ``n_rows`` rows
        consume its tokens."""
        self.cache_mgr.push_table()
        with TraceAnnotation("serve.dispatch.decode", rows=n_rows, tokens=n_rows):
            if self.sample_on_device:
                nxt, done, self.cache_mgr.cache = self._decode(
                    self.params, self.cache_mgr.cache, tokens, pos, temps, streams,
                    steps, stops, max_news,
                )
            else:
                logits, self.cache_mgr.cache = self._decode(
                    self.params, self.cache_mgr.cache, tokens, pos
                )
        with TraceAnnotation("serve.readback", kind="decode"):
            if self.sample_on_device:
                out = (np.asarray(nxt), np.asarray(done), None)
            else:
                out = (None, None, np.asarray(logits))
        self.stats.decode_dispatches += 1
        self.stats.steps_executed += 1
        self.stats.dispatches += 1
        self.heartbeat()
        return out

    def _advance_rows(self, rows, nxt, done, lg) -> int:
        emitted = 0
        slots = self.scheduler.slots
        for i in rows:
            slot = slots[i]
            slot.pos += 1
            if slot.remaining_prompt:
                slot.remaining_prompt.pop(0)
                self.stats.prompt_tokens_ingested += 1
                if slot.remaining_prompt:
                    continue  # still ingesting the prompt
                # decode-path ingestion just wrote the last prompt token:
                # publish the prompt's full pages (MoE/MLA archs reach the
                # prefix cache through this path)
                self.cache_mgr.prefix_insert(i, slot.req.prompt)
            tok = (
                int(nxt[i])
                if nxt is not None
                else self._host_sample(
                    lg[i], slot.req.temperature,
                    stream=slot.req.sample_stream, step=len(slot.req.output),
                )
            )
            self._accept_token(i, tok, bool(done[i]) if done is not None else None)
            emitted += 1
        return emitted

    def _decode_tick_fused(self) -> int:
        active, *inputs = self._build_decode_inputs()
        if not active:
            return 0
        nxt, done, lg = self._decode_dispatch(len(active), *inputs)
        with TraceAnnotation("serve.accept"):
            return self._advance_rows(active, nxt, done, lg)

    def _decode_tick_spec(self) -> int:
        """Speculative decode tick: propose up to ``spec_k`` draft tokens
        per decode-ready slot, verify all drafts plus the bonus position
        in ONE fused chunk-extend dispatch through the page table, accept
        the longest consistent run, and roll rejected positions back.

        Byte parity with :meth:`_decode_tick_fused` is structural, not
        statistical: the verify step samples position ``t`` from the
        stream key ``(stream, len(output) + t)`` — the exact key the
        non-speculative engine would use for that token — and emission
        truncates at the first per-position done, so a request's output
        is identical token-for-token no matter how many drafts were
        proposed or accepted.  Speculation only changes how many tokens
        land per dispatch (``accepted_per_dispatch``).  Rows whose
        proposer returns nothing degrade to plain one-token decode
        inside the same dispatch."""
        B, T = self.max_batch, self.spec_k + 1
        slots = self.scheduler.slots
        ready = [
            i for i, s in enumerate(slots)
            if s.req is not None and not s.remaining_prompt
        ]
        if not ready:
            return 0
        with TraceAnnotation("serve.inputs"):
            hists = {i: slots[i].req.prompt + slots[i].req.output for i in ready}
            with TraceAnnotation("serve.propose"):
                drafts = self.proposer.propose(ready, hists, self.spec_k)
            plan: Dict[int, List[int]] = {}
            for i in ready:
                # cap drafts so the slot can never advance past the
                # max_len-1 truncation point the non-speculative engine
                # finishes at
                room = self.max_len - 2 - slots[i].pos
                plan[i] = list(drafts.get(i, []))[:max(0, room)]
        if self.cache_mode == "paged":
            # reservation pass first (see _build_decode_inputs): the
            # verify dispatch writes pos .. pos+len(drafts) per row, and
            # a later row's allocation may preempt an earlier one.  Only
            # the base position (what plain decode would write) carries
            # full recovery semantics; draft positions are best-effort
            # and shrink the plan under pool pressure instead of
            # preempting or raising — speculation must never OOM a
            # workload the non-speculative engine serves
            with TraceAnnotation("serve.pages"):
                for i in ready:
                    s = slots[i]
                    if s.req is not None:
                        got = self.cache_mgr.reserve_speculative(
                            i, s.pos + 1, s.pos + 1 + len(plan[i]),
                            write_start=s.pos,
                        )
                        if got is not None:
                            plan[i] = plan[i][:max(0, got - (s.pos + 1))]
        live = [i for i in ready if slots[i].req is not None]
        if not live:
            return 0
        with TraceAnnotation("serve.inputs"):
            tokens = np.zeros((B, T), np.int32)
            offsets = np.zeros((B,), np.int32)
            lengths = np.zeros((B,), np.int32)
            temps = np.zeros((B,), np.float32)
            streams = np.zeros((B,), np.int32)
            steps = np.zeros((B,), np.int32)
            stops = np.full((B,), -1, np.int32)
            max_news = np.full((B,), 1 << 30, np.int32)
            n_tokens = 0
            for i in live:
                s = slots[i]
                d = plan[i]
                tokens[i, 0] = s.req.output[-1] if s.req.output else s.req.prompt[-1]
                tokens[i, 1:1 + len(d)] = d
                offsets[i] = s.pos
                lengths[i] = 1 + len(d)
                n_tokens += 1 + len(d)
                temps[i] = s.req.temperature
                streams[i] = s.req.sample_stream
                steps[i] = len(s.req.output)
                if s.req.stop_token is not None:
                    stops[i] = s.req.stop_token
                max_news[i] = s.req.max_new_tokens
                self.stats.draft_tokens_proposed += len(d)
        self.cache_mgr.push_table()
        with TraceAnnotation("serve.dispatch.verify", rows=len(live), tokens=n_tokens):
            tgt, n_emit, done, self.cache_mgr.cache = self._verify(
                self.params, self.cache_mgr.cache, tokens, offsets, lengths,
                temps, streams, steps, stops, max_news,
            )
        with TraceAnnotation("serve.readback", kind="verify"):
            tgt, n_emit, done = np.asarray(tgt), np.asarray(n_emit), np.asarray(done)
        self.stats.decode_dispatches += 1
        self.stats.steps_executed += 1
        self.stats.dispatches += 1
        self.stats.spec_dispatches += 1
        self.heartbeat()
        emitted = 0
        with TraceAnnotation("serve.accept"):
            for i in live:
                s = slots[i]
                n = int(n_emit[i])
                new_pos = s.pos + n
                if n < int(lengths[i]):
                    # rejected positions: rewind the write frontier;
                    # trailing whole pages go back to the pool (CoW
                    # rollback), stale KV inside the kept page sits past
                    # the frontier (masked)
                    self.cache_mgr.rewind_slot(i, new_pos)
                s.pos = new_pos
                self.stats.draft_tokens_accepted += n - 1
                self.stats.spec_tokens_emitted += n
                fin = bool(done[i]) or new_pos >= self.max_len - 1
                for t in range(n):
                    s.req.output.append(int(tgt[i, t]))
                    self.stats.tokens_emitted += 1
                    self.scheduler.on_token(i)
                emitted += n
                if fin:
                    self.scheduler.finish(i)
                    self.proposer.release(i)
        return emitted

    def _decode_tick_grouped(self) -> int:
        """Seed-style dispatching: one jitted call per distinct slot
        position.  Every call carries the full per-row position vector, so
        cache writes are correct and idempotent across the tick's calls
        (the seed's scalar-pos variant overwrote OTHER rows' histories);
        only the group's rows consume their call's outputs."""
        active, *inputs = self._build_decode_inputs()
        if not active:
            return 0
        groups: Dict[int, List[int]] = {}
        for i in active:
            groups.setdefault(self.scheduler.slots[i].pos, []).append(i)
        emitted = 0
        for _, rows in sorted(groups.items()):
            nxt, done, lg = self._decode_dispatch(len(active), *inputs)
            with TraceAnnotation("serve.accept"):
                emitted += self._advance_rows(rows, nxt, done, lg)
        return emitted

    # -- bookkeeping ---------------------------------------------------------
    def _accept_token(self, row: int, tok: int, done: Optional[bool] = None) -> None:
        slot = self.scheduler.slots[row]
        slot.req.output.append(tok)
        self.stats.tokens_emitted += 1
        self.scheduler.on_token(row)
        if done is None:
            # host fallback (sample_on_device=False): re-derive the mask
            done = len(slot.req.output) >= slot.req.max_new_tokens or (
                slot.req.stop_token is not None and tok == slot.req.stop_token
            )
        if done or slot.pos >= self.max_len - 1:
            self.scheduler.finish(row)

    def _host_sample(
        self,
        lg_row: np.ndarray,
        temperature: float,
        stream: Optional[int] = None,
        step: Optional[int] = None,
    ) -> int:
        """Host fallback sampler (``sample_on_device=False``): greedy or
        max-subtracted softmax — ``np.exp(lg / T)`` on raw logits overflows
        for large-magnitude logits.

        When the caller passes the request's ``(stream, step)``, the draw
        comes from an rng keyed on ``(seed, stream, step)`` — like the
        on-device path, independent of scheduling, slot assignment, and
        preemption replays.  Without them (direct/debug calls) it falls
        back to the engine-level rng."""
        lg = np.asarray(lg_row, np.float64)
        if temperature <= 0:
            return int(np.argmax(lg))
        z = (lg - lg.max()) / temperature
        p = np.exp(z)
        p /= p.sum()
        rng = (
            np.random.default_rng((self._rng_seed, stream, step))
            if stream is not None
            else self.rng
        )
        return int(rng.choice(len(p), p=p))

    def run_to_completion(self, max_steps: int = 100_000) -> List[Request]:
        steps = 0
        while (
            self.scheduler.pending or self.scheduler.has_active()
        ) and steps < max_steps:
            self.step()
            steps += 1
        # drain seam: background prefix-store publishes must be durable
        # before callers compare counters or hand pages to another engine
        self.cache_mgr.flush_store()
        return self.scheduler.finished


def _stats_alias(name: str) -> property:
    """Read/write view of one EngineStats counter on the engine."""
    return property(
        lambda self: getattr(self.stats, name),
        lambda self, value: setattr(self.stats, name, value),
    )


def _cache_alias(name: str) -> property:
    return property(lambda self: getattr(self.cache_mgr, name))


for _name in (
    "steps_executed", "decode_dispatches", "prefill_dispatches", "dispatches",
    "tokens_emitted", "prompt_tokens_ingested", "prefill_slots",
    "pages_in_use", "peak_pages", "page_allocs", "page_bytes",
    "dense_cache_bytes",
    "prefix_hit_tokens", "prompt_tokens_skipped", "pages_shared_peak",
    "prefix_hit_tokens_partial", "cow_partial_stitches",
    "cow_copies", "prefix_evictions", "preemptions", "tokens_discarded",
    "prefix_store_pages_published", "prefix_store_pages_hydrated",
    "prefix_store_tokens_hydrated",
    "spec_dispatches", "draft_dispatches",
    "draft_tokens_proposed", "draft_tokens_accepted", "spec_tokens_emitted",
    "revocation_notices", "drain_requeued_requests", "requests_resumed",
    "lease_slices", "lease_resumes",
    "checkpoints_published", "checkpoint_resumes", "tokens_recovered",
    "checkpoint_fallbacks", "decode_tokens_discarded",
    "publish_retries", "prefix_store_hash_mismatches",
    "hydration_fetch_ops", "prefix_store_bytes_fetched", "publish_dedup_hits",
    "handoffs_published", "handoffs_admitted", "handoff_fallbacks",
    "handoff_seal_rejects",
):
    setattr(ServeEngine, _name, _stats_alias(_name))
for _name in (
    "page_size", "n_pages", "pages_per_slot",
    "_free_pages", "_page_refs", "_slot_pages", "_table",
):
    setattr(ServeEngine, _name, _cache_alias(_name))
del _name

"""KVCacheManager — the serving tier's cache layer.

Owns everything about *where KV state lives*: the device cache pytree
(dense per-slot reservation or pooled pages + page table), the host-side
free-list allocator with per-page refcounts, copy-on-write of shared
pages, adaptive pool sizing, the radix prefix cache
(``repro.serving.prefix_cache``) and the optional cross-host prefix
store (``repro.serving.prefix_store``).  It never touches request
lifecycle (the scheduler's job) or device dispatch (the executor's).

Contracts with the other layers:

- the executor reads :attr:`cache`, passes it to its jitted dispatches
  and writes the returned pytree back; :meth:`push_table` must be
  called before any dispatch so the device page table matches the host
  shadow;
- :meth:`ensure_pages` is called ahead of every dispatch that will
  write a row's positions.  It allocates pages (allocate-on-write),
  privatizes shared pages in the write range (copy-on-write) and, on
  pool exhaustion, recovers by LRU prefix eviction then — through the
  scheduler-provided :attr:`preempt_for` callback — youngest-slot
  preemption.  ``False`` means the row itself was preempted and must be
  dropped from the dispatch;
- at admission the scheduler calls :meth:`stitch_prefix`; when a
  prompt becomes fully resident the executor calls
  :meth:`prefix_insert`.  Both are no-ops without the radix cache.

Allocator invariants (exercised by ``tests/test_serving_layers.py``
under randomized interleaving): a page's refcount equals the number of
slot tables mapping it plus one if the radix cache indexes it; a page
returns to the free list exactly at refcount zero; two unrelated slots
never map the same page (sharers always stitched byte-identical chunk
content); after a full drain ``pages_in_use`` equals the pages the
radix cache retains, each at refcount 1.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.prefix_cache import PrefixCache
from repro.serving.prefix_store import PrefixStore
from repro.serving.types import EngineStats, Slot

_LOG = logging.getLogger(__name__)


class KVCacheManager:
    def __init__(
        self,
        model,
        *,
        max_batch: int,
        max_len: int,
        stats: EngineStats,
        cache_mode: str = "dense",
        page_size: int = 16,
        total_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_store: Optional[PrefixStore] = None,
    ):
        self.model = model
        self.max_batch = max_batch
        self.max_len = max_len
        self.stats = stats
        self.cache_mode = cache_mode
        self.page_size = int(page_size)
        self.store = prefix_store if cache_mode == "paged" else None
        # background publish worker (created on first use): the tick loop
        # snapshots page bytes + increments counters synchronously, but
        # the object-store write itself happens off-thread.  flush_store()
        # is the drain seam (engine drain / lease end / teardown)
        self._publisher = None
        # chunk keys this engine has already published or seen present:
        # stops every later request sharing the prefix from re-paying a
        # store round-trip per chunk in prefix_insert
        self._published: set = set()
        # wired by the engine to RequestScheduler.preempt_for /
        # RequestScheduler.preempt: pool-pressure recovery crosses the
        # layer seam exactly here.  preempt_for never victimizes the
        # requester; when it answers YIELD the requester is requeued via
        # preempt_row — at a clean seam AFTER the allocation loop
        # unwinds, never mid-allocation
        self.preempt_for: Callable[[int], Optional[int]] = lambda row: None
        self.preempt_row: Callable[[int], None] = lambda row: None
        if cache_mode == "paged":
            self.pages_per_slot = -(-max_len // self.page_size)
            self.prefix = PrefixCache(self.page_size) if prefix_cache else None
            self._adaptive = not total_pages
            if total_pages:
                self._init_paged_pool(int(total_pages), queue_depth=0)
            else:
                # sized adaptively from queue depth at first submit (and
                # grown, up to the dense reservation, on later submits)
                self.n_pages: Optional[int] = None
                self.cache = None
        else:
            self.prefix = None
            self.cache = model.init_cache(max_batch, max_len)

    def cache_is_rolling(self) -> bool:
        """Sliding-window KV caches wrap writes mod t; right-padded prefill
        chunks could then alias still-visible slots — decode-path ingest.
        (Paged caches are never rolling; an adaptively-sized pool may not
        exist yet, which is fine for this check.)"""
        k = self.cache.get("k") if isinstance(self.cache, dict) else None
        return k is not None and k.shape[2] < self.max_len

    # ------------------------------------------------------------ pool setup
    def _init_paged_pool(self, total_pages: Optional[int], queue_depth: int,
                         pending: Optional[list] = None) -> None:
        """Create the device page pool and the host-side allocator state.

        ``total_pages=None`` sizes the pool adaptively from the queue at
        first submit: enough pages for the ``min(max_batch, queue depth)``
        largest queued requests (prompt + new-token budget, in whole
        pages) plus one request's worth of headroom for retained cached
        prefixes, clamped between one request and the dense reservation.
        """
        dense_pages = self.max_batch * self.pages_per_slot
        if total_pages is None:
            total_pages = self._adaptive_pages(pending or [])
            _LOG.info(
                "paged pool sized adaptively: %d pages of %d tokens "
                "(queue depth %d, max_batch %d, dense reservation %d pages)",
                total_pages, self.page_size, queue_depth, self.max_batch,
                dense_pages,
            )
        self.n_pages = int(total_pages)
        self.cache = self.model.init_cache(
            self.max_batch, self.max_len,
            paged=True, page_size=self.page_size, n_pages=self.n_pages,
        )
        # host-side allocator: free list + per-page refcounts + per-slot
        # page lists + the numpy shadow of the device page table (OOB
        # sentinel = unbacked)
        self._free_pages = list(range(self.n_pages))
        self._page_refs = [0] * self.n_pages
        self._slot_pages: List[List[int]] = [[] for _ in range(self.max_batch)]
        self._table = np.full(
            (self.max_batch, self.pages_per_slot), self.n_pages, np.int32
        )
        self._table_dirty = True
        # bytes of ONE page across every layer and pool leaf (k+v, or
        # the MLA latent pool) — peak_cache_bytes = peak_pages * this
        self.stats.page_bytes = sum(
            leaf.size * leaf.dtype.itemsize // self.n_pages
            for name, leaf in self.cache.items()
            if name.endswith("_pages")
        )
        self.stats.dense_cache_bytes = dense_pages * self.stats.page_bytes

    def _adaptive_pages(self, pending: list) -> int:
        """Pool size for the current queue: pages for the
        ``min(max_batch, queue depth)`` largest queued requests (prompt +
        new-token budget, whole pages) + one request of headroom for
        retained prefixes + pages already resident, clamped between one
        request and the dense reservation."""
        ps = self.page_size
        dense_pages = self.max_batch * self.pages_per_slot
        demands = [
            min(self.pages_per_slot, -(-(len(r.prompt) + r.max_new_tokens) // ps))
            for r in pending
        ] or [self.pages_per_slot]
        per_req = max(demands)
        conc = max(1, min(self.max_batch, len(pending)))
        want = sum(sorted(demands)[-conc:]) + per_req + self.stats.pages_in_use
        return max(per_req, min(dense_pages, want))

    def on_submit(self, pending: list) -> None:
        """Adaptive pool sizing, deferred to first (non-empty) submit so
        the queue depth is known; later submits can only GROW the pool,
        up to the dense reservation — never strand a bigger-than-pool
        request."""
        if self.cache_mode != "paged" or not self._adaptive or not pending:
            return
        if self.cache is None:
            self._init_paged_pool(None, len(pending), pending)
            return
        want = self._adaptive_pages(pending)
        if want > self.n_pages:
            # geometric step (>= 1.5x) so a stream of growing jobs
            # pays O(log) recompiles, not one per submit
            dense_pages = self.max_batch * self.pages_per_slot
            self._grow_pool(
                min(dense_pages,
                    max(want, self.n_pages + -(-self.n_pages // 2))),
                len(pending),
            )

    def _grow_pool(self, new_n: int, queue_depth: int) -> None:
        """Extend an adaptively-sized pool in place (later submits may
        queue larger requests than the first sizing saw).  Existing pages
        keep their ids; the OOB sentinel moves from old to new ``n_pages``
        in the table shadow and is re-pushed before the next dispatch.
        Growing changes the pool leaves' shapes, so the next dispatch
        retraces the jitted step — the submit path grows in geometric
        steps to bound how often that compile cliff is paid."""
        import jax.numpy as jnp

        old = self.n_pages
        for name, leaf in self.cache.items():
            if name.endswith("_pages"):
                pad = jnp.zeros(
                    leaf.shape[:1] + (new_n - old,) + leaf.shape[2:], leaf.dtype
                )
                self.cache[name] = jnp.concatenate([leaf, pad], axis=1)
        self.n_pages = new_n
        self._free_pages.extend(range(old, new_n))
        self._page_refs.extend([0] * (new_n - old))
        self._table[self._table == old] = new_n
        self._table_dirty = True
        _LOG.info(
            "paged pool grown adaptively: %d -> %d pages (queue depth %d)",
            old, new_n, queue_depth,
        )

    # ------------------------------------------------------- page allocator
    @property
    def peak_cache_bytes(self) -> int:
        """High-water cache footprint: pages actually resident (paged) or
        the full dense reservation."""
        if self.cache_mode != "paged":
            return sum(
                leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(self.cache)
            )
        return self.stats.peak_pages * self.stats.page_bytes

    def _incref(self, pid: int) -> None:
        """Add a reference (stitch / cache adoption), tracking the shared
        high-water mark at the 1 -> 2 transition."""
        self._page_refs[pid] += 1
        if self._page_refs[pid] == 2:
            self.stats._shared_pages += 1
            if self.stats._shared_pages > self.stats.pages_shared_peak:
                self.stats.pages_shared_peak = self.stats._shared_pages

    def _decref(self, pid: int) -> None:
        """Drop one reference; the page returns to the free list only when
        its last holder (slot or prefix cache) lets go."""
        self._page_refs[pid] -= 1
        if self._page_refs[pid] < 0:  # allocator invariant
            raise AssertionError(f"page {pid} refcount went negative")
        if self._page_refs[pid] == 1:
            self.stats._shared_pages -= 1
        elif self._page_refs[pid] == 0:
            self._free_pages.append(pid)  # LIFO: reuse hot pages
            self.stats.pages_in_use -= 1

    def _take_free_page(self) -> Optional[int]:
        """Pop a free page (refcount 1) WITHOUT recovery and WITHOUT peak
        tracking — callers record the high-water mark once their batch
        of allocations settles (a CoW transiently holds old + new page
        before the decref, which must not inflate the peak).  None when
        the free list is empty."""
        if not self._free_pages:
            return None
        pid = self._free_pages.pop()
        self._page_refs[pid] = 1
        self.stats.pages_in_use += 1
        self.stats.page_allocs += 1
        return pid

    def _alloc_page(self, row: int) -> Optional[int]:
        """Claim a free page for ``row`` (refcount 1).

        On exhaustion, recover in escalating order: evict LRU cached
        prefixes nobody maps, then ask the scheduler (``preempt_for``)
        to preempt the youngest active slot strictly younger than the
        requester — the scheduler never victimizes the requester itself
        mid-allocation.  A ``YIELD`` answer (the requester is the
        youngest; age priority says it is the one that must go) returns
        ``None``: the caller unwinds its allocation loop and requeues
        the row through :meth:`_yield_row`.  The ``victim == row`` guard
        is defensive against foreign ``preempt_for`` implementations.
        Raises only when a lone request cannot fit in the entire pool.
        """
        while not self._free_pages:
            if self.prefix is not None:
                evicted = self.prefix.evict(1, lambda p: self._page_refs[p])
                if evicted:
                    for pid in evicted:
                        self._decref(pid)  # cache ownership -> free list
                    self.stats.prefix_evictions += len(evicted)
                    continue
            victim = self.preempt_for(row)
            if victim is None:
                raise RuntimeError(
                    f"paged KV pool exhausted ({self.n_pages} pages of "
                    f"{self.page_size} tokens) with nothing evictable or "
                    "preemptable; raise total_pages or lower request length"
                )
            if victim < 0 or victim == row:
                return None  # requester must yield (see _yield_row)
        return self._take_free_page()  # non-None: the loop freed a page

    def _copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate one physical page across every layer
        and pool leaf (one device op per leaf, outside the jitted step)."""
        with TraceAnnotation("serve.pages.cow"):
            for name, leaf in self.cache.items():
                if name.endswith("_pages"):
                    self.cache[name] = leaf.at[:, dst].set(leaf[:, src])

    def ensure_pages(
        self, row: int, n_tokens: int, write_start: Optional[int] = None
    ) -> bool:
        """Back row ``row``'s first ``n_tokens`` positions with physical
        pages (allocate-on-write, called ahead of every dispatch that will
        write those positions).

        ``write_start`` marks the first position the coming dispatch will
        write: any page in the write range that another holder (a sharing
        slot or the prefix cache) still references is copied to a private
        page first, so shared pages are immutable once published.  Returns
        False when the row could not be backed and was yielded back to
        the queue (or preempted by another row's recovery); the caller
        must drop it from this tick's dispatch.
        """
        need = -(-n_tokens // self.page_size)
        if need > self.pages_per_slot:
            raise ValueError(
                f"request needs {n_tokens} cache positions but max_len="
                f"{self.max_len} caps a slot at {self.pages_per_slot} pages "
                f"of {self.page_size} tokens"
            )
        pages = self._slot_pages[row]
        shortfall = (need - len(pages)) - len(self._free_pages)
        if write_start is not None:
            # the CoW pass below will also allocate one page per shared
            # page in the write range — count those into the bulk reclaim
            shortfall += sum(
                1
                for j in range(min(write_start // self.page_size, len(pages)),
                               min(need, len(pages)))
                if self._page_refs[pages[j]] > 1
            )
        if shortfall > 0 and self.prefix is not None:
            # bulk pre-eviction: reclaim the whole shortfall in one radix
            # pass instead of one tree walk per page inside _alloc_page
            evicted = self.prefix.evict(shortfall, lambda p: self._page_refs[p])
            for pid in evicted:
                self._decref(pid)
            self.stats.prefix_evictions += len(evicted)
        while len(pages) < need:
            pid = self._alloc_page(row)
            if pid is None:
                return self._yield_row(row)
            self._table[row, len(pages)] = pid
            pages.append(pid)
            self._table_dirty = True
        if write_start is not None:
            for j in range(write_start // self.page_size, need):
                old = pages[j]
                if self._page_refs[old] > 1:  # shared: copy before write
                    new = self._alloc_page(row)
                    if new is None:
                        return self._yield_row(row)
                    self._copy_page(old, new)
                    self._decref(old)  # still >= 1: another slot / the cache
                    pages[j] = new
                    self._table[row, j] = new
                    self._table_dirty = True
                    self.stats.cow_copies += 1
        if self.stats.pages_in_use > self.stats.peak_pages:
            self.stats.peak_pages = self.stats.pages_in_use
        return True

    def reserve_speculative(
        self, row: int, base_tokens: int, want_tokens: int,
        write_start: Optional[int] = None,
    ) -> Optional[int]:
        """Back ``base_tokens`` positions with full :meth:`ensure_pages`
        semantics (eviction -> preemption -> yield: this is what the
        non-speculative dispatch would have demanded), then extend the
        backing toward ``want_tokens`` *best-effort* — free pages and
        prefix eviction only.  Draft positions are optional, so their
        pages must never preempt another slot or raise pool exhaustion:
        a speculative engine must run every workload the non-speculative
        engine runs, just with fewer drafts under pressure.

        Returns the number of positions backed (``>= base_tokens``), or
        ``None`` when the row could not get even its base demand and was
        yielded/preempted — the caller drops it from this dispatch.
        """
        if not self.ensure_pages(row, base_tokens, write_start=write_start):
            return None
        pages = self._slot_pages[row]
        want = min(-(-want_tokens // self.page_size), self.pages_per_slot)
        while len(pages) < want:
            pid = self._take_free_page()
            if pid is None and self.prefix is not None:
                evicted = self.prefix.evict(1, lambda p: self._page_refs[p])
                for e in evicted:
                    self._decref(e)
                self.stats.prefix_evictions += len(evicted)
                pid = self._take_free_page()
            if pid is None:
                break
            self._table[row, len(pages)] = pid
            pages.append(pid)
            self._table_dirty = True
        # a trailing page the row holds but *shares* caps the drafts at
        # its boundary: writing it would force a CoW copy, which drafts
        # aren't worth (cannot happen today — rewind decrefs trailing
        # pages and stitched pages sit below the frontier — but cheap)
        backed = len(pages)
        base_need = -(-base_tokens // self.page_size)
        for j in range(base_need, len(pages)):
            if self._page_refs[pages[j]] > 1:
                backed = j
                break
        if self.stats.pages_in_use > self.stats.peak_pages:
            self.stats.peak_pages = self.stats.pages_in_use
        return max(base_tokens, backed * self.page_size)

    def _yield_row(self, row: int) -> bool:
        """The requester is the youngest active slot and nothing could be
        freed for it: age priority says IT yields.  The yield happens
        here — after the allocation/CoW loop has fully unwound — never
        inside ``_alloc_page`` mid-loop (the old bug: ``preempt_for``
        could select the requesting row as victim mid-allocation and
        hand its own freshly-released row back to the allocator).  The
        scheduler requeues the request at the queue front and rolls its
        counters back; the deterministic per-request sampling streams
        make the rerun byte-identical.  Always returns False (the
        caller's drop-this-row signal).  The engine's wiring skips the
        requeue when the slot is already empty (a foreign ``preempt_for``
        policy preempted it directly)."""
        self.preempt_row(row)
        return False

    def can_admit(self) -> bool:
        """Admission control under pool pressure (consulted by the
        scheduler's refill): a request admitted into a pool with neither
        a free page nor an LRU-evictable cached page can only yield
        straight back to the queue on its first allocation (it is the
        youngest slot, so preemption is not available to it) — a pure
        admit/rollback churn cycle.  Holding the queue until a page
        exists lets the active slots run to completion and open the
        gate.  (When nothing is active every pool page is free or an
        evictable cached leaf, so the gate can never deadlock.)"""
        if self.cache_mode != "paged" or self.cache is None:
            return True
        if self._free_pages:
            return True
        return self.prefix is not None and self.prefix.evictable(
            lambda p: self._page_refs[p]
        )

    def release_slot(self, row: int) -> None:
        """Drop the slot's references (free-on-finish for private pages;
        shared/cached pages stay resident) and reset its table row to the
        OOB sentinel so stale writes become no-ops."""
        if self.cache_mode != "paged" or self.cache is None:
            return
        pages = self._slot_pages[row]
        if not pages:
            return
        for pid in reversed(pages):
            self._decref(pid)
        self._slot_pages[row] = []
        self._table[row, :] = self.n_pages
        self._table_dirty = True

    def rewind_slot(self, row: int, n_tokens: int) -> None:
        """Speculative rollback: shrink row ``row``'s backing to its first
        ``n_tokens`` positions after a verify dispatch accepted fewer
        tokens than were written.

        Only whole pages past the new frontier are dropped (decref — a
        page CoW-privatized for the dispatch goes straight back to the
        free list at refcount 0; the sentinel makes any stale in-flight
        write a device no-op).  Rejected tokens inside the kept tail page
        need no touch-up: they sit at positions >= the slot's rewound
        ``pos``, past every future query under the causal mask, and the
        next dispatch's ``write_start`` overwrites them — the same
        stale-past-the-frontier argument :meth:`reset_row` relies on.
        The page holding position ``n_tokens - 1`` is never shared at
        this point (ensure_pages privatized every page in the verify
        write range), so the accepted prefix cannot be aliased away."""
        if self.cache_mode != "paged" or self.cache is None:
            return
        keep = -(-n_tokens // self.page_size)
        pages = self._slot_pages[row]
        while len(pages) > keep:
            pid = pages.pop()
            self._table[row, len(pages)] = self.n_pages
            self._table_dirty = True
            self._decref(pid)

    def reset_row(self, row: int) -> None:
        """Prepare a row for a fresh admission.  Dense mode zeroes the
        row; paged mode has nothing to do (the row's pages went back to
        the free list at finish, its table row is the OOB sentinel, and
        stale data inside a re-issued page sits past the new owner's
        write frontier where the causal mask excludes it)."""
        if self.cache_mode == "paged":
            return
        import jax.numpy as jnp

        def zero_row(x):
            if x.ndim >= 2 and x.shape[1] == self.max_batch:
                return x.at[:, row].set(jnp.zeros_like(x[:, row]))
            return x

        self.cache = jax.tree.map(zero_row, self.cache)

    # --------------------------------------------------------- prefix cache
    def stitch_prefix(self, row: int, slot: Slot) -> None:
        """Admission-time prefix reuse (see :meth:`_stitch`).  Handoff
        admissions (``slot.req.handoff``) run the *demand-driven*
        hydration variant — the chain pages are expected to exist, so
        the walk may evict unpinned cached prefixes for room — and are
        accounted here: one ``hydration_ticks`` sample (store
        round-trips this admission made) and a ``handoff_fallbacks``
        count when the store could not cover the prompt down to the
        held-back frontier token (the remainder replays through the
        PR 8 ladder, byte-identical)."""
        demand = (
            bool(getattr(slot.req, "handoff", False))
            and self.store is not None
        )
        if not demand:
            self._stitch(row, slot, False)
            return
        ops0 = self.store.fetch_ops
        self._stitch(row, slot, True)
        self.stats._hydration_ticks.append(self.store.fetch_ops - ops0)
        # a guaranteed hit leaves exactly the held-back frontier token
        # to re-dispatch; anything longer means the store lied
        if len(slot.remaining_prompt) > 1:
            self.stats.handoff_fallbacks += 1
        self._sync_store_stats()

    def _stitch(self, row: int, slot: Slot, demand: bool) -> None:
        """Admission-time prefix reuse: map the longest cached prefix of
        the new request's prompt straight into its page table and skip
        prefill for those tokens.  With a cross-host store attached, a
        local radix miss first tries to hydrate pages other workers
        published.  At least one prompt token is always held back and
        re-dispatched — its logits seed generation — so a full-prompt
        hit re-writes one position inside the last shared page, which
        copy-on-write then privatizes.

        Matching does not stop at the last whole page: the longest
        common *token* prefix inside the first divergent page is reused
        too, by copying the partially-matched page into a slot-private
        page (the donor's divergent tail is overwritten when prefill
        resumes from the mid-page offset; until then it sits past the
        slot's write frontier where the causal mask excludes it).  The copy is
        best-effort: it only consumes a free page (or one LRU-evictable
        cached page), never preempts — a miss just falls back to the
        page-aligned stitch."""
        if self.prefix is None:
            return
        prompt = slot.req.prompt

        path, pnode, plen = self.prefix.match_partial(prompt)
        if self.store is not None:
            n_chunks = min(len(prompt) // self.page_size, self.pages_per_slot)
            hydrate = self._hydrate_demand if demand else self._hydrate
            if len(path) < n_chunks:
                with TraceAnnotation("serve.store.hydrate"):
                    hydrated = hydrate(prompt, [n.page for n in path], n_chunks)
                if hydrated:
                    # now extended locally (possibly exposing a new partial)
                    path, pnode, plen = self.prefix.match_partial(prompt)
        path = path[: self.pages_per_slot]
        matched = len(path) * self.page_size
        eff = min(matched, len(prompt) - 1)
        # sub-page candidate: tokens reusable inside the first divergent
        # page, capped by the hold-back (>= 1 token must be re-dispatched)
        # and dropped when the slot's table has no room for the CoW page
        partial = 0
        if pnode is not None and len(path) < self.pages_per_slot:
            partial = min(plen, len(prompt) - 1 - matched)
        # extended-key tail hydration: when the full-chunk match leaves a
        # sub-page remainder, another worker may have published exactly
        # that tail page under the extended content key (a drained slot's
        # generation checkpoint does — see publish_generation).  Fetching
        # it into a slot-private page turns a resume's whole frontier
        # into a hit; the hold-back still re-dispatches one token, whose
        # idempotent write lands inside the private page.  Best-effort:
        # free-list only, and skipped when a local partial sibling would
        # cover at least as many tokens
        tail_pid = None
        rem = list(prompt[matched:])
        if (
            self.store is not None
            and len(path) < self.pages_per_slot
            and 0 < len(rem) < self.page_size
            and len(rem) - 1 > partial
        ):
            parent = (
                self._chunk_keys(prompt, len(path))[-1]
                if path else self.store.root_key()
            )
            tkey = self.store.child_key(parent, rem)
            with TraceAnnotation("serve.store.hydrate"):
                arrays = self.store.fetch(tkey, self._page_like())
            self._sync_store_stats()
            if arrays is not None:
                pid = self._take_free_page()
                if pid is None and demand and self.prefix is not None:
                    # demand hydration may make room; pin the matched
                    # path with transient raw refcount bumps so the
                    # eviction pass cannot reclaim the chain mid-stitch
                    for n in path:
                        self._page_refs[n.page] += 1
                    try:
                        evicted = self.prefix.evict(
                            1, lambda p: self._page_refs[p]
                        )
                        for e in evicted:
                            self._decref(e)
                        self.stats.prefix_evictions += len(evicted)
                    finally:
                        for n in path:
                            self._page_refs[n.page] -= 1
                    pid = self._take_free_page()
                if pid is not None:
                    for name, arr in arrays.items():
                        self.cache[name] = self.cache[name].at[:, pid].set(arr)
                    tail_pid = pid
                    partial = len(rem) - 1
                    self._published.add(tkey)
                    self.stats.prefix_store_pages_hydrated += 1
                    self.stats.prefix_store_tokens_hydrated += partial
        if eff <= 0 and partial <= 0:
            return
        pages = self._slot_pages[row]
        for j, node in enumerate(path):
            self._incref(node.page)
            self._table[row, j] = node.page
            pages.append(node.page)
        if partial > 0:
            if tail_pid is not None:
                pid = tail_pid
            else:
                pid = self._cow_partial(pnode.page, row)
                if pid is not None:
                    self.stats.cow_partial_stitches += 1
            if pid is None:
                partial = 0  # no page to copy into: page-aligned fallback
            else:
                self._table[row, len(path)] = pid
                pages.append(pid)
                eff += partial
                slot.hit_tokens_partial = partial
                self.stats.prefix_hit_tokens_partial += partial
                if self.stats.pages_in_use > self.stats.peak_pages:
                    self.stats.peak_pages = self.stats.pages_in_use
        if eff <= 0:
            return
        self._table_dirty = True
        slot.pos = eff
        slot.remaining_prompt = list(prompt[eff:])
        slot.hit_tokens = matched
        slot.skipped_tokens = eff
        self.stats.prefix_hit_tokens += matched
        self.stats.prompt_tokens_skipped += eff

    def _cow_partial(self, src: int, row: int) -> Optional[int]:
        """Copy the partially-matched page ``src`` into a fresh private
        page for ``row`` (refcount 1).  Best-effort: tries the free list,
        then one LRU prefix eviction — never preemption (the caller is
        mid-admission).  ``src`` is pinned by a transient raw refcount
        bump (not :meth:`_incref`: the pin is not real sharing and must
        not touch ``pages_shared_peak``) so the eviction pass cannot
        reclaim the very page being copied."""
        self._page_refs[src] += 1
        try:
            pid = self._take_free_page()
            if pid is None and self.prefix is not None:
                evicted = self.prefix.evict(1, lambda p: self._page_refs[p])
                for e in evicted:
                    self._decref(e)
                self.stats.prefix_evictions += len(evicted)
                pid = self._take_free_page()
            if pid is not None:
                self._copy_page(src, pid)
            return pid
        finally:
            self._page_refs[src] -= 1

    def prefix_insert(self, row: int, prompt: List[int]) -> None:
        """Publish a freshly-ingested prompt's full pages to the radix
        cache (called the moment the prompt is fully resident, before the
        row can finish and release them).  Chunks already cached keep the
        cache's page; only newly adopted pages gain the cache's ref.
        With a cross-host store attached, the full chunks are also
        published under their chained content hashes."""
        if self.prefix is None:
            return
        n_full = min(len(prompt) // self.page_size, len(self._slot_pages[row]))
        if n_full == 0:
            return
        with TraceAnnotation("serve.prefix_insert"):
            pages = self._slot_pages[row][:n_full]
            adopted = self.prefix.insert(prompt, pages)
            for pid in adopted:
                self._incref(pid)
            if self.store is not None:
                with TraceAnnotation("serve.store.publish"):
                    self._publish(prompt, pages, n_full)

    # ----------------------------------------------- cross-host prefix store
    def _pool_leaves(self) -> Dict[str, object]:
        return {
            name: leaf for name, leaf in self.cache.items()
            if name.endswith("_pages")
        }

    def _page_arrays(self, pid: int) -> Dict[str, np.ndarray]:
        """One page's slice of every pool leaf, pulled to host."""
        return {name: np.asarray(leaf[:, pid]) for name, leaf in self._pool_leaves().items()}

    def _page_like(self) -> Dict[str, np.ndarray]:
        """Shape/dtype template a fetched page must match exactly."""
        return {
            name: np.empty(leaf.shape[:1] + leaf.shape[2:], leaf.dtype)
            for name, leaf in self._pool_leaves().items()
        }

    def _chunk_keys(self, prompt: List[int], n_chunks: int) -> List[str]:
        """Chained content keys for the first ``n_chunks`` full chunks."""
        ps = self.page_size
        keys, key = [], self.store.root_key()
        for j in range(n_chunks):
            key = self.store.child_key(key, prompt[j * ps:(j + 1) * ps])
            keys.append(key)
        return keys

    def _publish(self, prompt: List[int], pages: List[int], n_full: int) -> None:
        if len(self._published) > 100_000:
            # the memo only saves round-trips; resetting it is always
            # safe and bounds a long-lived engine on diverse traffic
            self._published.clear()
        for j, key in enumerate(self._chunk_keys(prompt, n_full)):
            if key in self._published:
                continue
            if not self.store.exists(key):
                # one existence probe, then an unconditional write: the
                # device->host page pull is deferred behind the probe,
                # and a concurrent publisher writing the same key is a
                # benign last-writer-wins race over identical bytes.
                # The pull happens at submit time (the pool page may be
                # evicted and reissued before the write lands) — passed
                # as a thunk so a submit the publisher dedups (the key
                # already pending in its queue) skips the device->host
                # pull and the pack entirely.  Serialization + the store
                # write run on the background publisher thread; counters
                # and the memo stay synchronous/deterministic
                if self._publisher is None:
                    self._publisher = self.store.publisher()
                self._publisher.submit(
                    key, lambda pid=pages[j]: self._page_arrays(pid)
                )
                self.stats.prefix_store_pages_published += 1
            self._published.add(key)

    def publish_generation(self, row: int, tokens: List[int]) -> int:
        """Publish a drained slot's resident KV — full chunks under the
        usual chained keys PLUS the sub-page tail under an extended
        content key — so a resuming worker gets a guaranteed prefix hit
        over ``tokens`` (the request's prompt + already-generated output
        minus the frontier token).  Today's page-quantized publish drops
        the partial last page; work-preserving recovery is exactly the
        case where that tail holds the paid-for decode work.  Returns
        the number of pages newly submitted for publication."""
        if self.store is None or self.cache_mode != "paged" or self.cache is None:
            return 0
        ps = self.page_size
        pages = self._slot_pages[row]
        before = self.stats.prefix_store_pages_published
        n_full = min(len(tokens) // ps, len(pages))
        if n_full:
            with TraceAnnotation("serve.store.publish"):
                self._publish(tokens, pages[:n_full], n_full)
        tail = tokens[n_full * ps:]
        if tail and n_full < len(pages):
            parent = (
                self._chunk_keys(tokens, n_full)[-1]
                if n_full else self.store.root_key()
            )
            tkey = self.store.child_key(parent, tail)
            if tkey not in self._published and not self.store.exists(tkey):
                # the tail blob carries the whole physical page; rows past
                # the tail frontier are garbage, but a hydrating reader
                # never attends past the frontier (causal mask) and the
                # hold-back re-dispatch overwrites the frontier position
                if self._publisher is None:
                    self._publisher = self.store.publisher()
                self._publisher.submit(
                    tkey, lambda pid=pages[n_full]: self._page_arrays(pid)
                )
                self.stats.prefix_store_pages_published += 1
            self._published.add(tkey)
        return self.stats.prefix_store_pages_published - before

    def chain_keys_for(self, tokens: List[int]) -> List[str]:
        """Content keys covering ``tokens``: every full chunk plus, when
        a sub-page remainder exists, its extended tail key — the exact
        set a handoff's demand hydration will fetch.  The prefill lease
        pins these against the TTL sweep before enqueueing a handoff."""
        if self.store is None:
            return []
        n_full = len(tokens) // self.page_size
        keys = self._chunk_keys(tokens, n_full)
        tail = tokens[n_full * self.page_size:]
        if tail:
            parent = keys[-1] if n_full else self.store.root_key()
            keys.append(self.store.child_key(parent, tail))
        return keys

    def ensure_chain_published(self, row: int, tokens: List[int]) -> List[str]:
        """Defensively re-probe and (re)submit every chain page covering
        ``tokens`` while row ``row`` still holds them, bypassing the
        ``_published`` memo — the memo means "submitted", not "durable",
        and a handoff points other workers at these exact keys.  A key
        whose queued write has not landed yet probes as absent and is
        resubmitted; the publisher's pending-set dedup then drops the
        duplicate before any snapshot/pack work (``publish_dedup_hits``).
        Returns the chain keys (full chunks + tail)."""
        if self.store is None or self.cache_mode != "paged" or self.cache is None:
            return []
        ps = self.page_size
        pages = self._slot_pages[row]
        n_full = min(len(tokens) // ps, len(pages))
        keys = self._chunk_keys(tokens, n_full)
        if self._publisher is None:
            self._publisher = self.store.publisher()
        for j, key in enumerate(keys):
            if not self.store.exists(key):
                self._publisher.submit(
                    key, lambda pid=pages[j]: self._page_arrays(pid)
                )
        tail = tokens[n_full * ps:]
        if tail and n_full < len(pages):
            parent = keys[-1] if n_full else self.store.root_key()
            tkey = self.store.child_key(parent, tail)
            keys.append(tkey)
            if not self.store.exists(tkey):
                self._publisher.submit(
                    tkey, lambda pid=pages[n_full]: self._page_arrays(pid)
                )
        self._sync_store_stats()
        return keys

    def _sync_store_stats(self) -> None:
        """Mirror the store/publisher-owned hardening counters into the
        shared stats block (they live on PrefixStore/AsyncPublisher so
        the store path has no stats dependency)."""
        if self.store is not None:
            self.stats.prefix_store_hash_mismatches = self.store.hash_mismatches
            self.stats.hydration_fetch_ops = self.store.fetch_ops
            self.stats.prefix_store_bytes_fetched = self.store.bytes_fetched
        if self._publisher is not None:
            self.stats.publish_retries = self._publisher.retries
            self.stats.publish_dedup_hits = self._publisher.dedup_hits

    def flush_store(self) -> None:
        """Drain the background publish queue (no-op without a store or
        before the first publish).  Called at the engine's natural drain
        seams so published pages are durable before counters are compared
        or the process exits."""
        if self._publisher is not None:
            self._publisher.flush()
        self._sync_store_stats()

    # ------------------------------------------------------------ debugging
    def check_invariants(self) -> None:
        """Assert the allocator's structural invariants (enabled after
        every engine tick under ``DS_DEBUG_INVARIANTS=1``):

        - every page's refcount equals its holder count — slot tables
          mapping it plus one if the radix cache indexes it (this also
          rules out unshared cross-slot aliasing: two slots on one page
          forces refcount >= 2);
        - no slot maps the same physical page twice;
        - the free list is duplicate-free, exactly the refcount-0 pages;
        - the table shadow mirrors the slot page lists (OOB sentinel
          past each slot's backing);
        - ``pages_in_use`` equals pool size minus free pages.

        Raises AssertionError with the failing page/slot on violation."""
        if self.cache_mode != "paged" or self.cache is None:
            return
        holders = [0] * self.n_pages
        for row, pages in enumerate(self._slot_pages):
            if len(set(pages)) != len(pages):
                raise AssertionError(
                    f"slot {row} maps a physical page twice: {pages}"
                )
            for j, pid in enumerate(pages):
                holders[pid] += 1
                if self._table[row, j] != pid:
                    raise AssertionError(
                        f"table shadow desync at slot {row} page {j}: "
                        f"table={self._table[row, j]} list={pid}"
                    )
            if not np.all(self._table[row, len(pages):] == self.n_pages):
                raise AssertionError(
                    f"slot {row}: table rows past its {len(pages)}-page "
                    "backing are not the OOB sentinel"
                )
        cached = set(self.prefix.pages()) if self.prefix is not None else set()
        for pid in range(self.n_pages):
            expect = holders[pid] + (1 if pid in cached else 0)
            if self._page_refs[pid] != expect:
                raise AssertionError(
                    f"page {pid}: refcount {self._page_refs[pid]} != "
                    f"{holders[pid]} slot holder(s)"
                    f"{' + 1 cache ref' if pid in cached else ''}"
                )
        free = self._free_pages
        if len(set(free)) != len(free):
            raise AssertionError("free list contains duplicates")
        for pid in free:
            if self._page_refs[pid] != 0:
                raise AssertionError(
                    f"free page {pid} has refcount {self._page_refs[pid]}"
                )
        zero = sum(1 for r in self._page_refs if r == 0)
        if zero != len(free):
            raise AssertionError(
                f"{zero} refcount-0 pages but {len(free)} on the free list"
            )
        if self.stats.pages_in_use != self.n_pages - len(free):
            raise AssertionError(
                f"pages_in_use={self.stats.pages_in_use} != "
                f"{self.n_pages - len(free)} resident pages"
            )

    def _hydrate(
        self, prompt: List[int], pages_so_far: List[int], n_chunks: int
    ) -> int:
        """Extend the local radix path for ``prompt`` (already covering
        ``pages_so_far`` chunks) from the cross-host store: fetch chunk
        pages other workers published, copy them into freshly allocated
        pool pages and index them, so the stitch that follows hits
        locally.  Hydration is best-effort and deliberately
        side-effect-free on other slots: it only consumes already-free
        pages (never evicts or preempts) and stops at the first miss or
        when the free list runs dry.  Returns the number of pages
        hydrated."""
        ps = self.page_size
        keys = self._chunk_keys(prompt, n_chunks)
        like = self._page_like()
        pages_so_far = list(pages_so_far)
        hydrated = 0
        for j in range(len(pages_so_far), n_chunks):
            arrays = self.store.fetch(keys[j], like)
            if arrays is None:
                break
            self._published.add(keys[j])  # a fetched page is in the store
            pid = self._take_free_page()
            if pid is None:
                break
            for name, arr in arrays.items():
                self.cache[name] = self.cache[name].at[:, pid].set(arr)
            pages_so_far.append(pid)
            hydrated += 1
        if hydrated:
            # the allocation above IS the cache's refcount on each
            # hydrated page (insert adopts them; nothing further to
            # incref)
            self.prefix.insert(prompt[: len(pages_so_far) * ps], pages_so_far)
            self.stats.prefix_store_pages_hydrated += hydrated
            self.stats.prefix_store_tokens_hydrated += hydrated * ps
            if self.stats.pages_in_use > self.stats.peak_pages:
                self.stats.peak_pages = self.stats.pages_in_use
        return hydrated

    def _hydrate_demand(
        self, prompt: List[int], pages_so_far: List[int], n_chunks: int
    ) -> int:
        """Demand-driven variant of :meth:`_hydrate` for handoff
        admissions: the chain pages are *expected* to exist (a prefill
        worker just published them and pinned them against the TTL
        sweep), so instead of stopping when the free list runs dry the
        walk may reclaim room by evicting unpinned LRU cached prefixes —
        never preempting (the caller is mid-admission).  Pages already
        matched or freshly hydrated are pinned by transient raw refcount
        bumps (the :meth:`_cow_partial` pattern) so the eviction pass
        cannot reclaim the very chain being assembled.  Stops at the
        first store miss — the caller's fallback ladder replays the
        remainder, byte-identical."""
        ps = self.page_size
        keys = self._chunk_keys(prompt, n_chunks)
        like = self._page_like()
        pages_so_far = list(pages_so_far)
        hydrated = 0
        pinned: List[int] = []
        try:
            for p in pages_so_far:
                self._page_refs[p] += 1
                pinned.append(p)
            for j in range(len(pages_so_far), n_chunks):
                arrays = self.store.fetch(keys[j], like)
                if arrays is None:
                    break
                self._published.add(keys[j])
                pid = self._take_free_page()
                if pid is None and self.prefix is not None:
                    evicted = self.prefix.evict(
                        1, lambda p: self._page_refs[p]
                    )
                    for e in evicted:
                        self._decref(e)
                    self.stats.prefix_evictions += len(evicted)
                    pid = self._take_free_page()
                if pid is None:
                    break
                for name, arr in arrays.items():
                    self.cache[name] = self.cache[name].at[:, pid].set(arr)
                pages_so_far.append(pid)
                self._page_refs[pid] += 1
                pinned.append(pid)
                hydrated += 1
        finally:
            for p in pinned:
                self._page_refs[p] -= 1
        if hydrated:
            self.prefix.insert(prompt[: len(pages_so_far) * ps], pages_so_far)
            self.stats.prefix_store_pages_hydrated += hydrated
            self.stats.prefix_store_tokens_hydrated += hydrated * ps
            if self.stats.pages_in_use > self.stats.peak_pages:
                self.stats.peak_pages = self.stats.pages_in_use
        return hydrated

    # ------------------------------------------------------------- dispatch
    def push_table(self) -> None:
        """Sync the host page table to the device cache before a dispatch."""
        if self.cache_mode == "paged" and self._table_dirty:
            import jax.numpy as jnp

            with TraceAnnotation("serve.push_table"):
                self.cache["page_table"] = jnp.asarray(self._table)
            self._table_dirty = False

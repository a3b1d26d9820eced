"""On-device token sampling for the serving engine.

The seed engine round-tripped full ``(B, vocab)`` logits to host every
token and sampled with numpy.  Here sampling is fused into the same
jitted dispatch as the decode/prefill step, so only ``B`` int32 token
ids cross the host boundary per tick.

Determinism contract: each request owns a sampling *stream* (an integer
assigned at submit time) and each emitted token an integer *step* (the
number of tokens already generated for that request).  The per-token key
is ``fold_in(fold_in(PRNGKey(base_seed), stream), step)`` — independent
of batch placement, slot assignment, and dispatch scheduling, so the
fused single-dispatch engine and the legacy per-position-group engine
draw token-for-token identical samples.

Temperature sampling uses the Gumbel-max trick on max-subtracted logits:
``argmax((logits - max(logits)) / T + gumbel)`` is an exact draw from
``softmax(logits / T)`` and never exponentiates raw logits (the seed's
host sampler overflowed ``np.exp(logits / T)`` for large logits).

Stop-token handling is on-device too: the fused dispatches take per-row
``stops`` (stop token id, ``-1`` = none) and ``max_news`` vectors and
return a *done mask* next to the sampled ids.  The engine finalizes rows
straight off that mask — the host never re-derives the stop condition
from the token stream, and a finished row is parked (and its cache pages
freed in paged mode) before the next dispatch instead of being filtered
after the fact.

Each jit target is named by its dispatch kind (``serve_prefill``,
``serve_decode``, ``serve_verify``), so its program appears in a
profiler trace's ``XLA Modules`` line as ``jit_serve_decode`` and so on.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def sample_tokens(
    logits: jax.Array,  # (B, V) unnormalized
    temps: jax.Array,  # (B,) 0 = greedy
    streams: jax.Array,  # (B,) per-request sampling stream ids
    steps: jax.Array,  # (B,) tokens already generated per request
    *,
    base_seed: int,
) -> jax.Array:
    """Sample one token per row; greedy rows take a plain argmax."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def row_key(stream, step):
        key = jax.random.PRNGKey(base_seed)
        return jax.random.fold_in(jax.random.fold_in(key, stream), step)

    keys = jax.vmap(row_key)(streams, steps)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, lg.shape[-1:], jnp.float32))(keys)
    safe_t = jnp.where(temps > 0, temps, 1.0).astype(jnp.float32)
    z = (lg - jnp.max(lg, axis=-1, keepdims=True)) / safe_t[:, None] + gumbel
    sampled = jnp.argmax(z, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def done_mask(
    nxt: jax.Array,  # (B,) sampled token ids
    steps: jax.Array,  # (B,) tokens already generated (before this one)
    stops: jax.Array,  # (B,) stop token id, -1 = no stop token
    max_news: jax.Array,  # (B,) per-request new-token budget
) -> jax.Array:
    """Per-row request-finished mask, computed inside the dispatch."""
    hit_stop = jnp.logical_and(stops >= 0, nxt == stops)
    return jnp.logical_or(hit_stop, steps + 1 >= max_news)


def sample_tokens_chunk(
    logits: jax.Array,  # (B, T, V) unnormalized, one position per draft slot
    temps: jax.Array,  # (B,)
    streams: jax.Array,  # (B,)
    steps: jax.Array,  # (B,) tokens already generated BEFORE this chunk
    *,
    base_seed: int,
) -> jax.Array:
    """Per-position sampling for the speculative verify dispatch.

    Position ``t`` of row ``b`` draws from the stream key ``(streams[b],
    steps[b] + t)`` — the exact key sequential decoding would use for its
    ``steps[b] + t``-th token.  Because the key depends only on (stream,
    token index) and each position's draw is elementwise independent, the
    verified tokens are byte-identical to what ``T`` non-speculative
    decode dispatches would have sampled, for greedy AND temperature rows
    alike (no rejection-resampling correction is needed)."""
    B, T, V = logits.shape
    t_idx = jnp.arange(T, dtype=jnp.int32)
    flat = sample_tokens(
        logits.reshape(B * T, V),
        jnp.broadcast_to(temps[:, None], (B, T)).reshape(-1),
        jnp.broadcast_to(streams[:, None], (B, T)).reshape(-1),
        (steps[:, None] + t_idx[None, :]).reshape(-1),
        base_seed=base_seed,
    )
    return flat.reshape(B, T)


def make_verify_step(model, base_seed: int) -> Callable:
    """Build the speculative-verify jit target: one fused chunk-extend
    dispatch scores all ``k+1`` positions (last accepted token + ``k``
    draft tokens), samples the target token at every position, and
    applies the longest-consistent-run acceptance rule on device.

    Inputs per row: ``tokens[b] = [x0, d1 .. dm, pad...]`` where ``x0``
    is the last accepted token and ``d1..dm`` the proposer's drafts
    (``lengths[b] = 1 + m``; ``lengths[b] = 0`` parks the row).  The
    target token at position ``t`` is what non-speculative decode would
    emit after consuming ``tokens[b, :t+1]``; draft ``d_{t+1}`` is
    *consistent* iff it equals that target.  The row emits
    ``tgt[b, :n_emit[b]]``: the accepted run plus the bonus token from
    the first inconsistent (or last) position, truncated at the first
    position whose emitted token finishes the request (stop token or
    new-token budget) — sequential decode would never have sampled past
    it.  Rejected positions' KV stays in the cache past the rewound
    write frontier, where the causal mask excludes it, until the cache
    manager drops/overwrites it.

    Returns ``(tgt (B, T), n_emit (B,), done (B,), cache)``."""
    vocab = model.cfg.vocab_size

    def serve_verify(params, cache, tokens, offsets, lengths, temps, streams,
                     steps, stops, max_news):
        logits, cache = model.verify_chunk(params, cache, tokens, offsets, lengths)
        B, T = tokens.shape
        t_idx = jnp.arange(T, dtype=jnp.int32)
        tgt = sample_tokens_chunk(
            logits[:, :, :vocab], temps, streams, steps, base_seed=base_seed
        )
        # longest greedy-consistent run: draft t+1 survives iff it exists
        # (inside lengths) and every draft before it survived
        is_draft = t_idx[None, 1:] < lengths[:, None]
        match = jnp.logical_and(tokens[:, 1:] == tgt[:, :-1], is_draft)
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        emit_cap = n_acc + 1  # accepted run + the bonus target token
        # per-position done: emitting tgt[b, t] is the request's
        # (steps[b] + t + 1)-th token — budget and stop checks per slot
        hit_stop = jnp.logical_and(stops[:, None] >= 0, tgt == stops[:, None])
        over = steps[:, None] + t_idx[None, :] + 1 >= max_news[:, None]
        pos_done = jnp.logical_and(
            jnp.logical_or(hit_stop, over), t_idx[None, :] < emit_cap[:, None]
        )
        any_done = jnp.any(pos_done, axis=1)
        first_done = jnp.argmax(pos_done, axis=1)
        n_emit = jnp.where(any_done, first_done + 1, emit_cap)
        n_emit = jnp.where(lengths > 0, n_emit, 0).astype(jnp.int32)
        return tgt, n_emit, any_done, cache

    return serve_verify


def make_decode_step(model, base_seed: int, on_device: bool) -> Callable:
    """Build the engine's jit target: vectorized-position decode, with
    sampling + stop-token done mask fused on-device (default) or raw
    logits returned for the host-sampling fallback."""
    vocab = model.cfg.vocab_size

    if not on_device:

        def serve_decode_logits(params, cache, tokens, pos):
            logits, cache = model.decode_step(params, cache, tokens, pos)
            return logits[:, 0, :vocab], cache

        return serve_decode_logits

    def serve_decode(params, cache, tokens, pos, temps, streams, steps, stops, max_news):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        nxt = sample_tokens(
            logits[:, 0, :vocab], temps, streams, steps, base_seed=base_seed
        )
        return nxt, done_mask(nxt, steps, stops, max_news), cache

    return serve_decode


def _gather_rows(rows: Optional[int], cache, tokens, offsets, lengths, *per_row):
    """Gather the rows of a slot-indexed ``(B, ...)`` prefill dispatch that
    carry tokens into ``rows`` compact rows, inside the program.

    Returns ``(idx, cache, tokens, offsets, lengths, *per_row)``: ``idx``
    (``(rows,)``) holds the slot of each compact row, ``B`` past the last
    one; the cache's page table is gathered to those rows (the page pools
    are shared and addressed by physical page, so they pass through whole).
    A padding row reads slot ``B - 1``'s inputs but has length 0: its
    writes go to the out-of-bounds page and :func:`_to_slots` drops its
    outputs.  With ``rows`` None, or not below ``B``, nothing is gathered
    and ``idx`` is None."""
    B = tokens.shape[0]
    if rows is None or rows >= B:
        return (None, cache, tokens, offsets, lengths, *per_row)
    (idx,) = jnp.nonzero(lengths > 0, size=rows, fill_value=B)

    def take(a):
        return jnp.take(a, idx, axis=0, mode="clip")

    cache = dict(cache, page_table=take(cache["page_table"]))
    lengths = jnp.where(idx < B, take(lengths), 0)
    return (idx, cache, take(tokens), take(offsets), lengths, *map(take, per_row))


def _to_slots(idx, B: int, table, cache, *outs):
    """Scatter compact-row outputs back to ``(B, ...)`` slots (padding rows
    dropped, other slots zero) and put the full page table back."""
    if idx is None:
        return (*outs, cache)
    outs = [jnp.zeros((B,) + o.shape[1:], o.dtype).at[idx].set(o, mode="drop") for o in outs]
    return (*outs, dict(cache, page_table=table))


def make_prefill_step(model, base_seed: int, on_device: bool,
                      rows: Optional[int] = None) -> Callable:
    """Build the engine's fused chunked-prefill jit target (last-token
    logits sampled on-device with the done mask, or returned raw for the
    host fallback).

    Inputs and outputs are indexed by slot, ``(B, ...)``.  With ``rows``
    below ``B`` (a paged cache), the program gathers the at most ``rows``
    slots with ``lengths > 0`` and runs the model on ``(rows, T)`` tokens
    only (:func:`_gather_rows`); the caller never plans more rows than
    that into one dispatch."""
    vocab = model.cfg.vocab_size

    if not on_device:

        def serve_prefill_logits(params, cache, tokens, offsets, lengths):
            B, table = tokens.shape[0], cache.get("page_table")
            idx, cache, tokens, offsets, lengths = _gather_rows(
                rows, cache, tokens, offsets, lengths)
            logits, cache = model.prefill_chunk(params, cache, tokens, offsets, lengths)
            return _to_slots(idx, B, table, cache, logits[:, :vocab])

        return serve_prefill_logits

    def serve_prefill(params, cache, tokens, offsets, lengths, temps, streams, steps,
                      stops, max_news):
        B, table = tokens.shape[0], cache.get("page_table")
        idx, cache, tokens, offsets, lengths, temps, streams, steps, stops, max_news = (
            _gather_rows(rows, cache, tokens, offsets, lengths,
                          temps, streams, steps, stops, max_news))
        logits, cache = model.prefill_chunk(params, cache, tokens, offsets, lengths)
        nxt = sample_tokens(logits[:, :vocab], temps, streams, steps, base_seed=base_seed)
        return _to_slots(idx, B, table, cache, nxt, done_mask(nxt, steps, stops, max_news))

    return serve_prefill

"""Faults planted under the timed path, for the check's own tests and for
reading the check's upper limits on the chip (``tools/control.py
--fault``).  Each takes the engine before the run and breaks one of its
jitted dispatches."""

import numpy as np


def cache_unchanged(engine):
    """The decode dispatch returns the cache it was given: no decoded
    token's key and value is ever written."""
    decode = engine._decode

    def step(params, cache, *rest):
        nxt, done, _ = decode(params, cache, *rest)
        return nxt, done, cache

    engine._decode = step


def half_batch(engine):
    """The prefill dispatch leaves out the upper half of the rows it
    extends (the upper half of those with tokens, rounded up)."""
    prefill = engine._prefill

    def step(params, cache, tokens, offsets, lengths, *rest):
        lengths = np.array(lengths)
        rows = np.nonzero(lengths > 0)[0]
        lengths[rows[len(rows) // 2:]] = 0
        return prefill(params, cache, tokens, offsets, lengths, *rest)

    engine._prefill = step


def token_altered(engine):
    """The decode dispatch serves the next id in row 0 in place of the one
    it sampled."""
    decode = engine._decode
    vocab = engine.model.cfg.vocab_size

    def step(params, cache, *rest):
        nxt, done, cache = decode(params, cache, *rest)
        return nxt.at[0].set((nxt[0] + 1) % vocab), done, cache

    engine._decode = step


FAULTS = {f.__name__: f for f in (cache_unchanged, half_batch, token_altered)}

"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the program
finished is drawn from the seed: the longest request (prompt plus
output), one from every batch row in use (a request the row finished,
or, where it finished none, the one it was serving when the window
closed, with the tokens it had served; so a fault in any one row of the
batch shows), one whose prompt was resumed after a
copy-on-write partial-page stitch, one that was served from the prefix
cache, and more until the sample holds at least ``min_requests``
requests and ``min_tokens`` served tokens.  The plain reference runs
once over each prompt followed by its served tokens, and for every
served token reads the gap by which the reference's logit of that token
lies below the reference's best logit at that position.  The number
compared is the widest such gap.  Served tokens are greedy, so a correct
program serves the reference's best token up to rounding, and the gap
stays at the size of rounding; a wrong key, value, position, page or
sample puts it at the size of the logits' spread.

On the TPU a float32 matmul at default precision rounds its operands to
bfloat16, so the configured float32 program and the same program kept
in bfloat16 serve tokens whose gaps differ by less than twice (section 4
of ``PERF.md``): the gap cannot tell them apart.  A second number can:
the share of the key and value numbers in the cache the window wrote
that are bfloat16 numbers (:func:`kv_bf16_exact_share`).
"""

from __future__ import annotations

from typing import List

import numpy as np


def choose(records, seed: int, min_tokens: int, min_requests: int = 4) -> List:
    begun = [r for r in records if r.req is not None and r.req.output]
    done = [r for r in begun if r.done]
    if not done:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    order = [done[i] for i in rng.permutation(len(done))]
    picks = [max(order, key=lambda r: len(r.spec.prompt) + len(r.req.output))]
    rows = {p.row for p in picks}
    for r in order + [r for r in begun if not r.done]:
        if r.row not in rows:
            picks.append(r)
            rows.add(r.row)
    for want in (lambda r: r.hit_partial > 0, lambda r: r.hit_tokens > 0):
        extra = next((r for r in order if want(r) and r not in picks), None)
        if extra is not None:
            picks.append(extra)
    for r in order:
        if len(picks) >= min_requests and sum(len(p.req.output) for p in picks) >= min_tokens:
            break
        if r not in picks:
            picks.append(r)
    return picks


def served(records) -> List[tuple]:
    """Host copies (prompt, served tokens, admission facts) of the sample,
    so the program's state can be freed before the reference runs."""
    return [(list(r.spec.prompt), [int(t) for t in r.req.output],
             {"uid": r.spec.uid, "row": r.row, "hit_tokens": r.hit_tokens, "hit_partial": r.hit_partial})
            for r in records]


def kv_bf16_exact_share(engine, pages: int = 64) -> float:
    """Share of the nonzero key and value numbers in the last ``pages``
    pages the pool handed out that a bfloat16 could hold exactly (the low
    16 bits of their float32 form are zero).  A cache computed and kept
    in float32 holds about 2**-16 of them; one kept in bfloat16, all."""
    import jax
    import jax.numpy as jnp

    mgr = engine.cache_mgr
    k = max(1, min(pages, engine.stats.peak_pages))  # the free list hands out the top ids first

    @jax.jit
    def count(x):
        x = x.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        nz = x != 0
        return jnp.sum(nz & ((bits & 0xFFFF) == 0)), jnp.sum(nz)

    exact = total = 0
    for name, leaf in mgr.cache.items():
        if name.endswith("_pages"):
            e, t = count(leaf[:, mgr.n_pages - k:])
            exact, total = exact + int(e), total + int(t)
    return exact / total if total else 1.0


def widest_gap(reference, sample) -> dict:
    """Widest gap over every served token of the sample, with its parts."""
    worst, tokens, per, gaps = 0.0, 0, [], []
    for prompt, out, facts in sample:
        seq = prompt + out[:-1]
        pos = np.arange(len(prompt) - 1, len(seq))
        gap = reference.gaps_at(seq, pos, out)
        g = float(gap.max())
        per.append({**facts, "prompt": len(prompt), "served": len(out), "gap": g})
        gaps.append(gap)
        worst = max(worst, g)
        tokens += len(out)
    gaps = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"max_logit_gap": worst, "tokens": tokens, "requests": per,
            "mean_logit_gap": float(gaps.mean()), "mismatch_share": float((gaps > 0).mean()),
            "gap_p99": float(np.percentile(gaps, 99))}

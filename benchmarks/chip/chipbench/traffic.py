"""One general traffic generator, driven by a mix file
(``traffic/<name>.json``).

Every length, gap and count is drawn by stratified sampling: ``n`` draws
take the distribution's quantiles at ``(i + 0.5) / n``, and ``--seed``
only permutes them and draws the token ids.  So every seed gets the same
multiset of sizes and gaps in another order, and runs with different
seeds do the same amount of work.  An offline backlog is the exception:
there the order itself changes the work, so the mix fixes it and the
seed draws only the ids.  Token ids are drawn from the whole
vocabulary.  Every request is greedy (temperature 0) and has no stop
token, so it emits exactly its ``max_new_tokens`` unless the cache length
``max_len`` ends it first.

Mix keys (all lengths in tokens, times in seconds):

- ``mode``: ``open_loop`` (requests fall due on a schedule fixed before
  the window, whatever the server does) or ``offline`` (a backlog of
  ``backlog`` x ``max_batch`` requests is kept queued, taken in turn from
  a pool of ``pool`` requests made in blocks of ``block``, whose sizes
  ``order_seed`` orders).
- ``rate_per_s``: open loop; arrivals (of requests, or of sessions when
  ``sessions`` is given) per second, as exponential gaps.
- ``prompt``, ``max_new_tokens``: distributions (see :func:`draw`).
- ``sessions``: multi-turn sessions replayed from the seed:
  ``system_prompts`` (``count``, ``length`` and Zipf ``popularity``),
  ``turns``, ``user`` and ``assistant`` message lengths, ``think_s``.
  Turn ``k`` sends the session's system prompt, every earlier user
  message and recorded assistant turn, and its own user message; it
  falls due ``think_s`` after turn ``k - 1``.  A session ends early
  where the next turn would not fit ``max_len``.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np

_NORMAL = NormalDist()


@dataclass
class RequestSpec:
    uid: str
    due_s: float  # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int
    session: int = -1
    turn: int = 0
    tags: dict = field(default_factory=dict)


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "uniform":
        x = dist["low"] + u * (dist["high"] + 1 - dist["low"])
        return float(min(math.floor(x), dist["high"]))
    elif kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    elif kind == "geometric":
        p = 1.0 / dist["mean"]
        x = math.ceil(math.log1p(-u) / math.log1p(-p)) if p < 1 else 1
        x = max(1, x)
    elif kind == "constant":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return float(min(max(x, dist.get("low", x)), dist.get("high", x)))


def draw(dist: dict, n: int, rng: np.random.Generator, integer: bool = True) -> np.ndarray:
    """``n`` stratified draws of ``dist``, in an order permuted by ``rng``."""
    vals = np.array([_quantile(dist, (i + 0.5) / n) for i in range(n)], np.float64)
    if integer:
        vals = np.rint(vals).astype(np.int64)
    return vals[rng.permutation(n)]


def _arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """``floor(rate * seconds)`` arrival times in ``[0, seconds)``:
    exponential gaps, stratified and permuted, scaled to fill the window."""
    n = max(1, int(rate * seconds))
    gaps = draw({"dist": "exponential", "mean": 1.0 / rate}, n, rng, integer=False)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def _ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(n)).tolist()


def _zipf_counts(n_items: int, total: int, s: float) -> np.ndarray:
    """``total`` uses spread over ``n_items`` by Zipf popularity (largest
    remainder, so the counts sum to ``total`` exactly)."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    share = w / w.sum() * total
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[: total - counts.sum()]:
        counts[i] += 1
    return counts


def generate(mix: dict, *, seed: int, seconds: float, vocab: int, max_len: int) -> List[RequestSpec]:
    """The window's requests, sorted by due time."""
    rng = np.random.default_rng(seed)
    if mix["mode"] == "offline":
        return _offline(mix, rng, vocab, max_len)
    if "sessions" in mix:
        return _sessions(mix, rng, seconds, vocab, max_len)
    return _independent(mix, rng, _arrivals(mix["rate_per_s"], seconds, rng), vocab, max_len)


def _independent(mix, rng, due, vocab, max_len) -> List[RequestSpec]:
    n = len(due)
    plen = draw(mix["prompt"], n, rng)
    new = draw(mix["max_new_tokens"], n, rng)
    out = []
    for i in range(n):
        p = int(min(plen[i], max_len - 2))
        out.append(RequestSpec(f"r{i}", float(due[i]), _ids(rng, p, vocab),
                               int(min(new[i], max_len - 1 - p))))
    return out


def _offline(mix, rng, vocab, max_len) -> List[RequestSpec]:
    """The backlog's pool, in blocks of ``block`` requests: each block's
    prompt and output lengths are stratified, and paired and ordered by
    the mix's ``order_seed``, not by ``--seed``, which draws only the ids.
    In a backlog, which request refills which row, beside which others,
    sets how many padded prefill dispatches a window makes; so sizes in
    an order of the seed's would make each seed do other work."""
    k = mix["block"]
    if mix["pool"] % k:
        raise ValueError(f"pool {mix['pool']} is not a multiple of block {k}")
    order = np.random.default_rng(mix["order_seed"])
    out = []
    for _ in range(mix["pool"] // k):
        plen, new = draw(mix["prompt"], k, order), draw(mix["max_new_tokens"], k, order)
        for j in range(k):
            p = int(min(plen[j], max_len - 2))
            out.append(RequestSpec(f"r{len(out)}", 0.0, _ids(rng, p, vocab),
                                   int(min(new[j], max_len - 1 - p))))
    return out


def _sessions(mix, rng, seconds, vocab, max_len) -> List[RequestSpec]:
    ses = mix["sessions"]
    starts = _arrivals(mix["rate_per_s"], seconds, rng)
    n_ses = len(starts)
    sp = ses["system_prompts"]
    sys_len = draw(sp["length"], sp["count"], rng)
    systems = [_ids(rng, n, vocab) for n in sys_len]
    which = np.repeat(np.arange(sp["count"]), _zipf_counts(sp["count"], n_ses, sp["popularity"]["zipf_s"]))
    which = which[rng.permutation(n_ses)]
    turns = draw(ses["turns"], n_ses, rng)
    n_turns = int(turns.sum())
    user = draw(ses["user"], n_turns, rng)
    asst = draw(ses["assistant"], n_turns, rng)
    think = draw(ses["think_s"], n_turns, rng, integer=False)
    new = draw(mix["max_new_tokens"], n_turns, rng)
    out, k = [], 0
    for s in range(n_ses):
        prompt = list(systems[which[s]])
        t = float(starts[s])
        for turn in range(int(turns[s])):
            i, k = k, k + 1
            prompt = prompt + _ids(rng, user[i], vocab)
            if t >= seconds or len(prompt) + int(new[i]) > max_len - 1:
                k += int(turns[s]) - turn - 1  # the session ends; keep the draws aligned
                break
            out.append(RequestSpec(f"s{s}t{turn}", t, prompt, int(new[i]), session=s,
                                   turn=turn, tags={"system": int(which[s])}))
            prompt = prompt + _ids(rng, asst[i], vocab)
            t += float(think[i])
    out.sort(key=lambda r: r.due_s)
    return out

"""The traffic generator: seeds repeat, sizes follow the stated
distributions, and every seed gets the same sizes in another order."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import traffic  # noqa: E402

# mixes of cells not yet in the benchmark (PERF.md, Open questions), so
# that the generator's plain open-loop arrivals are tested too
LATER = {
    "short_unshared": {
        "mode": "open_loop", "rate_per_s": 4.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8, "low": 32, "high": 1536},
        "max_new_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8, "low": 16, "high": 512}},
    "long_prompts": {
        "mode": "open_loop", "rate_per_s": 1.12, "engine": {"max_len": 4096},
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.6, "low": 256, "high": 3584},
        "max_new_tokens": {"dist": "uniform", "low": 16, "high": 64}},
    "chat_sessions": {"mode": "open_loop", "about": "Multi-turn chat replayed from the seed: sessions arrive as Poisson; each turn resends the session's system prompt and history", "rate_per_s": 1.0, "engine": {"max_batch": 32, "total_pages": 4096}, "sessions": {"system_prompts": {"count": 16, "length": {"dist": "uniform", "low": 512, "high": 1024}, "popularity": {"zipf_s": 1.0}}, "turns": {"dist": "geometric", "mean": 3, "low": 1, "high": 8}, "user": {"dist": "lognormal", "median": 64, "sigma": 1.0, "low": 8, "high": 512}, "assistant": {"dist": "lognormal", "median": 128, "sigma": 0.8, "low": 16, "high": 512}, "think_s": {"dist": "exponential", "mean": 4.0}}, "max_new_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8, "low": 16, "high": 512}},
}
MIXES = {**{n[:-5]: traffic.load_mix(os.path.join(BENCH, "traffic", n))
            for n in sorted(os.listdir(os.path.join(BENCH, "traffic")))}, **LATER}
BIG_SEED = 2**31 + 977


def gen(name, seed, seconds=30.0, vocab=49152, max_len=2048):
    mix = dict(MIXES[name])
    if mix["mode"] == "offline":
        mix["pool"] = 512
    max_len = mix.get("engine", {}).get("max_len", max_len)
    return traffic.generate(mix, seed=seed, seconds=seconds, vocab=vocab, max_len=max_len)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    a, b = gen(name, BIG_SEED), gen(name, BIG_SEED)
    assert [(r.uid, r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
           [(r.uid, r.due_s, r.prompt, r.max_new_tokens) for r in b]
    c = gen(name, BIG_SEED + 1)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", ["short_unshared", "long_prompts", "offline_decode"])
def test_seeds_share_the_sizes(name):
    a, b = gen(name, 1), gen(name, BIG_SEED)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    # the gaps between arrivals, and from the last arrival to the window's end
    gaps = [np.sort(np.diff([r.due_s for r in x] + [30.0])) for x in (a, b)]
    assert gaps[0] == pytest.approx(gaps[1])


@pytest.mark.parametrize("name", ["short_unshared", "long_prompts", "offline_decode"])
def test_lengths_follow_the_mix(name):
    mix, reqs = MIXES[name], gen(name, 5)
    for key, got in (("prompt", [len(r.prompt) for r in reqs]),
                     ("max_new_tokens", [r.max_new_tokens for r in reqs])):
        d = mix[key]
        assert min(got) >= d["low"] and max(got) <= d["high"]
        if d["dist"] == "lognormal":
            assert np.median(got) == pytest.approx(d["median"], rel=0.05)
            # the 84th percentile sits one sigma above the median
            hi = np.exp(np.log(d["median"]) + d["sigma"])
            if hi < d["high"]:
                assert np.percentile(got, 84.13) == pytest.approx(hi, rel=0.08)
        else:
            assert np.median(got) == pytest.approx((d["low"] + d["high"]) / 2, rel=0.05)


@pytest.mark.parametrize("name", ["short_unshared", "long_prompts"])
def test_open_loop_arrivals(name):
    mix, seconds = MIXES[name], 30.0
    due = np.array([r.due_s for r in gen(name, 9, seconds)])
    assert len(due) == int(mix["rate_per_s"] * seconds)
    assert due.min() == 0.0 and due.max() < seconds and np.all(np.diff(due) >= 0)
    gaps = np.diff(due)
    # exponential gaps: standard deviation about the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.25)


def test_token_ids_span_the_vocabulary():
    ids = np.concatenate([r.prompt for r in gen("short_unshared", 3)])
    assert ids.min() >= 0 and ids.max() < 49152
    assert ids.max() > 49152 * 0.99 and ids.min() < 49152 * 0.01


def test_sessions_resend_their_history():
    reqs = gen("chat_sessions", BIG_SEED, seconds=60.0)
    mix = MIXES["chat_sessions"]["sessions"]
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
        assert len(r.prompt) + r.max_new_tokens <= 2047
    multi = [s for s in by_session.values() if len(s) > 1]
    assert multi, "no session with more than one turn"
    for turns in multi:
        turns.sort(key=lambda r: r.turn)
        for prev, nxt in zip(turns, turns[1:]):
            assert nxt.prompt[: len(prev.prompt)] == prev.prompt
            assert nxt.due_s > prev.due_s
    # Zipf popularity: the most popular system prompt opens the most sessions
    firsts = [s[0].tags["system"] for s in by_session.values()]
    counts = np.bincount(firsts, minlength=mix["system_prompts"]["count"])
    assert counts[0] == counts.max()
    # a first turn is a 512-1024 token system prompt and an 8-512 token message
    assert all(512 + 8 <= len(s[0].prompt) <= 1024 + 512 for s in by_session.values())


def test_stratified_draws_hit_the_quantiles():
    rng = np.random.default_rng(0)
    d = {"dist": "lognormal", "median": 100, "sigma": 0.5, "low": 1, "high": 10**6}
    x = traffic.draw(d, 1001, rng, integer=False)
    assert np.median(x) == pytest.approx(100, rel=1e-9)
    g = traffic.draw({"dist": "geometric", "mean": 3, "low": 1, "high": 8}, 999, rng)
    assert g.min() == 1 and g.max() <= 8 and 2.5 < g.mean() < 3.1


def test_offline_order_is_the_mix_not_the_seed():
    """A backlog's sizes come in the same order for every seed (the order
    sets the padded prefill work); the seed draws only the ids.  Each
    block of the pool holds the same stratified sizes."""
    a, b = gen("offline_decode", 1), gen("offline_decode", BIG_SEED)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == \
           [(len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    k = MIXES["offline_decode"]["block"]
    blocks = [sorted(len(r.prompt) for r in a[i:i + k]) for i in range(0, len(a), k)]
    assert len(blocks) > 1 and all(x == blocks[0] for x in blocks)
    mix = dict(MIXES["offline_decode"], order_seed=MIXES["offline_decode"]["order_seed"] + 1)
    mix["pool"] = len(a)
    c = traffic.generate(mix, seed=1, seconds=30.0, vocab=49152, max_len=2048)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]

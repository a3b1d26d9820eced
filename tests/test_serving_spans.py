"""The serving engine's profiler spans (``serve.*``).

A tiny paged engine with fused prefill serves a few requests under
``jax.profiler.trace``; the host plane, read back with the benchmark's
loader (``benchmarks/chip/chipbench/program_spans.py``), holds one
``serve.step`` per ``step()`` call, every dispatch, read-back, page pass
and accept inside a step, dispatch spans that agree with the engine's
counters, and only the spans ``docs/serving.md`` documents."""

import os
import re
import sys

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.core.storage import ObjectStore
from repro.models import Model
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefix_store import PrefixStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import program_spans  # noqa: E402

COUNTERS = ("prefill_dispatches", "decode_dispatches", "prompt_tokens_ingested",
            "cow_copies", "cow_partial_stitches")


def documented_spans():
    """The span names of the Tracing table in ``docs/serving.md``."""
    with open(os.path.join(ROOT, "docs", "serving.md"), encoding="utf-8") as f:
        text = f.read()
    section = text.split("## Tracing", 1)[1].split("\n## ", 1)[0]
    return set(re.findall(r"^\| `(serve\.[a-z_.]+)` \|", section, re.M))


def requests():
    # a shares its first two pages with c, and ends inside d's last
    # page, so admissions stitch whole pages and copy a partial one
    a = list(range(1, 21))
    return [
        Request(uid="a", prompt=a, max_new_tokens=3),
        Request(uid="b", prompt=[7, 8, 9], max_new_tokens=5),
        Request(uid="c", prompt=a[:16], max_new_tokens=4),
        Request(uid="d", prompt=a[:12] + [99, 98], max_new_tokens=2),
        Request(uid="e", prompt=[5] * 11, max_new_tokens=6),
    ]


@pytest.fixture(scope="module", params=["plain", "ngram", "store"])
def traced(request, tmp_path_factory):
    """``ngram`` verifies drafts; with ``store`` the warm-up engine publishes
    the prompts' pages to a prefix store and the traced one hydrates them."""
    cfg = reduced(get_arch("ds-paper-100m"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = None
    if request.param == "store":
        root = tmp_path_factory.mktemp("store")
        store = PrefixStore(ObjectStore(str(root)), "ns")

    def engine():
        return ServeEngine(model, params, max_batch=2, max_len=64, prefill_chunk=8,
                           cache_mode="paged", page_size=8, total_pages=32,
                           speculative="ngram" if request.param == "ngram" else "off",
                           prefix_store=store)

    warm = engine()  # compiles outside the trace
    warm.submit(requests())
    warm.run_to_completion()
    eng = engine()
    before = {k: getattr(eng.stats, k) for k in COUNTERS}
    log_dir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    calls = 0
    with jax.profiler.trace(log_dir):
        eng.submit(requests())
        while eng.pending or eng.scheduler.has_active():
            eng.step()
            calls += 1
    delta = {k: getattr(eng.stats, k) - before[k] for k in COUNTERS}
    spans = program_spans.read_spans(program_spans.xplane(log_dir))
    return spans, calls, delta, request.param


def named(spans, *names):
    return [s for s in spans if s.name in names]


def test_one_step_span_per_step_call(traced):
    spans, calls, _, _ = traced
    steps = named(spans, "serve.step")
    assert calls > 0 and len(steps) == calls
    assert [s.args["tick"] for s in steps] == list(range(1, calls + 1))


def test_tick_activities_nest_inside_a_step(traced):
    spans, _, _, _ = traced
    steps = [(s.start, s.end) for s in named(spans, "serve.step")]
    inner = [s for s in spans if s.name.startswith("serve.dispatch.")
             or s.name in ("serve.readback", "serve.pages", "serve.accept")]
    assert {s.name for s in inner} >= {"serve.readback", "serve.pages", "serve.accept"}
    for s in inner:
        assert any(a <= s.start and s.end <= b for a, b in steps), s


def test_dispatch_spans_match_the_engine_counters(traced):
    spans, _, delta, mode = traced
    prefill = named(spans, "serve.dispatch.prefill")
    decode = named(spans, "serve.dispatch.decode", "serve.dispatch.verify")
    assert len(prefill) == delta["prefill_dispatches"] > 0
    # a verify dispatch counts as the tick's decode dispatch
    assert len(decode) == delta["decode_dispatches"] > 0
    assert {s.name for s in decode} == {"serve.dispatch.verify" if mode == "ngram"
                                        else "serve.dispatch.decode"}
    assert len(named(spans, "serve.pages.cow")) == delta["cow_copies"] + delta["cow_partial_stitches"]
    assert len(named(spans, "serve.pages.cow")) > 0
    for s in prefill + decode:
        assert 0 < s.args["rows"] <= 2 and s.args["tokens"] >= s.args["rows"]


def test_prefill_tokens_are_the_prompt_tokens_ingested(traced):
    spans, _, delta, _ = traced
    prefill = named(spans, "serve.dispatch.prefill")
    assert sum(s.args["tokens"] for s in prefill) == delta["prompt_tokens_ingested"] > 0
    assert all(s.args["tokens"] <= s.args["rows"] * 8 for s in prefill)


def test_every_span_is_documented(traced):
    spans, _, _, mode = traced
    documented = documented_spans()
    assert len(documented) == 17
    assert {s.name for s in spans} <= documented
    admitted = named(spans, "serve.admit.request")
    assert sorted(s.args["uid"] for s in admitted) == ["a", "b", "c", "d", "e"]
    if mode == "ngram":
        assert named(spans, "serve.propose")
    if mode == "store":
        assert named(spans, "serve.store.hydrate") and named(spans, "serve.store.publish")


def test_prefill_slots_are_the_rows_the_program_ran(traced):
    # max_batch 2 at an 8-token chunk: every row runs (prefill_rows 2)
    spans, _, _, _ = traced
    prefill = named(spans, "serve.dispatch.prefill")
    assert prefill and all(s.args["slots"] == 2 * 8 for s in prefill)

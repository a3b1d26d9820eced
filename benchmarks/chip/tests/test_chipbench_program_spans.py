"""The reduction of the engine's ``serve.*`` spans: host gap per dispatch,
prefill occupancy and idle time by innermost span, on events worked by
hand, and ``None`` where a run has no trace or its trace no program span."""

import os
from types import SimpleNamespace

import pytest

import chipbench_tiny  # noqa: F401 - puts the harness on the path
from chipbench import cells, program_spans, tracing
from chipbench.program_spans import Span

MS = 1_000_000  # ns


def hand_spans():
    """Two ticks: a prefill dispatch of 40 prompt tokens over 2 rows in the
    first (0-10 ms), a decode dispatch of 3 rows in the second (12-20 ms),
    and 2 ms outside the engine between them."""
    return [
        Span(0, 10 * MS, "serve.step", {"tick": 1}),
        Span(0, 1 * MS, "serve.admit"),
        Span(1 * MS, 2 * MS, "serve.inputs"),
        Span(2 * MS, 3 * MS, "serve.dispatch.prefill", {"rows": 2, "tokens": 40}),
        Span(3 * MS, 8 * MS, "serve.readback", {"kind": "prefill"}),
        Span(8 * MS, 9 * MS, "serve.accept"),
        Span(12 * MS, 20 * MS, "serve.step", {"tick": 2}),
        Span(12 * MS, 13 * MS, "serve.pages"),
        Span(14 * MS, 15 * MS, "serve.dispatch.decode", {"rows": 3, "tokens": 3}),
        Span(15 * MS, 19 * MS, "serve.readback", {"kind": "decode"}),
    ]


# the device runs 2.5-7.5 ms (two overlapping ops) and 15.5-18.5 ms
OPS = [(2.5 * MS, 6 * MS, "%fusion.1"), (5 * MS, 7.5 * MS, "%fusion.2"),
       (15.5 * MS, 18.5 * MS, "%fusion.3")]


def hand_trace():
    return program_spans.ProgramTrace(hand_spans(), tracing.union(OPS), 0, 20 * MS)


def test_host_gap_is_idle_inside_steps_per_dispatch():
    # step 1 idles 10 - 5 ms, step 2 8 - 3 ms: 10 ms over 2 dispatches
    assert program_spans.host_gap_ns(hand_trace()) == pytest.approx(5 * MS)


def test_prefill_occupancy_is_tokens_over_slots():
    # 40 tokens in one (4 x 16) dispatch
    assert program_spans.occupancy(hand_trace(), 4, 16) == pytest.approx(62.5)


def test_innermost_pieces():
    spans = [Span(0, 10, "serve.step"), Span(2, 5, "serve.pages"),
             Span(3, 4, "serve.pages.cow"), Span(6, 12, "serve.accept")]
    # the accept span outlives its step and is cut at the step's end
    assert program_spans.innermost(spans) == [
        (0, 2, "serve.step"), (2, 3, "serve.pages"), (3, 4, "serve.pages.cow"),
        (4, 5, "serve.pages"), (5, 6, "serve.step"), (6, 10, "serve.accept")]


def test_idle_by_innermost_span():
    got = dict(program_spans.idle_by_span(hand_trace()))
    want = {"serve.admit": 1, "serve.inputs": 1, "serve.dispatch.prefill": 0.5,
            "serve.readback": 1.5, "serve.accept": 1, "serve.step": 3,
            program_spans.OUTSIDE: 2, "serve.pages": 1, "serve.dispatch.decode": 1}
    assert got == pytest.approx({k: v / 1e3 for k, v in want.items()})
    # all of the traced part's idle time, nothing else
    assert sum(got.values()) == pytest.approx((20 - 8) / 1e3)


def fake_run(trace=True, name="cell-a"):
    engine = {"max_batch": 4, "prefill_chunk": 16}
    return SimpleNamespace(trace=object() if trace else None,
                           cell=SimpleNamespace(name=name, engine=engine))


def plant(monkeypatch, tmp_path, name, spans):
    """A traced run's directory whose trace holds ``spans`` and ``OPS``."""
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    prof = tmp_path / ".bench_trace" / name / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(b"")
    reads = []

    def read_spans(path):
        reads.append(path)
        return spans

    monkeypatch.setattr(program_spans, "read_spans", read_spans)
    monkeypatch.setattr(tracing, "load", lambda d: tracing.Events(ops=list(OPS)))
    return reads


def test_metrics_of_a_run_read_its_trace_once(monkeypatch, tmp_path):
    reads = plant(monkeypatch, tmp_path, "cell-a", hand_spans())
    run = fake_run()
    assert program_spans.host_gap_ms(run) == pytest.approx(5.0)
    assert program_spans.prefill_occupancy(run) == pytest.approx(62.5)
    assert len(reads) == 1 and reads[0].endswith(os.path.join("1", "host.xplane.pb"))


@pytest.mark.parametrize("metric", [program_spans.host_gap_ms, program_spans.prefill_occupancy])
def test_none_without_trace_or_program_spans(monkeypatch, tmp_path, metric):
    assert metric(fake_run(trace=False)) is None
    # a program without spans: the trace holds device ops and no serve.*
    plant(monkeypatch, tmp_path, "cell-b", [])
    assert metric(fake_run(name="cell-b")) is None
    # no trace file at all under the run's directory
    assert metric(fake_run(name="cell-c")) is None

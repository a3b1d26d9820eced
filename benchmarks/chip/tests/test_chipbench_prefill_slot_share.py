"""``prefill_slot_share``: prompt tokens over the slots the prefill
programs ran, from the ``serve.dispatch.prefill`` spans' ``tokens`` and
``slots``, on spans worked by hand; ``None`` where no span carries
``slots`` (a program that runs every row) or the run has no trace."""

from types import SimpleNamespace

import pytest

import chipbench_tiny  # noqa: F401 - puts the harness on the path
from chipbench import cells, program_spans, tracing
from chipbench.program_spans import Span

MS = 1_000_000  # ns
read = cells.reader("prefill_slot_share.offline")
share = read.__globals__["share"]


def spans(with_slots=True):
    """Two prefill dispatches of (8 x 64) slots carrying 53 and 139 prompt
    tokens, and a decode dispatch, which never counts."""
    extra = (lambda n: {"slots": n}) if with_slots else (lambda n: {})
    return [
        Span(0, 10 * MS, "serve.step", {"tick": 1}),
        Span(1 * MS, 2 * MS, "serve.dispatch.prefill", {"rows": 1, "tokens": 53, **extra(512)}),
        Span(3 * MS, 4 * MS, "serve.dispatch.prefill", {"rows": 3, "tokens": 139, **extra(512)}),
        Span(5 * MS, 6 * MS, "serve.dispatch.decode", {"rows": 64, "tokens": 64}),
    ]


def test_tokens_over_slots_run():
    assert share(spans()) == pytest.approx(100.0 * (53 + 139) / 1024)


def test_none_where_no_span_has_slots():
    assert share(spans(with_slots=False)) is None
    assert share([]) is None


def fake_run(trace=True, name="cell-a"):
    return SimpleNamespace(trace=object() if trace else None,
                           cell=SimpleNamespace(name=name, engine={}))


def test_read_from_a_traced_run(monkeypatch, tmp_path):
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    for name in ("cell-a", "cell-b"):
        prof = tmp_path / ".bench_trace" / name / "plugins" / "profile" / "1"
        prof.mkdir(parents=True)
        (prof / f"{name}.xplane.pb").write_bytes(b"")
    by_file = {"cell-a": spans(), "cell-b": spans(with_slots=False)}
    monkeypatch.setattr(program_spans, "read_spans",
                        lambda path: by_file[path.rsplit("/", 1)[1].split(".")[0]])
    monkeypatch.setattr(tracing, "load", lambda d: tracing.Events(ops=[(0, 5 * MS, "%f")]))
    assert read(fake_run()) == pytest.approx(18.75)
    assert read(fake_run(name="cell-b")) is None  # the spans of a program without slots
    assert read(fake_run(trace=False)) is None
    assert read(fake_run(name="cell-c")) is None  # no trace file

"""Executor: prompt tokens the prefill dispatches carried over the token
slots their programs ran (the ``tokens`` and ``slots`` arguments of the
``serve.dispatch.prefill`` spans), in percent, in the traced part.

A program whose prefill spans carry no ``slots`` reads ``None``, as does
a run without a trace."""

from chipbench import program_spans

SPAN = "serve.dispatch.prefill"


def share(spans):
    """Sum of ``tokens`` over sum of ``slots`` of the prefill dispatch
    spans that carry ``slots``, in percent; ``None`` where none does."""
    ran = [s.args for s in spans if s.name == SPAN and "slots" in s.args]
    slots = sum(a["slots"] for a in ran)
    if not slots:
        return None
    return 100.0 * sum(a["tokens"] for a in ran) / slots


def read(run):
    pt = program_spans.of_run(run)
    return share(pt.spans) if pt is not None else None

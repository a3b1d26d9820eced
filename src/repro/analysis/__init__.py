"""``dslint`` — the serving stack's invariant linter.

Nine PRs in, this repro's coordination disciplines (retry-wrapped
store/queue ops, durable-before-ack ordering, byte-determinism of
engine ticks, counter plumbing from ``EngineStats`` through
``snapshot()`` -> RESULTS -> docs) lived only in people's
heads and scattered regression tests — and several past bugs (the PR 5
self-preemption live-lock, PR 8's unretried store ops, the truncated
npz blob crash) were exactly violations of those unwritten rules.
This package makes them machine-checkable: an AST walk over every file
under ``src/repro/`` enforcing ~7 codebase-specific rules, each
grounded in a real past bug class (see ``docs/analysis.md`` for the
catalog and the motivating bug behind each rule).

Run it::

    PYTHONPATH=src python -m repro.analysis            # full tree
    PYTHONPATH=src python -m repro.analysis --changed  # inner loop
    PYTHONPATH=src python -m repro.analysis --list-rules

Tier-1 runs the full tree via ``tests/test_analysis.py`` — a new PR
that drifts from any discipline fails the suite, not a review.

Suppression is explicit and audited:

- inline pragma: ``# dslint: disable=R1(reason)`` on the offending
  line or on the enclosing ``def``/``class`` header;
- the committed baseline (``baseline.json`` next to this file) for
  grandfathered findings, each entry carrying a written justification.

An empty reason or justification is itself a finding (rule R0), so
nothing can be silenced without saying why.
"""

from repro.analysis.engine import (  # noqa: F401
    Finding,
    Project,
    Report,
    run_analysis,
)
from repro.analysis.rules import ALL_RULES  # noqa: F401

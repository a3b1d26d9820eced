"""R4 — counter-registry drift.

Motivating bugs: three times in PRs 6-9 a new ``EngineStats`` counter
was plumbed into some-but-not-all of its consumers — present in
``snapshot()`` but missing from the ``docs/serving.md`` counter tables
— and the drift was only caught by reading diffs side by side.  The
registries can never silently diverge again:

1. every public ``EngineStats`` field must be covered by
   ``snapshot()`` (the dynamic ``fields(self)`` comprehension covers
   all of them; an explicit-dict rewrite must name each one);
2. every public field must appear (backticked) in ``docs/serving.md``.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from repro.analysis.rules.common import Rule

TYPES_PATH = "src/repro/serving/types.py"
DOCS_PATH = "docs/serving.md"


def _engine_stats_fields(module) -> List[ast.AnnAssign]:
    for node in ast.walk(module.tree):
        if isinstance(node, ast.ClassDef) and node.name == "EngineStats":
            return [
                stmt for stmt in node.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            ]
    return []


def _snapshot_func(module) -> Optional[ast.FunctionDef]:
    for node in ast.walk(module.tree):
        if isinstance(node, ast.ClassDef) and node.name == "EngineStats":
            for stmt in node.body:
                if isinstance(stmt, ast.FunctionDef) and stmt.name == "snapshot":
                    return stmt
    return None


def _snapshot_is_dynamic(snap: ast.FunctionDef) -> bool:
    """True when snapshot() iterates ``fields(self)`` — the dynamic form
    that covers every field by construction."""
    for node in ast.walk(snap):
        if isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Name) and fn.id == "fields") or (
                isinstance(fn, ast.Attribute) and fn.attr == "fields"
            ):
                return True
    return False


def _snapshot_names(snap: ast.FunctionDef) -> Set[str]:
    names: Set[str] = set()
    for node in ast.walk(snap):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            names.add(node.value)
        if isinstance(node, ast.Attribute):
            names.add(node.attr)
    return names


class CounterRegistryRule(Rule):
    rule_id = "R4"
    title = ("EngineStats fields, snapshot() and the docs counter tables "
             "must agree (no silent counter drift)")

    def check_project(self, project):
        types_mod = project.module(TYPES_PATH)
        if types_mod is None:
            return
        fields = _engine_stats_fields(types_mod)
        public = [f for f in fields if not f.target.id.startswith("_")]

        # 1. snapshot() coverage
        snap = _snapshot_func(types_mod)
        if snap is None:
            yield types_mod.finding(
                "R4", 1, "EngineStats has no snapshot() method — RESULTS.json "
                "consumers read it")
        elif not _snapshot_is_dynamic(snap):
            named = _snapshot_names(snap)
            for f in public:
                if f.target.id not in named:
                    yield types_mod.finding(
                        "R4", f,
                        f"counter {f.target.id!r} is not covered by "
                        "snapshot() — it would silently vanish from "
                        "RESULTS.json",
                    )

        # 2. docs coverage (backticked mention anywhere in serving.md)
        docs = project.read_text(DOCS_PATH)
        if docs is not None:
            for f in public:
                if f"`{f.target.id}`" not in docs:
                    yield types_mod.finding(
                        "R4", f,
                        f"counter {f.target.id!r} is in snapshot() but "
                        f"missing from the {DOCS_PATH} counter tables — "
                        "operators cannot interpret an undocumented "
                        "counter",
                    )

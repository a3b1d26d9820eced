"""dslint (repro.analysis) tests: per-rule fixtures, pragma/baseline
behavior, the full-tree tier-1 gate, and the acceptance drills from the
PR spec (re-introducing PR 8's unretried PrefixStore put, dropping a
counter from a registry)."""

import json
import os

import pytest

from repro.analysis import run_analysis
from repro.analysis.engine import changed_files, update_baseline
from repro.analysis.rules import ALL_RULES

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "dslint")


def fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


def lint_tree(tmp_path, files, **kwargs):
    """Write ``files`` (relpath -> source) under a fresh root and lint it
    with an empty baseline."""
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source, encoding="utf-8")
    kwargs.setdefault("baseline_path", str(tmp_path / "baseline.json"))
    return run_analysis(str(tmp_path), **kwargs)


def rules_fired(report):
    return sorted({f.rule for f in report.findings})


# --------------------------------------------------------------- rule catalog
def test_rule_ids_are_unique_and_titled():
    ids = [r.rule_id for r in ALL_RULES]
    assert len(ids) == len(set(ids))
    assert all(r.title for r in ALL_RULES)
    assert "R0" not in ids  # reserved for engine hygiene findings


# ------------------------------------------------------------- R1 fixtures
def test_r1_trips_on_bare_lease_ops(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r1_bad.py")}
    )
    r1 = [f for f in report.findings if f.rule == "R1"]
    assert len(r1) == 2, report.render()
    assert any("store.put_json" in f.message for f in r1)
    assert any("rq.delete" in f.message for f in r1)


def test_r1_passes_retry_wrapped_ops(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r1_good.py")}
    )
    assert report.ok, report.render()


def test_r1_ignores_modules_without_lease_role(tmp_path):
    source = fixture("r1_bad.py").replace("# dslint-role: lease", "")
    report = lint_tree(tmp_path, {"src/repro/fix.py": source})
    assert report.ok, report.render()


# ------------------------------------------------------------- R2 fixtures
def test_r2_trips_on_ack_before_put(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r2_bad.py")}
    )
    assert rules_fired(report) == ["R2"], report.render()


def test_r2_passes_put_then_ack_and_cross_loop_order(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r2_good.py")}
    )
    assert report.ok, report.render()


# ------------------------------------------------------------- R3 fixtures
def test_r3_trips_on_clock_rng_and_set_iteration(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r3_bad.py")}
    )
    r3 = [f for f in report.findings if f.rule == "R3"]
    assert len(r3) == 3, report.render()
    blob = " ".join(f.message for f in r3)
    assert "time.time" in blob and "random.random" in blob and "seen" in blob


def test_r3_passes_seeded_and_sorted(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r3_good.py")}
    )
    assert report.ok, report.render()


# ------------------------------------------------------------- R5 fixtures
def test_r5_trips_on_unlocked_shared_writes(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r5_bad.py")}
    )
    r5 = [f for f in report.findings if f.rule == "R5"]
    assert len(r5) == 2, report.render()  # one per unguarded side
    assert all("pending" in f.message for f in r5)


def test_r5_passes_locked_and_single_writer(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r5_good.py")}
    )
    assert report.ok, report.render()


def test_r5_flags_module_globals_in_lease_modules(tmp_path):
    source = "# dslint-role: lease\nCACHE = {}\n"
    report = lint_tree(tmp_path, {"src/repro/fix.py": source})
    assert rules_fired(report) == ["R5"], report.render()
    suppressed = source.replace(
        "CACHE = {}", "CACHE = {}  # dslint: disable=R5(per-key ownership)"
    )
    report = lint_tree(tmp_path, {"src/repro/fix.py": suppressed})
    assert report.ok, report.render()


# --------------------------------------------------------- R4 (project rule)
TYPES_EXPLICIT = '''
from dataclasses import dataclass

@dataclass
class EngineStats:
    ticks: int = 0
    tokens_emitted: int = 0
    _scratch: int = 0

    def snapshot(self):
        return {"ticks": self.ticks}
'''

TYPES_DYNAMIC = '''
from dataclasses import dataclass, fields

@dataclass
class EngineStats:
    ticks: int = 0
    tokens_emitted: int = 0
    _scratch: int = 0

    def snapshot(self):
        return {f.name: getattr(self, f.name) for f in fields(self)
                if not f.name.startswith("_")}
'''

DOCS_BOTH = "counters: `ticks` and `tokens_emitted`\n"


def test_r4_trips_on_counter_dropped_from_snapshot(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/serving/types.py": TYPES_EXPLICIT,
        "docs/serving.md": DOCS_BOTH,
    })
    r4 = [f for f in report.findings if f.rule == "R4"]
    assert len(r4) == 1 and "tokens_emitted" in r4[0].message, report.render()
    assert "snapshot" in r4[0].message


def test_r4_trips_on_undocumented_counter(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/serving/types.py": TYPES_DYNAMIC,
        "docs/serving.md": "counters: `ticks`\n",
    })
    r4 = [f for f in report.findings if f.rule == "R4"]
    assert len(r4) == 1 and "tokens_emitted" in r4[0].message, report.render()
    assert "docs/serving.md" in r4[0].message


def test_r4_passes_agreeing_registries(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/serving/types.py": TYPES_DYNAMIC,
        "docs/serving.md": DOCS_BOTH,
    })
    assert report.ok, report.render()


# --------------------------------------------------------- R6 (project rule)
OPS_FIXTURE = "def myop(x):\n    return x\n\n\ndef _helper(x):\n    return x\n"


def test_r6_trips_on_missing_oracle_and_test(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/kernels/ops.py": OPS_FIXTURE,
        "src/repro/kernels/ref.py": "def myop_reference(x):\n    return x\n",
        "tests/test_kernels.py": "def test_other():\n    pass\n",
    })
    r6 = [f for f in report.findings if f.rule == "R6"]
    msgs = " | ".join(f.message for f in r6)
    assert "no module-level ORACLES" in msgs, report.render()
    assert "no ORACLES entry" in msgs
    assert "never referenced" in msgs


def test_r6_passes_registered_and_tested_kernel(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/kernels/ops.py": OPS_FIXTURE,
        "src/repro/kernels/ref.py": (
            "def myop_reference(x):\n    return x\n\n\n"
            'ORACLES = {"myop": myop_reference}\n'
        ),
        "tests/test_kernels.py": "def test_myop():\n    assert myop\n",
    })
    assert report.ok, report.render()


# --------------------------------------------------------- R7 (project rule)
CONFIG_FIXTURE = '''
from dataclasses import dataclass

INERT_PAPER_FIELDS = {
    "dead_knob": "paper parity: nothing to size in the simulation",
    "vanished": "covers a field that no longer exists",
}

@dataclass
class DSConfig:
    live_knob: int = 1
    dead_knob: int = 2
    ghost_knob: int = 3
'''


def test_r7_trips_on_inert_and_stale_entries(tmp_path):
    report = lint_tree(tmp_path, {
        "src/repro/core/config.py": CONFIG_FIXTURE,
        "src/repro/core/user.py": "def use(cfg):\n    return cfg.live_knob\n",
    })
    r7 = [f for f in report.findings if f.rule == "R7"]
    msgs = " | ".join(f.message for f in r7)
    assert "ghost_knob" in msgs, report.render()  # consumed nowhere
    assert "vanished" in msgs  # stale refusal entry
    assert "dead_knob" not in msgs  # refused with a reason: fine
    assert "live_knob" not in msgs  # consumed: fine


def test_r7_consumption_via_string_or_kwarg_counts(tmp_path):
    user = (
        'def use(d, **kw):\n'
        '    a = d["ghost_knob"]\n'
        '    return a\n'
    )
    report = lint_tree(tmp_path, {
        "src/repro/core/config.py": CONFIG_FIXTURE,
        "src/repro/core/user.py": (
            "def use(cfg):\n    return cfg.live_knob\n" + user
        ),
    })
    msgs = " | ".join(f.message for f in report.findings if f.rule == "R7")
    assert "ghost_knob" not in msgs, report.render()


# ------------------------------------------------------- pragmas & baseline
def test_pragma_suppresses_but_hygiene_fires(tmp_path):
    report = lint_tree(
        tmp_path, {"src/repro/fix.py": fixture("r0_bad.py")}
    )
    # the R1 finding is suppressed by the (malformed) pragma...
    assert not any(f.rule == "R1" for f in report.findings)
    assert len(report.suppressed) == 1
    # ...but the empty reason and the unknown rule id are R0 findings
    r0 = [f for f in report.findings if f.rule == "R0"]
    msgs = " | ".join(f.message for f in r0)
    assert "no reason" in msgs and "R99" in msgs, report.render()


def test_pragma_on_def_header_covers_the_body(tmp_path):
    source = (
        "# dslint-role: lease\n"
        "def probe(store, key):  # dslint: disable=R1(probe is best-effort)\n"
        "    return store.exists(key)\n"
    )
    report = lint_tree(tmp_path, {"src/repro/fix.py": source})
    assert report.ok, report.render()
    assert len(report.suppressed) == 1


def test_baseline_workflow_roundtrip(tmp_path):
    files = {"src/repro/fix.py": fixture("r1_bad.py")}
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src, encoding="utf-8")
    bl = tmp_path / "baseline.json"

    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    assert len(report.findings) == 2

    with pytest.raises(ValueError):
        update_baseline(str(tmp_path), justification="  ",
                        baseline_path=str(bl))

    update_baseline(str(tmp_path), justification="known, tracked elsewhere",
                    baseline_path=str(bl))
    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    assert report.ok and len(report.baselined) == 2, report.render()

    # fingerprints survive unrelated edits above the finding
    p = tmp_path / "src/repro/fix.py"
    p.write_text("# new leading comment\n" + p.read_text(), encoding="utf-8")
    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    assert report.ok and len(report.baselined) == 2, report.render()

    # fixing the violations makes the entries stale (full runs only)
    p.write_text(fixture("r1_good.py"), encoding="utf-8")
    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    assert not report.findings and len(report.stale_baseline) == 2
    update_baseline(str(tmp_path), justification="sweep stale",
                    baseline_path=str(bl))
    assert json.loads(bl.read_text()) == {}


def test_baseline_entry_without_justification_is_a_finding(tmp_path):
    files = {"src/repro/fix.py": fixture("r2_bad.py")}
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src, encoding="utf-8")
    bl = tmp_path / "baseline.json"
    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    (fp,) = [f.fingerprint for f in report.findings]
    bl.write_text(json.dumps({fp: {"rule": "R2", "justification": ""}}))
    report = run_analysis(str(tmp_path), baseline_path=str(bl))
    assert rules_fired(report) == ["R0"], report.render()
    assert "no written" in report.findings[0].message


# ------------------------------------------------------ paths / changed mode
def test_paths_mode_limits_module_findings(tmp_path):
    files = {
        "src/repro/bad.py": fixture("r1_bad.py"),
        "src/repro/other.py": fixture("r3_bad.py"),
    }
    report = lint_tree(tmp_path, files, paths=["src/repro/other.py"])
    assert rules_fired(report) == ["R3"], report.render()
    # stale-baseline detection is deferred on partial runs
    assert report.stale_baseline == []


def test_changed_files_runs_on_the_repo():
    out = changed_files(REPO_ROOT)
    assert isinstance(out, list)
    assert all(p.startswith("src/repro/") for p in out)


def test_cli_list_rules_and_clean_run(capsys):
    from repro.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule.rule_id in listed


# ------------------------------------------------------------ tier-1 gates
def test_repo_tree_is_clean():
    """THE tier-1 gate: the real tree has zero unbaselined findings and
    no stale baseline entries."""
    report = run_analysis(REPO_ROOT)
    assert report.ok, report.render()
    assert report.stale_baseline == [], report.render()


def test_acceptance_reintroduced_bare_prefix_store_put(tmp_path):
    """Stripping the publish pragma (= re-introducing PR 8's unretried
    put) must fail the lint."""
    src_path = os.path.join(
        REPO_ROOT, "src", "repro", "serving", "prefix_store.py"
    )
    with open(src_path, encoding="utf-8") as f:
        source = f.read()
    assert "# dslint: disable=R1" in source
    import re

    stripped = re.sub(r"\s*# dslint: disable=R1[^\n]*", "", source)
    report = lint_tree(
        tmp_path, {"src/repro/serving/prefix_store.py": stripped}
    )
    r1 = [f for f in report.findings if f.rule == "R1"]
    assert any("put_bytes" in f.message for f in r1), report.render()


def test_acceptance_counter_dropped_from_docs(tmp_path):
    """Un-documenting a real counter must fail the lint."""
    with open(
        os.path.join(REPO_ROOT, "src", "repro", "serving", "types.py"),
        encoding="utf-8",
    ) as f:
        types_src = f.read()
    with open(
        os.path.join(REPO_ROOT, "docs", "serving.md"), encoding="utf-8"
    ) as f:
        docs = f.read()
    report = lint_tree(tmp_path, {
        "src/repro/serving/types.py": types_src,
        "docs/serving.md": docs.replace("`ticks`", "ticks"),
    })
    r4 = [f for f in report.findings if f.rule == "R4"]
    assert any("'ticks'" in f.message for f in r4), report.render()

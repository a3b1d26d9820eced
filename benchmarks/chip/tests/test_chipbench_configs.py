"""Each configuration file against the program's registry, and
``BENCHMARK.json`` against the rules its readers rely on."""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cells, harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def config_file(name):
    with open(os.path.join(ROOT, CONFIGS[name]["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_matches_the_registry(name):
    from repro.configs import get_arch

    conf = config_file(name)
    arch = get_arch(conf["arch"])
    assert sorted(conf["reduced"]) == sorted(CONFIGS[name]["reduced"])
    # keys set from the published model where the registry has another
    # value; none of them is a width
    from_source = set(conf.get("from_source", []))
    assert not from_source & {"d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab_size"}
    for key, value in conf["model"].items():
        published = arch.resolved_head_dim if key == "head_dim" else getattr(arch, key)
        if key in conf["reduced"]:
            assert value != published and conf["published"][key] == published
        elif key in from_source:
            assert value != published, key
        else:
            assert value == published, key
    # the program builds what the file says, and no width is cut
    built = harness.build_arch(conf)
    assert built.d_model == arch.d_model and built.d_ff == arch.d_ff
    assert built.n_heads == arch.n_heads and built.n_kv_heads == arch.n_kv_heads


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_layout_matches_the_program(name):
    """The reference's table of weights has the program's paths and shapes,
    so both get the same numbers from the same seed."""
    import jax

    from repro.models import Model

    from chipbench import weights

    conf = config_file(name)
    ref = cells.reference_module(conf["reference"])
    model = Model(harness.build_arch(conf))
    program = weights.spec_of(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert program == {tuple(k): tuple(v) for k, v in ref.weight_spec(conf["model"]).items()}


def test_benchmark_json_follows_its_rules():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and os.path.isdir(os.path.join(ROOT, p))
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(cells.metric_file(m["name"], os.path.join(ROOT, SPEC["paths"][0])))
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cell_names = {w["name"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert w["config"] in CONFIGS and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(ROOT, SPEC["paths"][0], "traffic", w["traffic"] + ".json"))
        cell = cells.load(w["name"], ROOT)
        assert {m["name"] for m in cell.end_to_end} - {"setup_s"} and cell.per_layer
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= cell_names
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in cells.load(w, ROOT).end_to_end}
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)

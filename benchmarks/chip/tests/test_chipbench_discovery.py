"""The harness finds a new configuration, traffic mix, metric and model
family by their names in ``BENCHMARK.json`` and their files alone."""

import dataclasses
import importlib.util
import json
import os
import shutil
import time

import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import cells, harness, peaks, weights

FIXTURE = os.path.join(tiny.BENCH, "tests", "data", "family_fixture.py")
DENSE = os.path.join(tiny.BENCH, "references", "dense_transformer.py")


def _family(path):
    spec = importlib.util.spec_from_file_location("family_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _deepseek_spec():
    """``{path: shape}`` of the program's deepseek-v2-236b at ``reduced()`` widths."""
    import jax

    from repro.configs import get_arch
    from repro.configs.base import reduced
    from repro.models import Model

    arch = reduced(get_arch("deepseek-v2-236b"))
    spec = weights.spec_of(jax.eval_shape(Model(arch).init, jax.random.PRNGKey(0)))
    return spec, dict(n_layers=arch.n_layers)


def _leaves(tree):
    import jax

    return {"/".join(k.key for k in kp): np.asarray(x)
            for kp, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_family_file_rule_makes_every_leaf():
    """A family file's rule makes all 33 leaves of the program's latent
    attention, sparse expert model; the same seed makes the same numbers."""
    import jax.numpy as jnp

    spec, model = _deepseek_spec()
    assert len(spec) == 33
    rule = _family(FIXTURE).weight_scale
    a = _leaves(weights.make(spec, model, 2**31 + 9, jnp.float32, rule))
    b = _leaves(weights.make(spec, model, 2**31 + 9, jnp.float32, rule))
    c = _leaves(weights.make(spec, model, 2**31 + 10, jnp.float32, rule))
    assert sorted(a) == sorted("/".join(p) for p in spec) and len(a) == 33
    for name in a:
        assert a[name].shape == spec[tuple(name.split("/"))]
        np.testing.assert_array_equal(a[name], b[name])
    assert not np.array_equal(a["layers/moe/wi"], c["layers/moe/wi"])
    assert np.all(a["layers/attn/kv_norm/w"] == 1.0)


def test_dense_rule_names_the_leaf_and_its_file():
    import jax.numpy as jnp

    spec, model = _deepseek_spec()
    with pytest.raises(KeyError) as e:
        weights.make(spec, model, 1, jnp.float32, _family(DENSE).weight_scale)
    assert "dense_layers/attn/k_up" in str(e.value)
    assert os.path.join("references", "dense_transformer.py") in str(e.value)


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "references"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(tiny.BENCH, "metrics", "setup_s.py"), bench / "metrics")
    shutil.copy(os.path.join(tiny.BENCH, "references", "dense_transformer.py"), bench / "references")
    with open(os.path.join(tiny.BENCH, "configs", "granite-34b-l4.json")) as f:
        conf = json.load(f)
    conf["name"] = "tiny-dense"
    conf["model"].update(_family(DENSE).TINY, n_kv_heads=2)
    conf["engine"].update(tiny.TINY_ENGINE)
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(conf))
    (bench / "traffic" / "burst_mix.json").write_text(json.dumps({
        "mode": "open_loop", "rate_per_s": 10.0,
        "prompt": {"dist": "uniform", "low": 8, "high": 40},
        "max_new_tokens": {"dist": "constant", "value": 12}}))
    (bench / "metrics" / "served_tokens.py").write_text(
        "def read(run):\n    return sum(len(r.token_t) for r in run.window.records)\n")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 2,
        "configs": [{"name": "tiny-dense", "source": "test", "file": "bench/configs/tiny-dense.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny-dense", "traffic": "burst_mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "served_tokens", "unit": "tokens", "better": "higher", "bound": 0.05,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load("tiny.burst", str(tmp_path))
    assert cell.config["name"] == "tiny-dense" and cell.mix["rate_per_s"] == 10.0
    run = harness.run_cell(cell, 12345, 2.0, trace=False, t_start=time.perf_counter(),
                           compile_count=lambda: 0, peak=peaks.peak_for("TPU v5 lite"))
    assert harness.is_correct(run)
    served = sum(len(r.token_t) for r in run.window.records)
    assert served > 0 and all(len(r.token_t) == 12 for r in run.window.records if r.done)
    assert run.metrics["served_tokens"] == {"value": float(served), "unit": "tokens"}
    assert set(run.metrics) == {"served_tokens", "setup_s"}


def test_dispatch_work_comes_from_the_family_file(tmp_path):
    """A configuration whose reference is a new family file: each dispatch
    records what that file's work functions return, not the dense counts."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "references"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(tiny.BENCH, "metrics", "setup_s.py"), bench / "metrics")
    shutil.copy(FIXTURE, bench / "references" / "fixture_family.py")
    with open(os.path.join(tiny.BENCH, "configs", "granite-34b-l4.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny-fixture", reference="fixture_family")
    conf["model"].update(_family(FIXTURE).tiny(conf["model"]))
    conf["engine"].update(tiny.TINY_ENGINE)
    (bench / "configs" / "tiny-fixture.json").write_text(json.dumps(conf))
    shutil.copy(os.path.join(tiny.BENCH, "traffic", "offline_decode.json"), bench / "traffic")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 2,
        "configs": [{"name": "tiny-fixture", "source": "test", "reduced": [], "why": "test",
                     "file": "bench/configs/tiny-fixture.json"}],
        "workloads": [{"name": "tiny.offline", "config": "tiny-fixture",
                       "traffic": "offline_decode", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
                        "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = tiny.shrink(cells.load("tiny.offline", str(tmp_path)))
    run = harness.run_cell(cell, 2**31 + 3, 1.0, trace=False, t_start=time.perf_counter(),
                           compile_count=lambda: 0, peak=peaks.peak_for("TPU v5 lite"),
                           check_outputs=False)
    family = _family(FIXTURE)
    kinds = {d.kind for d in run.window.dispatches}
    assert kinds == {"prefill", "decode"}
    for d in run.window.dispatches:
        assert type(d.work).__name__ == "FamilyWork"
        count = family.extend_work if d.kind == "prefill" else family.decode_work
        assert dataclasses.astuple(count(*d.work.inputs)) == dataclasses.astuple(d.work)
        assert d.work.tokens > 0

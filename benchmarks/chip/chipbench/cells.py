"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file is the ``file`` of its ``configs`` entry; the
mix is ``traffic/<traffic>.json``; each metric is computed by
``metrics/<name>.py``'s ``read(run)``, or, where no such file is, by
that of the name without its last ``.<suffix>`` (``mfu.online`` and
``mfu.offline`` both read ``metrics/mfu.py``).  Everything the harness
knows of a model family is in the reference file the configuration names
(:func:`reference_module`).  A new configuration, mix, metric or family
is therefore a new file and a new entry, never an edit here.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    @property
    def engine(self) -> dict:
        """Engine settings: the configuration's, with the mix's overrides."""
        return {**self.config["engine"], **self.mix.get("engine", {})}


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    bench_dir = os.path.join(root, bench["paths"][0])
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=_load_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        mix=_load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        end_to_end=e2e,
        per_layer=per_layer,
        bench_dir=bench_dir,
    )


def metric_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    """``metrics/<name>.py``, else ``metrics/<name less its last .suffix>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(bench_dir, "metrics", name.rsplit(".", 1)[0] + ".py")
    return path


def reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(run)`` of the metric's file (:func:`metric_file`)."""
    path = metric_file(name, bench_dir)
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(name: str, bench_dir: str = BENCH_DIR):
    """The family file ``references/<name>.py`` a configuration names: its
    plain reference (``weight_spec``, ``Reference``), weight rule
    (``weight_scale``), work counts (``extend_work``, ``decode_work``) and
    tiny widths (``tiny``)."""
    path = os.path.join(bench_dir, "references", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_table(cell: Cell, per_layer: bool) -> Dict[str, dict]:
    return {m["name"]: m for m in (cell.per_layer if per_layer else cell.end_to_end)}

"""Where a cell's pieces live, found by name from ``BENCHMARK.json``.

Nothing here lists the configurations, mixes or metrics: a cell names its
configuration, the configuration entry names its file, the cell's
``traffic`` is ``mixes/<traffic>.json``, a configuration's ``reference``
is ``references/<reference>.py`` and a metric ``<name>`` is
``metrics/<name>.py``. Adding one is adding its file and its entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _covers(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(root: pathlib.Path, workload: str,
              bench_dir: pathlib.Path = HERE) -> Cell:
    """The cell named ``workload`` of ``root/BENCHMARK.json``, with its
    configuration and traffic files read and the metrics it reports."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=conf["name"], config=load_json(root / conf["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(bench_dir / "mixes" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _covers(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _covers(m, workload)])


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: pathlib.Path = HERE
                  ) -> Callable[[Any], Optional[float]]:
    """``read(record) -> value or None`` of metric ``name``."""
    return _module(bench_dir / "metrics" / f"{name}.py",
                   f"chipbench_metric_{name}").read


def reference(name: str, bench_dir: pathlib.Path = HERE):
    """The plain reference module ``references/<name>.py``."""
    return _module(bench_dir / "references" / f"{name}.py",
                   f"chipbench_reference_{name}")

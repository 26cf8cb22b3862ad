"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later change adds one by adding its files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: pathlib.Path = BENCH_DIR / "metrics"
                ) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run)``.  A name with a
    variant, ``<base>.<variant>``, falls back to ``<base>.py`` where it
    has no file of its own."""
    path = metrics_dir / f"{name}.py"
    if not path.is_file() and "." in name:
        path = metrics_dir / f"{name.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} in "
                                f"{metrics_dir}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_traffic(traffic: str, config: str,
                 traffic_dir: pathlib.Path = BENCH_DIR / "traffic") -> dict:
    """The mix's parameters: ``<traffic>.json`` overlaid key by key with
    the cell's own ``<traffic>/<config>.json`` where that exists."""
    params = load_json(traffic_dir / f"{traffic}.json")
    cell_file = traffic_dir / traffic / f"{config}.json"
    if cell_file.is_file():
        params.update(load_json(cell_file))
    return params


def cell_metrics(entries: List[dict], cell: str,
                 metrics_dir: pathlib.Path = BENCH_DIR / "metrics"
                 ) -> List[Metric]:
    """The metrics of ``entries`` that ``cell`` reports: those without
    a ``workloads`` list, and those whose list names it."""
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(name=m["name"], unit=m["unit"],
                          read=load_reader(m["name"], metrics_dir)))
    return out


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = load_json(root / "BENCHMARK.json")
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=load_json(root / configs[w["config"]]["file"]),
                traffic=load_traffic(w["traffic"], w["config"],
                                     bench / "traffic"),
                end_to_end=cell_metrics(spec["end_to_end"], name,
                                        bench / "metrics"),
                per_layer=cell_metrics(spec["per_layer"], name,
                                       bench / "metrics"))

"""Finds the pieces of a cell by name: the benchmark is driven by data.

`BENCHMARK.json` names each cell's configuration, traffic mix and metrics.
Each piece lives in a file of its own, found by its name alone:

  bench/configs/<config>.json    sizes, engine settings, limits of `correct`
  bench/arch/<model_type>[.py]   the architecture binding that a config's
                                 `model_type` names: its weights, plain
                                 reference, parameter layout and op counts
                                 (the interface: `bench/arch/__init__.py`)
  bench/traffic/<traffic>.json   parameters of the one general generator
  bench/metrics/<metric>.py      a reader with `read(run) -> float | None`
  bench/peaks.json               peak rates keyed by `device_kind`

A later change adds a configuration (of a new architecture too), a mix or
a per-layer metric by adding such files and an entry in `BENCHMARK.json`;
no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]] = None  # None: every cell

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _metric(entry: Dict[str, Any]) -> Metric:
    return Metric(entry["name"], entry["unit"], entry.get("workloads"))


def load_cell(name: str, *, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its config and mix."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in map(_metric, bench["end_to_end"]) if m.applies_to(name)]
    layer = [m for m in map(_metric, bench["per_layer"]) if m.applies_to(name)]
    return Cell(
        name=name,
        config_name=w["config"],
        traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=layer,
    )


def bare_cell(config_name: str, traffic_name: str, *, root: str = ROOT) -> Cell:
    """A cell of a configuration and a mix that BENCHMARK.json need not
    list (for the knee sweep and the calibration of limits)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cfg_entry = {c["name"]: c for c in bench["configs"]}[config_name]
    return Cell(
        name=f"{config_name}.{traffic_name}",
        config_name=config_name,
        traffic_name=traffic_name,
        chips=1,
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(root, "bench", "traffic", traffic_name + ".json")),
        end_to_end=[],
        per_layer=[],
    )


def metric_reader(name: str, *, root: str = ROOT) -> Callable[[Any], Optional[float]]:
    """`read` of `bench/metrics/<name>.py`, loaded by file path (metric
    names hold dots, so they are not importable module names)."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(device_kind: str, *, root: str = ROOT) -> Dict[str, float]:
    """Published peaks of one chip of `device_kind`; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]

"""What a run is told: the cell from ``BENCHMARK.json``, its configuration,
its traffic mix, its correctness limits and its per-layer metric readers.
Each lives in a file of its own, found by the name in ``BENCHMARK.json``."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[dict], Any]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list        # Metric readers this cell reports


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader(name: str) -> Callable[[dict], Any]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; one of "
                         f"{sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / confs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [Metric(m["name"], m["unit"], _reader(m["name"]))
                 for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def run_seconds(root: pathlib.Path = ROOT) -> int:
    return int(_load_json(root / "BENCHMARK.json")["run_seconds"])


def peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of one chip of ``kind`` (a device_kind as JAX
    reports it). A kind that is not in the table is an error."""
    table = _load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][kind]

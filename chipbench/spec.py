"""Cells, configurations, traffic mixes, limits and metric readers, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  A
configuration is the file its entry names; a mix is
``chipbench/traffic/<traffic>.json``; a cell's correctness limits are
``chipbench/limits/<cell>.json``; a per-layer metric's reader is
``chipbench/metrics/<metric>.py``.  Adding any of them is adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

REPO = Path(__file__).resolve().parents[1]
HERE = Path("chipbench")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = REPO) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def _by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} (known: {known})")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = REPO) -> Cell:
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=c["name"], config=_json(root / c["file"]),
        traffic_name=w["traffic"],
        traffic=_json(root / HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(root / HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str, root: Path = REPO) -> Callable[[Dict[str, Any]], Any]:
    path = root / HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = REPO) -> Dict[str, float]:
    table = _json(root / HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]

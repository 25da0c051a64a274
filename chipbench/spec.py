"""Cells, configurations, traffic mixes, limits, metric readers and a
configuration's parts, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  A
configuration is the file its entry names; a mix is
``chipbench/traffic/<traffic>.json``; a cell's correctness limits are
``chipbench/limits/<cell>.json``; a per-layer metric's reader is
``chipbench/metrics/<metric>.py``.  A configuration names its client
model, its inputs and its partition under the keys ``family``,
``inputs`` and ``partition``: ``chipbench/families/<family>.py``,
``chipbench/inputs/<inputs>.py`` and ``chipbench/partitions/<partition>.py``.
Adding any of them is adding files.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List

REPO = Path(__file__).resolve().parents[1]
HERE = Path("chipbench")
# a configuration's key -> the directory of the files it names
PARTS = {"family": "families", "inputs": "inputs", "partition": "partitions"}


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


def _check_parts(config: Dict[str, Any]) -> Dict[str, Any]:
    for key, folder in PARTS.items():
        if key not in config:
            raise KeyError(f"configuration {config.get('name')!r} has no {key!r} key: "
                           f"it names its {key} file, chipbench/{folder}/<{key}>.py")
    return config


def load_cell(name: str, root: Path = REPO) -> Cell:
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=c["name"], config=_check_parts(_json(root / c["file"])),
        traffic_name=w["traffic"],
        traffic=_json(root / HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(root / HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _exec(path: Path, module_name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(name: str, root: Path = REPO) -> ModuleType:
    return _exec(root / HERE / "metrics" / f"{name}.py", f"chipbench_metric_{name}")


def metric_reader(name: str, root: Path = REPO) -> Callable[[Dict[str, Any]], Any]:
    return metric_module(name, root).read


@functools.lru_cache(maxsize=None)
def part(key: str, name: str, root: Path = REPO) -> ModuleType:
    """A configuration's part by its key: ``part("family", "mlp")`` is
    ``chipbench/families/mlp.py``."""
    return _exec(root / HERE / PARTS[key] / f"{name}.py", f"chipbench_{key}_{name}")


def parts(config: Dict[str, Any], root: Path = REPO) -> Dict[str, ModuleType]:
    """The configuration's family, inputs and partition, by key."""
    _check_parts(config)
    return {key: part(key, config[key], root) for key in PARTS}


def peaks(device_kind: str, root: Path = REPO) -> Dict[str, float]:
    table = _json(root / HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]

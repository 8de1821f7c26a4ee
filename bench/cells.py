"""Finding a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are found from those names alone:

    bench/configs/<config>.json        sizes of the configuration as run
    bench/references/<reference>.py    its plain reference (named inside)
    bench/traffic/<traffic>.json       the mix the generator reads
    bench/limits/<workload>.json       the limit of each compared number
    bench/metrics/<metric>.py          one reader per per-layer metric

A new cell, mix, configuration or metric is a new file and a new entry;
no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports; raises KeyError naming what is missing."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, chips=w["chips"],
        config_name=w["config"],
        config=_json(ROOT / configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _load(path: Path, module: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict) -> ModuleType:
    """The configuration's plain reference module."""
    name = config["reference"]
    return _load(HERE / "references" / f"{name}.py", f"bench_reference_{name}")


def metric_reader(name: str) -> ModuleType:
    """The reader module of per-layer metric ``name`` (its ``read(run)``)."""
    return _load(HERE / "metrics" / f"{name}.py",
                 "bench_metric_" + name.replace(".", "_"))

"""Resolve a workload of BENCHMARK.json to the files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it:

    configuration   the `file` of its `configs` entry (JSON)
    traffic mix     benchmarks/chip/traffic/<traffic>.json
    per-layer       benchmarks/chip/metrics/<metric>.py, with a function
    metric          `read(reading)` that returns a number or None

A new cell is therefore a workload entry plus data files, and a new
per-layer metric a metric entry plus its reader.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Tuple

HERE = Path(__file__).resolve().parent
BENCHMARK = HERE.parents[1] / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: Tuple[dict, ...]
    per_layer: Tuple[Tuple[dict, ModuleType], ...]


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, root: Path = HERE) -> ModuleType:
    path = root / "metrics" / f"{name}.py"
    loader_spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    if loader_spec is None or not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader at {path}")
    module = importlib.util.module_from_spec(loader_spec)
    loader_spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise TypeError(f"{path} defines no read(reading)")
    return module


def _by_name(entries: List[dict], kind: str) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for e in entries:
        if e["name"] in out:
            raise ValueError(f"two {kind} named {e['name']!r}")
        out[e["name"]] = e
    return out


def resolve(bench: dict, workload: str, root: Path = HERE) -> Cell:
    """The cell `workload` of `bench`, with its files read from the
    benchmark directory `root` (configuration paths are relative to the
    checkout, two levels up)."""
    workloads = _by_name(bench["workloads"], "workloads")
    if workload not in workloads:
        raise KeyError(f"no workload {workload!r}; known: {sorted(workloads)}")
    w = workloads[workload]
    cfg_entry = _by_name(bench["configs"], "configs")[w["config"]]
    e2e = tuple(m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload]))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(
        (m, load_reader(m["name"], root)) for m in bench["per_layer"]
        if workload in m.get("workloads", [workload] if m["moves"] in reported else []))
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(root.parents[1] / cfg_entry["file"]),
        traffic=load_json(root / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )

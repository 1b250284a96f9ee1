"""BENCHMARK.json and the files it names, found by name.

A cell joins a configuration (`configs/<name>.json`, named by the
`file` of its entry), a traffic mix (`traffic/<name>.json`) and the
metrics that apply to it. Each per-layer metric is read by
`metrics/<name>.py`, whose `read(ctx)` returns a number or None.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PACKAGE = Path(__file__).resolve().parent


class SpecError(ValueError):
    pass


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named `workload`, with its configuration and traffic
    loaded and its metrics filtered to those it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(root / "benchmark" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    layers = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layers)


def reader(name: str, root: Path = ROOT):
    """The `read(ctx)` of per-layer metric `name`."""
    path = root / "benchmark" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def device_entry(kind: str, root: Path = ROOT) -> dict:
    """The table row of a device kind; an unknown kind is an error."""
    with open(root / "benchmark" / "devices.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise SpecError(f"device kind {kind!r} is not in benchmark/devices.json")
    return table["devices"][kind]

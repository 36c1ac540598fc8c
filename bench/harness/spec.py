"""What one cell is, found by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each of those, and each per-layer
metric's reader, is a file of its own under the benchmark's directory.

  configs  the file that ``BENCHMARK.json`` gives for the configuration
  traffic  ``traffic/<traffic>.json``
  metrics  ``metrics/<metric name>.py``, defining ``read(ctx)``, for
           every end-to-end and per-layer metric

A new cell, configuration, traffic mix or metric is new files plus new
entries in ``BENCHMARK.json``; nothing here changes for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

__all__ = ["BENCH_DIR", "ROOT", "Cell", "Metric", "load_cell"]

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable                 # read(ctx) -> number, or None
    what: str = ""                 # the reader's docstring: what it reads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple              # Metric, in BENCHMARK.json order
    per_layer: tuple


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _load_reader(path: Path) -> tuple[Callable, str]:
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read, " ".join((mod.__doc__ or "").split())


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix and the readers of its per-layer metrics."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    bench_dir = root / Path(bench["paths"][0])
    def metrics(group):
        return tuple(
            Metric(m["name"], m["unit"],
                   *_load_reader(bench_dir / "metrics" / f"{m['name']}.py"))
            for m in bench[group] if _applies(m, name))

    e2e, layer = metrics("end_to_end"), metrics("per_layer")
    return Cell(name=name, chips=int(w["chips"]),
                config=_load_json(root / cfg_entry["file"]),
                traffic=_load_json(bench_dir / "traffic"
                                   / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)

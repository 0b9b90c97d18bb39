"""Find a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` (the repo root) lists the cells. A cell ``<config>.<mix>``
is made of:

* ``bench/configs/<config>.json``: the model, its published sizes, the
  program's arch and overrides, the engine's shape, and ``"model"``, the
  name of its model module;
* ``bench/models/<model>.py``: everything that knows the model's shape:
  its sizes, weights, plain reference and costs (``models/dense.py`` says
  what a module gives);
* ``bench/traffic/<mix>.json``: the generator's kind and distributions;
* ``bench/cells/<cell>.json``: what differs by configuration (length bounds,
  the fixed rate) and the limits of the output check;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a model, a mix, a cell or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    model: ModuleType
    bench: Path                 # the directory the cell's files are in


def load_module(path: Path) -> ModuleType:
    """The Python file at ``path`` as a module, run once per process (so
    a model's jitted reference compiles once)."""
    name = f"bench_file:{path.resolve()}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    here = root / "bench"
    config = json.loads((here / "configs" / f"{w['config']}.json")
                        .read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=json.loads((here / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        cell=json.loads((here / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        model=load_module(here / "models" / f"{config['model']}.py"),
        bench=here)

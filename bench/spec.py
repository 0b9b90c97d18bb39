"""Find a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` (the repo root) lists the cells. A cell ``<config>.<mix>``
is made of:

* ``bench/configs/<config>.json``: the model, its published sizes, the
  program's arch and overrides, and the engine's shape;
* ``bench/traffic/<mix>.json``: the generator's kind and distributions;
* ``bench/cells/<cell>.json``: what differs by configuration (length bounds,
  the fixed rate) and the limits of the output check;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a mix, a cell or a metric adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

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
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((here / "configs" / f"{w['config']}.json")
                          .read_text()),
        traffic=json.loads((here / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        cell=json.loads((here / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])

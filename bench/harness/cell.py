"""Find every piece of a cell by the names in ``BENCHMARK.json``.

* the configuration: the file its ``configs`` entry names;
* the traffic mix: ``bench/traffic/<traffic>.json``;
* the cell's own settings, ``bench/cells/<cell>.json``: the most slots the
  program's fit may try (``max_slots``) and the limits of the correctness
  check (``limits``);
* each metric: ``bench/metrics/<metric>.py``, a reader with ``read(run)``.

A cell takes the end-to-end metrics and the per-layer metrics whose
``workloads`` list names it, or that have no such list. Adding a cell, a mix,
a configuration or a metric adds files and entries; it edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Metric:
    name: str
    unit: str
    better: str


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    max_slots: int
    limits: Dict[str, float]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    own = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    pick = lambda group: [                                    # noqa: E731
        Metric(m["name"], m["unit"], m["better"])
        for m in bench[group] if _applies(m, name)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / conf_entry["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        max_slots=int(own["max_slots"]),
        limits=own["limits"],
        end_to_end=pick("end_to_end"),
        per_layer=pick("per_layer"),
    )


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

* ``configs/<config>.json``: the model's sizes as published, with each cut
  listed, and the name of its plain reference under ``refs/``, which says how
  the sizes become the program's fields, the step's FLOPs and the attention
  call's work;
* ``traffic/<traffic>.json``: the batch, the token mix and the training job,
  with the ``kind`` of cell that names its runner, ``runners/<kind>.py``;
* ``limits/<workload>.json``: the limits of the correctness check;
* ``metrics/<metric>.py``: the reader of one per-layer metric;
* ``peaks.json``: the published peaks of each device kind.

A cell, a configuration or a metric is added by adding its file and its entry
in ``BENCHMARK.json``; no file that is there needs an edit.
"""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple

    @property
    def sizes(self):
        """The configuration's sizes, as its plain reference reads them."""
        return reference(self.config).Sizes.from_config(self.config)


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name,
        chips=w["chips"],
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return importlib.import_module(f"{__package__}.metrics.{name}").read


def runner(cell: Cell):
    """The runner of the cell's kind, ``runners/<traffic['kind']>.py``."""
    return importlib.import_module(f"{__package__}.runners.{cell.traffic['kind']}")


def reference(config: dict):
    """The plain reference module ``refs/<config['reference']>.py``."""
    return importlib.import_module(f"{__package__}.refs.{config['reference']}")

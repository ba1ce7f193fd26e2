"""What every entry point of the benchmark shares: reading ``BENCHMARK.json``
and finding, by name, the files that belong to one cell.

The harness is driven by data. ``BENCHMARK.json`` names cells, their
configuration and traffic mix, and the metrics with the cells that report
them; everything that belongs to one of those is a file of its own:

    configs/<configuration>.json      sizes, layout, reference, departures
    traffic/<mix>.json                its ``runner`` and that runner's parameters
    runners/<runner>.py               ``run(cell: Cell, args) -> dict`` (the record)
    end_to_end/<metric>.py            ``read(record) -> float | None``
    layer_metrics/<metric>.py         ``read(record) -> float | None``
    reference/<family>.py             the plain reference of a model family

No file here names a cell, a configuration or a metric in code: a later PR
adds files and entries and edits nothing.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import statistics
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPEC_PATH = os.path.join(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]


@dataclass(frozen=True)
class RunArgs:
    """What the command line gives a runner. ``rehearsal`` is set only by
    ``rehearse.py`` and the benchmark's tests: a run on the CPU at a tiny
    size, whose numbers mean nothing and are printed nowhere."""
    seed: int
    seconds: float
    trace: bool
    t_start: float
    rehearsal: bool = False


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_spec() -> Dict[str, Any]:
    return load_json(SPEC_PATH)


def load_cell(spec: Dict[str, Any], workload: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[entry["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     entry["traffic"] + ".json"))
    return Cell(name=workload, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module. Names may hold dots and
    dashes, so the file is loaded by path, not imported by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"BENCHMARK.json names {kind}/{name}, but there is "
                         f"no {os.path.relpath(path, ROOT)}")
    module_name = "benchmark_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(spec: Dict[str, Any], group: str, workload: str
               ) -> List[Dict[str, Any]]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: all of the group but those that list other cells."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(spec: Dict[str, Any], group: str, kind: str, workload: str,
                 record: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` from each metric's reader. A reader
    that finds nothing to read returns None and the metric is left out. A
    per-layer metric is reported only where the metric it moves is."""
    reported = {m["name"] for m in metrics_of(spec, "end_to_end", workload)}
    out = {}
    for metric in metrics_of(spec, group, workload):
        if "moves" in metric and metric["moves"] not in reported:
            continue
        reader: Callable[[Dict[str, Any]], Optional[float]] = \
            load_module(kind, metric["name"]).read
        value = reader(record)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def median(values) -> Optional[float]:
    """Median of a sequence, None when it is empty."""
    values = list(values)
    return statistics.median(values) if values else None

"""What BENCHMARK.json says of one cell, with the files it names loaded.

The harness holds no cell, configuration, traffic or metric name: a cell is
an entry of ``workloads``; its configuration is the entry of ``configs`` of
that name and the file it gives, whose ``reference`` names the module under
``references/`` that makes its data and checks the answers; its traffic is
``traffic/<traffic>.json``, whose ``driver`` names the module under
``traffic/`` that speaks to the entry point (a mix that only changes a
driver's parameters is one more JSON file naming that driver); a metric
``<name>`` is read by ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if rehearse:
        # the tiny CPU form: the sizes the configuration's own file gives for it
        config = {**config, **config["rehearse"]}
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name) and m["moves"] in reported]
    return Cell(name, int(cell["chips"]), config, traffic, end_to_end, per_layer)


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of this directory, found by the name a
    data file gives (a name may hold ``-`` and ``.``, so not an import)."""
    qualified = f"benchmark.{kind}.{name}"
    if qualified in sys.modules:
        return sys.modules[qualified]
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark/{kind}/ has no {name}.py")
    spec = importlib.util.spec_from_file_location(qualified, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[qualified] = module  # dataclasses look their module up there
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[qualified]
        raise
    return module


def reader(kind: str, metric: str) -> Callable:
    """``read(run)`` of ``<kind>/<metric>.py`` (``end_to_end`` or
    ``layer_metrics``)."""
    return load_module(kind, metric).read


def read_metrics(kind: str, metrics: List[dict], run) -> Dict[str, dict]:
    """Every metric of the list whose reader found something, with all the
    digits it measured."""
    out: Dict[str, dict] = {}
    for metric in metrics:
        value = reader(kind, metric["name"])(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out

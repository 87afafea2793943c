"""Find a cell's pieces by name: nothing here names a cell, a query, a
traffic mix, a controller or a metric.

``BENCHMARK.json`` (at the root of the checkout) lists the cells.  A cell
names a configuration and a traffic mix:

* the configuration's ``file`` is a JSON of sizes: the ``RuntimeConfig``
  fields at its top level (read through ``RuntimeConfig.from_json``), the
  deployment's stream sizes, and ``query``, which names
  ``perfbench/queries/<query>.py`` (the events, the plain reference and
  the decoding of delivered outputs);
* the traffic mix is the data file ``perfbench/traffic/<traffic>.json``.
  Its ``loop`` names ``perfbench/loops/<loop>.py`` (how ticks are offered),
  and its optional ``controller.script`` names
  ``perfbench/controllers/<script>.py``.

A per-layer metric ``<name>`` is read by ``perfbench/metrics/<name>.py``'s
``read(ctx)``.  Adding a cell, a configuration, a query, a traffic mix, a
loop, a controller or a metric is adding files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH_DIR = os.path.basename(HERE)


class SpecError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict              # the configuration file's contents
    traffic: Dict             # the traffic file's contents
    end_to_end: List[Dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]
    root: str                 # checkout the files were read from

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.root)


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = REPO) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(work)})")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(os.path.join(root, confs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name, w, config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


def load_module(kind: str, name: str, root: str = REPO):
    """``perfbench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {kind} module {name!r} at {path}")
    mod_name = f"perfbench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = REPO) -> Callable:
    """``read(ctx)`` of ``perfbench/metrics/<metric>.py``."""
    return load_module("metrics", metric, root).read

"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root of a checkout lists configurations, cells
and metrics.  Everything that belongs to one of them is a file of its
own, found by its name:

* a configuration: the JSON file its entry names (``file``); the plain
  reference it names (``"reference"`` -> ``bench/references/<name>.py``;
  a mix that computes something else names its own); and under the same
  name its architecture, ``bench/architectures/<name>.py``, which maps the
  file onto the program (``program_config(conf)``), gives the counts the
  decode metrics read (``dims(conf)``, ``decode_needs(dims, steps,
  contexts)``) and may give ``init_scale(path, leaf)`` for weights the
  default rule does not cover (``serve.make_weights``).  The two stay
  apart because a reference imports nothing of the program;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric, end-to-end or per-layer: ``bench/metrics/<name>.py``, which
  defines ``read(run)`` returning a number, or None where the run has
  nothing for it to read.

So a new configuration, architecture, mix or metric is new files and new
entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Mapping, Optional

BENCH_DIR = "bench"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: str

    def reference(self):
        """The plain reference module: the mix's, where the mix computes
        something other than the configuration's model (a layer graph),
        else the configuration's."""
        name = self.traffic.get("reference") or self.config["reference"]
        return load_module(os.path.join(self.root, BENCH_DIR, "references",
                                        name + ".py"))

    def architecture(self):
        """The configuration's architecture module."""
        return load_module(architecture_path(self.root, self.config),
                           prefix="bench_architecture_")


def architecture_path(root: str, config: Mapping) -> str:
    return os.path.join(root, BENCH_DIR, "architectures",
                        config["reference"] + ".py")


def load_module(path: str, prefix: str = "bench_"):
    name = prefix + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    # registered, as an import would be: a dataclass defined in the module
    # looks its module up by name
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: Mapping, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metric(root: str, entry: Mapping) -> Metric:
    mod = load_module(os.path.join(root, BENCH_DIR, "metrics",
                                   entry["name"] + ".py"))
    return Metric(name=entry["name"], unit=entry["unit"], read=mod.read)


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of the ``BENCHMARK.json`` under ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, confs[w["config"]]["file"])) as f:
        config = json.load(f)
    arch = architecture_path(root, config)
    if not os.path.isfile(arch):
        raise FileNotFoundError(
            f"configuration {w['config']!r} names the architecture "
            f"{config['reference']!r}, and {arch} does not exist")
    with open(os.path.join(root, BENCH_DIR, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [_metric(root, m) for m in bench["end_to_end"] if _applies(m, name)]
    # a per-layer metric without a workloads list is read wherever the
    # end-to-end metric it moves is reported
    reported = {m.name for m in e2e}
    per_layer = [_metric(root, m) for m in bench["per_layer"]
                 if _applies(m, name) and ("workloads" in m
                                           or m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)

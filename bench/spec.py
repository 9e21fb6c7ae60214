"""``BENCHMARK.json`` and the files it names, found by name.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one ``configs`` gives, and its
``family`` names the module ``bench/families/<family>.py`` that makes the
model's weights, inputs, drivers, reference and answer checks (``FAMILY``);
the mix is ``bench/traffic/<traffic>.json``; each metric that applies to
the cell, end-to-end or per-layer, is read by
``bench/metrics/<metric name>.py``.  Adding a cell, a mix, a configuration,
a model family or a metric therefore means adding files and entries, not
editing code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what every family module exposes:
#:
#: ``model_config(config)``      the program's ``ModelConfig``;
#: ``make_params(config, word, device)``  seeded weights, made on the device;
#: ``make_inputs(config, mix, word)``     the seeded input pool;
#: ``DRIVERS``                   each traffic ``entry`` name to its
#:                               ``bench.drive.Driver`` subclass;
#: ``reference(config, params, answered, inputs)``  what the answers of the
#:                               ``bench.drive.Record`` ``answered`` are held
#:                               to, computed once the window has closed;
#: ``compare(answered, expected, limits)``  the family's checks, each
#:                               ``{"value": ..., "limit": ...}`` by name;
#: ``TOLERANCE_CHECKS``          the names of those checks whose limits the
#:                               configuration's ``limits`` set.
FAMILY = ("model_config", "make_params", "make_inputs", "DRIVERS",
          "reference", "compare", "TOLERANCE_CHECKS")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    """One workload with everything it names, resolved."""

    name: str
    chips: int
    config: Dict            # the configuration file's contents
    traffic: str
    mix: Dict               # the traffic file's contents
    end_to_end: List[Dict]  # end-to-end metrics this cell reports
    per_layer: List[Dict]   # per-layer metrics this cell reports
    family: ModuleType      # the configuration's family module


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(bench: Dict, workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``; raises KeyError for an unknown name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    file = configs[w["config"]]["file"]
    config = load_json(os.path.join(root, file))
    if "family" not in config:
        raise ValueError(f"{file} names no family; known: "
                         f"{known_families(root)}")
    mix = load_json(traffic_file(w["traffic"], root))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=w["traffic"], mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        family=family(config["family"], root))


def traffic_file(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{traffic}.json")


def metric_file(metric: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{metric}.py")


def _load(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name + re.sub(r"\W", "_", os.path.basename(path)[:-3]), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read(run) -> float | None`` function of one metric; ``run`` is
    a ``bench.check.Run``, and None means there was nothing to read."""
    return _load(metric_file(metric, root), "bench_metric_").read


def families_dir(root: str = ROOT) -> str:
    return os.path.join(root, "bench", "families")


def known_families(root: str = ROOT) -> List[str]:
    return sorted(f[:-3] for f in os.listdir(families_dir(root))
                  if f.endswith(".py"))


def family(name: str, root: str = ROOT) -> ModuleType:
    """The module ``bench/families/<name>.py``, loaded by path; raises
    ValueError for a name that no module there has, or for a module that
    lacks a name of ``FAMILY``."""
    known = known_families(root)
    if name not in known:
        raise ValueError(f"unknown family {name!r}; known: {known}")
    mod = _load(os.path.join(families_dir(root), f"{name}.py"),
                "bench_family_")
    lacks = [k for k in FAMILY if not hasattr(mod, k)]
    if lacks:
        raise ValueError(f"family {name!r} lacks {lacks}")
    return mod

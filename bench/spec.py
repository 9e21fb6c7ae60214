"""``BENCHMARK.json`` and the files it names, found by name.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one ``configs`` gives; the mix is
``bench/traffic/<traffic>.json``; each metric that applies to the cell,
end-to-end or per-layer, is read by ``bench/metrics/<metric name>.py``.  Adding a cell, a mix,
a configuration or a metric therefore means adding files and entries, not
editing code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = load_json(traffic_file(w["traffic"], root))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=w["traffic"], mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def traffic_file(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{traffic}.json")


def metric_file(metric: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{metric}.py")


def metric_reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read(run) -> float | None`` function of one metric; ``run`` is
    a ``bench.check.Run``, and None means there was nothing to read."""
    path = metric_file(metric, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

"""``BENCHMARK.json`` and the files it names, found by name.

- a configuration ``<name>`` is ``bench/configs/<name>.json``;
- a traffic mix ``<name>`` is ``bench/traffic/<name>.json``;
- a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``,
  whose ``read(run)`` returns the value or None where it finds nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Mapping

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _json(kind: str, name: str) -> Dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as fh:
        return json.load(fh)


def config(name: str) -> Dict:
    return _json("configs", name)


def traffic(name: str) -> Dict:
    return _json("traffic", name)


def reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def workload(bench: Mapping, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def end_to_end(bench: Mapping, cell: str) -> List[Dict]:
    """The end-to-end metrics a cell reports."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: Mapping, cell: str) -> List[Dict]:
    """The per-layer metrics a cell reports: those that list it, and those
    that list no cell and move an end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if ("workloads" in m and cell in m["workloads"])
            or ("workloads" not in m and m["moves"] in reported)]

"""Finds what ``BENCHMARK.json`` names, by name:

* a configuration ``<config>`` is ``configs/<config>.json``, and its plain
  reference the module that the file's ``reference`` key names, beside it;
* a traffic mix ``<traffic>`` is ``traffic/<traffic>.json``;
* a cell ``<cell>`` keeps the limits of its correctness check, with the
  readings they were set from, in ``workloads/<cell>.json``;
* a per-layer metric ``<metric>`` is read by ``metrics/<metric>.py``,
  whose ``read(ctx)`` returns a number, or ``None`` where the trace holds
  nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return _json(REPO / "BENCHMARK.json")


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name):
    return _json(HERE / "configs" / f"{name}.json")


def reference(cfg):
    return _module(HERE / "configs" / cfg["reference"])


def traffic(name):
    return _json(HERE / "traffic" / f"{name}.json")


def limits(cell_name):
    return _json(HERE / "workloads" / f"{cell_name}.json")


def reader(metric_name):
    return _module(HERE / "metrics" / f"{metric_name}.py")


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell(name, bench=None):
    """Everything one run of cell ``name`` needs, as one dict."""
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    return {
        "name": name,
        "chips": w["chips"],
        "config": config(w["config"]),
        "traffic": traffic(w["traffic"]),
        "limits": limits(name)["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if applies(m, name)],
    }

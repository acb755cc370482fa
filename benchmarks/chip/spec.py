"""Finds what ``BENCHMARK.json`` names, by name:

* a configuration ``<config>`` is ``configs/<config>.json``, and its plain
  reference the module that the file's ``reference`` key names, beside it;
* the model behind a configuration is ``families/<family>.py``, by the
  file's ``family`` key: the only module of the harness that knows the
  model. It gives ``build(cfg, traffic, **overrides)``, the round through
  the program's own launcher (``.st``, ``.data`` and the jitted round
  ``.epoch.jitted``); ``make_inputs(key, cfg)``, the state in the
  program's layout and the client data; ``train_flops_per_sample(model)``;
  ``SPLIT``, the (module, attribute) of the program's split builder, which
  ``faults.py`` patches; and ``tiny(cfg)``, the configuration at CPU test
  size;
* a traffic mix ``<traffic>`` is ``traffic/<traffic>.json``;
* a cell ``<cell>`` keeps the limits of its correctness check, with the
  readings they were set from, in ``workloads/<cell>.json``;
* a per-layer metric ``<metric>`` is read by ``metrics/<metric>.py``,
  whose ``read(ctx)`` returns a number, or ``None`` where the trace holds
  nothing to read.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return _json(REPO / "BENCHMARK.json")


@functools.cache
def _module(path):
    """The module at ``path``, loaded once a process and registered as
    ``bench_<directory>_<stem>``, so that a fault can patch it by name."""
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name):
    return _json(HERE / "configs" / f"{name}.json")


def reference(cfg):
    return _module(HERE / "configs" / cfg["reference"])


def family(cfg):
    return _module(HERE / "families" / f"{cfg['family']}.py")


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

"""The correctness check of the benchmark, driven without a chip.

The rest of a run (``run.run_cell``, past the look for a chip) drives a
small configuration: the sound f32 program passes its cells' limits, and
each fault that a cell's numbers catch on every chip seed (the
``coverage`` its ``workloads/<cell>.json`` records), planted in the
program underneath (``faults.py``), makes ``correct`` come out false under
that cell's limits. The runs use ResNet-8 at width 8 on 8x8 inputs. The
control, the reference computed in the precision below the one a cell's
traffic states, fails every cell's limits with the cell's own model and 2
of its clients, 8 rows each.
"""
import json

import pytest

from chip import faults
from chip import run as R
from chip import spec as S

BENCH = S.benchmark()
CELLS = [S.cell(w["name"]) for w in BENCH["workloads"]]
SEED = 2 ** 33 + 5


def tiny(cell):
    """The cell on ResNet-8 at width 8, 8x8 inputs, 4 clients x 4 rows."""
    cfg = json.loads(json.dumps(cell["config"]))
    cfg["model"].update(depth=8, width=8, input_hw=8, num_classes=4)
    cfg["fleet"].update(num_clients=4, per_client_batch=4)
    return dict(cell, config=cfg)


def run(cell, fault=None):
    if fault is None:
        return R.run_cell(cell, SEED, 0.05, configure_jax=False)
    with faults.planted(fault):
        return R.run_cell(cell, SEED, 0.05, configure_jax=False)


def caught_on_chip(cell):
    cover = S.limits(cell["name"])["coverage"]
    return [f for f in faults.FAULTS
            if f in cover and cover[f].split("/")[0] == cover[f].split("/")[1]]


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_sound_run_is_correct(cell):
    res = run(tiny(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell["limits"])


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in CELLS for f in caught_on_chip(c)],
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_fault_makes_the_cell_incorrect(cell, fault):
    res = run(tiny(cell), fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_control_is_incorrect(cell):
    cfg = json.loads(json.dumps(cell["config"]))
    cfg["fleet"].update(num_clients=2, per_client_batch=8)
    small = dict(cell, config=cfg)
    ctl = R.reference_round(small, 3, mode=cell["traffic"]["control"])
    nums, _ = R.compare(ctl[0], ctl[2], R.reference_round(small, 3))
    ok, checks = R.judge(nums, cell["limits"])
    assert not ok, checks

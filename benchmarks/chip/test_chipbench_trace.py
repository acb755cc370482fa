"""The trace reductions on a hand-built two-chip trace
(``testdata/trace_small.json``), every number worked out by hand, and the
loader on a trace that the profiler records here."""
import json
import math
from pathlib import Path

import pytest

from chip import flops as F
from chip import spec as S
from chip import trace as TR

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def tr():
    return json.loads((HERE / "testdata" / "trace_small.json").read_text())


def test_window_span(tr):
    assert TR.span(tr, "bench.window") == (0, 1000)
    with pytest.raises(KeyError):
        TR.span(tr, "bench.nothing")


def test_busy_is_the_union_clipped_to_the_window(tr):
    # chip 0: [0,150] [200,350] [400,450] [600,800]; the op at 1200 is out
    # chip 1: [0,300] [350,450]
    assert TR.busy_ns(tr, 0, 1000) == {"/device:TPU:0": 550,
                                       "/device:TPU:1": 400}
    assert TR.busy_ns(tr, 100, 300)["/device:TPU:0"] == 150


def test_kernel_time_by_base_name(tr):
    got = TR.op_time_ns(tr, 0, 1000, lambda n: n == "sfpl_bn_act")
    assert got == {"/device:TPU:0": (100, 2), "/device:TPU:1": (0, 0)}
    assert TR.base_name("sfpl_bn_act.4") == "sfpl_bn_act"
    assert TR.base_name("all-to-all.2.1") == "all-to-all"


def test_exposed_collective(tr):
    coll = lambda n: n.startswith("all-to-all")
    # chip 0: all-to-all [200,300] under fusion [250,350] -> 50 exposed
    # chip 1: all-to-all [0,300], nothing else until 350 -> 300
    assert TR.exposed_ns(tr, 0, 1000, coll) == {"/device:TPU:0": 50,
                                                "/device:TPU:1": 300}


def test_subtract():
    assert TR.subtract([[0, 10]], []) == 10
    assert TR.subtract([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert TR.subtract([[0, 10], [20, 30]], [[5, 25]]) == 10


def test_top_ops_average_over_chips(tr):
    got = TR.top_ops(tr, 0, 1000)
    want = [["all-to-all", 200e-9], ["fusion", 150e-9],
            ["convolution", 100e-9], ["sfpl_bn_act", 50e-9]]
    assert [n for n, _ in got] == [n for n, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert math.isclose(a, b)


def test_idle_gaps_labelled_by_host_span(tr):
    # chip 0's gaps: [800,1000] (mid 900, in the wait), [450,600] (mid
    # 525, in the dispatch), then [150,200] and [350,400] under no span
    got = TR.idle_gaps(tr, 0, 1000)
    assert got == [["bench.round.wait", 200e-9],
                   ["bench.round.dispatch", 150e-9],
                   ["host.none", 50e-9], ["host.none", 50e-9]]


def _ctx(tr, name, rounds=1):
    from chip import run as R
    cell = S.cell(name)
    return R.Ctx(cell, tr, 0, 1000, rounds, F.peaks("TPU v5 lite"))


def test_readers_on_the_hand_trace(tr):
    ctx = _ctx(tr, "r56_c100.f32", rounds=2)
    assert math.isclose(S.reader("device.idle").read(ctx),
                        100 * (1 - (550 + 400) / 2 / 1000))
    # the BN epilogue kernel runs in bf16 traffic only
    ctx.traffic = dict(ctx.traffic, compute_dtype="bfloat16")
    ops, nbytes = ctx.family.bn_act_cost(ctx.config["model"],
                                         ctx.config["fleet"], "bfloat16")
    least = nbytes / 819e9
    assert math.isclose(S.reader("bn_act_roofline").read(ctx),
                        100 * least * 2 / 100e-9)
    # no sfpl_bn_act event: nothing to read, and no zero
    ctx.tr = {"devices": {"/device:TPU:0": [["fusion.1", 0, 10]]},
              "spans": tr["spans"]}
    assert S.reader("bn_act_roofline").read(ctx) is None
    ctx.tr = {"devices": {}, "spans": tr["spans"]}
    assert S.reader("device.idle").read(ctx) is None


def test_mfu_reader():
    ctx = _ctx({"devices": {}, "spans": []}, "r32_c10.f32", rounds=10)
    ctx.window_s = 2.0
    rate = 10 * 640 * 4 / 2.0
    want = (100 * 6 * ctx.family.forward_macs(ctx.config["model"]) * rate
            / 197e12)
    assert math.isclose(S.reader("model_step.mfu").read(ctx), want)


def test_load_reads_the_profilers_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.round.dispatch"):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    got = TR.load(str(tmp_path))
    names = [n for n, _, _ in got["spans"]]
    assert "bench.window" in names and "bench.round.dispatch" in names
    lo, hi = TR.span(got, "bench.window")
    assert hi > lo
    # the CPU has no device plane: nothing device-side to reduce
    assert got["devices"] == {}

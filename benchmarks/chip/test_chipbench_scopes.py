"""The scope reductions (``scopes.py``): ``scope_map`` on HLO text that the
CPU compiles from a tiny round with the program's phase and kind scopes,
the eight scope readers on a hand trace whose op names come from that map,
the ``@phase`` label of an idle gap, the map of the executable that a
traced run's window ran, and the readers that were there before, which
read on ``testdata/trace_small.json`` exactly what they read before the
scopes came."""
import json
import math
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chip import flops as F
from chip import gen
from chip import run as R
from chip import scopes as SC
from chip import spec as S
from chip import trace as TR

HERE = Path(__file__).resolve().parent
PHASES = ("client_fwd", "shuffle", "server", "server_opt", "client_update",
          "fedavg")


def tiny_round(w, v, x, y):
    """The round's phases, as siblings, around a one-layer client and a
    one-layer server with a batch norm."""
    with jax.named_scope("sfpl.client_fwd"):
        a = jnp.tanh(x @ v)
    with jax.named_scope("sfpl.shuffle"):
        y = y[::-1]

    def loss(w, a):
        with jax.named_scope("sfpl.shuffle"):
            a = a[::-1]
        with jax.named_scope("sfpl.server"):
            with jax.named_scope("conv"):
                h = a @ w
            with jax.named_scope("bn"):
                h = (h - h.mean(0)) * jax.lax.rsqrt(h.var(0) + 1e-5)
            return jnp.mean((h - y[:, None]) ** 2)

    l, (gw, ga) = jax.value_and_grad(loss, argnums=(0, 1))(w, a)
    with jax.named_scope("sfpl.server_opt"):
        w = w - 0.1 * gw
    with jax.named_scope("sfpl.client_update"):
        _, vjp = jax.vjp(lambda v_: jnp.tanh(x @ v_), v)
        v = v - 0.1 * vjp(ga)[0]
    with jax.named_scope("sfpl.fedavg"):
        v = v * jnp.mean(v)
    return w, v, l


@pytest.fixture(scope="module")
def hlo():
    args = (jnp.ones((8, 4)), jnp.ones((6, 8)), jnp.ones((16, 6)),
            jnp.ones((16,)))
    return jax.jit(tiny_round).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def scopes(hlo):
    return SC.from_text(hlo)


def by_scope(scopes):
    """{(phase, direction, kind): one instruction name} of the map."""
    out = {}
    for name in sorted(scopes["ops"]):
        op = scopes["ops"][name]
        out.setdefault((*(SC.phase_of(op) or (None, None)),
                        SC.kind_of(op)), name)
    return out


def test_scope_map_of_a_compiled_round(hlo, scopes):
    assert scopes["module"] == "jit_tiny_round" == SC.module_name(hlo)
    ops = scopes["ops"]
    phases = {SC.phase_of(op) for op in ops.values()}
    for p in PHASES:
        assert (p, "fwd") in phases, p
    # autodiff's transposes: the server backward and the route back
    assert ("server", "bwd") in phases and ("shuffle", "bwd") in phases
    kinds = {(SC.phase_of(op), SC.kind_of(op)) for op in ops.values()}
    for d in ("fwd", "bwd"):
        assert (("server", d), "conv") in kinds
        assert (("server", d), "bn") in kinds
    # a fusion is named by its root's scope
    fused = [n for n in ops if "fusion" in n and SC.phase_of(ops[n])]
    assert fused
    # every instruction line with an op_name is in the map, under it
    assert all(ops[n] for n in ops)
    assert len(ops) >= hlo.count("op_name=")


@pytest.mark.parametrize("op_name,phase,kind", [
    ("jit(f)/while/body/closed_call/jvp(sfpl.server)/conv/mul",
     ("server", "fwd"), "conv"),
    ("jit(f)/while/body/closed_call/transpose(jvp(sfpl.server))/bn/mul",
     ("server", "bwd"), "bn"),
    ("jit(f)/while/body/sfpl.client_update/"
     "vmap(transpose(sfpl.client_update))/vmap(jvp(conv))",
     ("client_update", "bwd"), "conv"),
    ("jit(f)/while/body/sfpl.client_fwd/vmap(bn)/jit(_var)",
     ("client_fwd", "fwd"), "bn"),
    ("jit(f)/sfpl.fedavg/reduce_sum", ("fedavg", "fwd"), None),
    ("jit(f)/while/body/add", None, None),
    ("jit(f)/sfpl.server/sfpl.shuffle/add", None, None),
])
def test_phase_and_kind_of_an_op_name(op_name, phase, kind):
    assert SC.phase_of(op_name) == phase
    assert SC.kind_of(op_name) == kind


def test_scope_map_gives_compiler_copies_a_scope():
    text = "\n".join([
        "HloModule jit_r, is_scheduled=true",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%p)",
        "  %copy-done = f32[8]{0} copy-done(%copy-start)",
        "  %fusion.1 = f32[8]{0} fusion(%copy-done), kind=kLoop, "
        "calls=%f, metadata={op_name=\"jit(r)/sfpl.server/conv/mul\" "
        "stack_frame_id=1}",
        "  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) "
        "copy-start(%fusion.1)",
        "  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)",
        "  %lone = f32[8]{0} negate(%p)",
        "  ROOT %tuple = (f32[8]{0}, f32[8]{0}) tuple(%copy-done.1, %lone)",
        "}"])
    got = SC.scope_map(text)
    # a prefetch takes its consumer's scope, an eviction its producer's
    for n in ("copy-start", "copy-done", "copy-start.1", "copy-done.1",
              "fusion.1"):
        assert got[n] == "jit(r)/sfpl.server/conv/mul", n
    # an op that reaches no op_name either way has none
    assert "lone" not in got
    assert SC.module_name(text) == "jit_r"


def _ctx(tr, name="r32_c10.f32", rounds=1, scopes=None, lo=0, hi=1000):
    """A reader context as ``run.run_cell`` makes it, with ``scopes`` as
    the run's scope map (``None``: none could be made)."""
    ctx = R.Ctx(S.cell(name), tr, lo, hi, rounds, F.peaks("TPU v5 lite"))
    ctx.scopes = scopes
    return ctx


def hand_trace(scopes):
    """Two chips; on each, one op of every (phase, direction, kind) of
    the map, op ``i`` at ``1000 i`` ns for ``10 (i + 1)`` ns on chip 0 and
    twice that on chip 1; besides, per chip, a ``while`` around them and
    an op the map lacks."""
    keys = sorted(by_scope(scopes), key=str)
    devices = {}
    for c, dev in enumerate(("/device:TPU:0", "/device:TPU:1")):
        ev = [["while.1", 0, 10 ** 6]]
        for i, key in enumerate(keys):
            name = by_scope(scopes)[key]
            text = f"%{name} = f32[8]{{0}} fusion(%p)"
            ev.append([text if c else name, 1000 * i, 10 * (i + 1) * (c + 1)])
        ev.append(["not-in-the-map.7", 90000, 500])
        devices[dev] = ev
    return {"devices": devices, "spans": []}, keys


def expected_ns(keys, keep):
    """Summed ns of the hand trace's ops whose key ``keep`` accepts,
    averaged over its two chips (factor 1.5)."""
    return 1.5 * sum(10 * (i + 1) for i, k in enumerate(keys) if keep(*k))


@pytest.mark.parametrize("metric,keep", [
    ("round.client_fwd.ms", lambda p, d, k: p == "client_fwd"),
    ("round.shuffle.ms", lambda p, d, k: p == "shuffle"),
    ("round.server_fwd.ms", lambda p, d, k: (p, d) == ("server", "fwd")),
    ("round.server_bwd.ms", lambda p, d, k: (p, d) == ("server", "bwd")
     or p == "server_opt"),
    ("round.client_update.ms", lambda p, d, k: p == "client_update"),
    ("round.fedavg.ms", lambda p, d, k: p == "fedavg"),
])
def test_phase_readers_on_a_hand_trace(scopes, metric, keep):
    tr, keys = hand_trace(scopes)
    ns = expected_ns(keys, keep)
    assert ns > 0
    got = S.reader(metric).read(_ctx(tr, rounds=4, scopes=scopes,
                                     hi=10 ** 6))
    assert math.isclose(got, ns / 1e6 / 4)
    # no map, or a program without the scope: nothing to read
    assert S.reader(metric).read(_ctx(tr, rounds=4, hi=10 ** 6)) is None
    bare = {"module": scopes["module"], "ops": {}}
    assert S.reader(metric).read(_ctx(tr, rounds=4, scopes=bare,
                                      hi=10 ** 6)) is None


@pytest.mark.parametrize("metric,kind", [("model.conv.share", "conv"),
                                         ("model.bn.share", "bn")])
def test_kind_readers_on_a_hand_trace(scopes, metric, kind):
    tr, keys = hand_trace(scopes)
    ctx = _ctx(tr, scopes=scopes, hi=10 ** 6)
    busy = ctx.busy_ns()
    mean_busy = sum(busy.values()) / 2
    want = 100 * expected_ns(keys, lambda p, d, k: k == kind) / mean_busy
    assert 0 < want < 100
    assert math.isclose(S.reader(metric).read(ctx), want)
    assert S.reader(metric).read(_ctx(tr, hi=10 ** 6)) is None


def test_the_six_phases_cover_the_scoped_ops(scopes):
    tr, keys = hand_trace(scopes)
    ctx = _ctx(tr, rounds=2, scopes=scopes, hi=10 ** 6)
    total = sum(S.reader(m).read(ctx) for m in (
        "round.client_fwd.ms", "round.shuffle.ms", "round.server_fwd.ms",
        "round.server_bwd.ms", "round.client_update.ms", "round.fedavg.ms"))
    assert math.isclose(total, expected_ns(
        keys, lambda p, d, k: p is not None) / 1e6 / 2)
    # left out of every phase: the ops without one (the map's and the one
    # it lacks), never the loop
    got = SC.scope_ns(tr, 0, 10 ** 6, scopes)
    ops = by_scope(scopes)
    assert {b for (p, _, _, b) in got if p is None} == {
        TR.base_name(ops[k]) for k in keys if k[0] is None} | {
        "not-in-the-map"}
    assert "while" not in {b for (_, _, _, b) in got}


def test_idle_gap_named_by_the_phase_that_ends_it(scopes):
    ops = by_scope(scopes)
    srv = ops[("server", "fwd", "conv")]
    fed = ops[("fedavg", "fwd", None)]
    tr = {"devices": {"/device:TPU:0": [
        [fed, 0, 100], [srv, 300, 100], [fed, 450, 50],
        ["not-in-the-map.1", 700, 100]]},
        "spans": [["bench.window", 0, 1000], ["bench.round.wait", 90, 250],
                  ["bench.round.dispatch", 420, 40]]}
    # gaps: [100,300] ends at the server's op, [800,1000] at no op,
    # [500,700] at an op the map lacks, [400,450] at the FedAvg
    got = SC.idle_gaps(tr, 0, 1000, scopes)
    assert got == [
        ["bench.round.wait@sfpl.server", 200e-9],
        ["host.none@none", 200e-9],
        ["host.none@none", 200e-9],
        ["bench.round.dispatch@sfpl.fedavg", 50e-9]]
    # the same gaps, in the same order, as the harness names them
    assert [[n.split("@")[0], s] for n, s in got] == TR.idle_gaps(
        tr, 0, 1000)


def tiny_cell():
    """``r32_c10.f32`` at its family's CPU test size."""
    cell = S.cell("r32_c10.f32")
    return dict(cell, config=S.family(cell["config"]).tiny(cell["config"]))


def test_the_map_is_that_of_the_executable_the_window_ran(monkeypatch):
    """A traced run maps the executable that its window ran: the jitted
    round lowered on the live state and data after the window, the same
    instructions under the same scopes as the round's first call, placed
    as ``run.Program.start`` places it; the round is built once."""
    cell, seed = tiny_cell(), 2 ** 33 + 5
    fam = S.family(cell["config"])
    builds, maps = [], []
    build, of_program = fam.build, SC.of_program

    def counted(*a, **k):
        builds.append(a)
        return build(*a, **k)

    def kept(prog):
        st, data = fam.make_inputs(gen.seed_key(seed), prog.cfg)
        first = prog.fn.lower(prog.keys[0],
                              jax.device_put(st, prog.st_layout),
                              jax.device_put(data, prog.data_layout))
        maps.append((of_program(prog),
                     SC.from_text(first.compile().as_text())))
        return maps[-1][0]
    monkeypatch.setattr(fam, "build", counted)
    monkeypatch.setattr(SC, "of_program", kept)
    res = R.run_cell(cell, seed, 0.05, trace=True,
                     peaks=F.peaks("TPU v5 lite"), configure_jax=False)
    assert res["correct"], res["checks"]
    assert len(builds) == 1
    [(got, first)] = maps
    assert got == first
    phases = {SC.phase_of(op) for op in got["ops"].values()}
    assert ("server", "bwd") in phases and ("fedavg", "fwd") in phases
    # the CPU has no device plane: the scope readers read nothing
    assert "round.server_fwd.ms" not in res["metrics"]


def test_no_map_where_the_round_cannot_be_built():
    """A program whose executable cannot be made (here, one freed) gives
    no map and raises nothing, and the scope readers read nothing."""
    freed = types.SimpleNamespace(fn=None, args=lambda: ())
    assert SC.of_program(freed) is None
    ctx = R.Ctx(tiny_cell(), {"devices": {}, "spans": []}, 0, 1, 1,
                F.peaks("TPU v5 lite"))
    assert ctx.scopes is None
    assert S.reader("model.conv.share").read(ctx) is None


@pytest.fixture(scope="module")
def small():
    return json.loads((HERE / "testdata" / "trace_small.json").read_text())


def test_earlier_readers_read_as_before(small, scopes):
    """The numbers these readers gave on this trace before the scopes
    came, a scope map given or not."""
    for sc in (None, scopes):
        ctx = _ctx(small, "r56_c100.f32", rounds=2, scopes=sc)
        assert S.reader("device.idle").read(ctx) == 52.5
        assert S.reader("model_step.mfu").read(ctx) == 4902475.3705583755
        ctx.traffic = dict(ctx.traffic, compute_dtype="bfloat16")
        assert S.reader("bn_act_roofline").read(ctx) == 34056480.82051282
    assert TR.top_ops(small, 0, 1000) == [
        ["all-to-all", 2e-07], ["fusion", 1.5e-07], ["convolution", 1e-07],
        ["sfpl_bn_act", 5e-08]]
    assert TR.idle_gaps(small, 0, 1000) == [
        ["bench.round.wait", 2e-07], ["bench.round.dispatch", 1.5e-07],
        ["host.none", 5e-08], ["host.none", 5e-08]]
    assert TR.busy_ns(small, 0, 1000) == {"/device:TPU:0": 550,
                                          "/device:TPU:1": 400}
    # nothing of the scopes in it: the new readers stay silent
    for m in ("round.server_fwd.ms", "model.conv.share"):
        assert S.reader(m).read(_ctx(small, scopes=scopes)) is None

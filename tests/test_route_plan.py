"""Route-plan collector: the plan-based exchange must (a) reproduce the
dense oracle bit-for-bit — forward AND gradients — across collector modes,
flush structures, and pipelines, (b) lower to exactly ONE all_to_all per
exchange direction with no sorts on the exchange path, and (c) never let
an overflowing row clobber an in-capacity row at undersized slack.

Multi-shard behavior runs in a subprocess with 8 forced host devices (the
device count must be fixed before jax initializes); structural jaxpr
inspection and host-side plan math run in-process.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.core import engine_dist as ED

WORKER_PLAN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.collector_dist import (
    build_route_plans, exact_pair_cap, make_balanced_perm, pair_capacity,
    plan_shuffle, shuffle_shard_map)

from repro.core.engine_dist import make_data_mesh
mesh = make_data_mesh(8)
N, D = 64, 5
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (N, D))
xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data")))

# dense plans for a balanced perm: exact capacity, no overflow accounting,
# zero slack padding (the send buffer is exactly the b-row slab)
bperm = make_balanced_perm(jax.random.fold_in(key, 2), N, 8)
cap = exact_pair_cap(N, 8)
plans = jax.jit(lambda p: build_route_plans(p, 8, cap=cap,
                                            may_drop=False))(bperm)
fwd, bwd = plans
assert fwd.dense and bwd.dense
assert fwd.overflow is None
assert fwd.send_idx.shape == (8, N // 8), fwd.send_idx.shape
out = jax.jit(lambda x, pl: plan_shuffle(x, pl, mesh=mesh))(xs, plans)
np.testing.assert_allclose(np.asarray(out),
                           np.asarray(x)[np.asarray(bperm)], rtol=1e-6)
print("dense-plan OK")

# autodiff through plan_shuffle routes gradients by the BACKWARD plan
w = jnp.arange(float(N))[:, None]
g = jax.grad(lambda v: jnp.sum(
    plan_shuffle(v, plans, mesh=mesh) * w))(xs)
inv = np.argsort(np.asarray(bperm))
np.testing.assert_allclose(np.asarray(g),
                           np.tile(inv[:, None], (1, D)), rtol=1e-6)
print("plan-grad OK")

# kernelized gathers agree with the jnp path, forward and backward
out_k = jax.jit(lambda x, pl: plan_shuffle(x, pl, mesh=mesh,
                                           use_kernel=True))(xs, plans)
np.testing.assert_allclose(np.asarray(out_k), np.asarray(out), rtol=1e-6)
g_k = jax.grad(lambda v: jnp.sum(
    plan_shuffle(v, plans, mesh=mesh, use_kernel=True) * w))(xs)
np.testing.assert_allclose(np.asarray(g_k), np.asarray(g), rtol=1e-6)
print("plan-kernel OK")

# overflow NO-CLOBBER regression at undersized slack: the rolled perm
# routes all b=8 rows of each source slab to one destination pair against
# capacity 2. Every output row must be EITHER exact (the in-capacity rows
# — the old exchange corrupted one of these per overflow by writing
# through slot cap-1) OR zero (the overflowing rows), and the zero count
# must equal exactly the overflow: 6 dropped rows per shard, never more.
adv = jnp.roll(jnp.arange(N), -8)
assert pair_capacity(N, 8, 1.0) == 2
bad = np.asarray(shuffle_shard_map(xs, adv, mesh=mesh, slack=1.0))
oracle = np.asarray(x)[np.asarray(adv)]
zero = np.abs(bad).sum(axis=1) == 0
np.testing.assert_allclose(bad[~zero], oracle[~zero], rtol=1e-6)
assert int(zero.sum()) == 8 * 6, int(zero.sum())
print("no-clobber OK")

# (LAST: the deliberately-triggered in-graph callback error surfaces
# asynchronously and would poison later collectives) — a balanced-mode
# collector with check_capacity=True must RAISE on a mis-declared perm
# (identity: diagonal load b=8 vs exact cap 1), not silently misroute:
# the exact-capacity plan keeps overflow accounting when checking is on.
from repro.core import round as RD
coll = RD.MeshAllToAll(mesh=mesh, num_clients=8, check_capacity=True)
try:
    r = jax.jit(lambda v, p: coll.permute(v, p))(xs, jnp.arange(N))
    r.block_until_ready()
    raise SystemExit("balanced check_capacity did not raise")
except SystemExit:
    raise
except Exception as e:
    assert "capacity exceeded" in str(e) or "CpuCallback" in str(e), e
    print("balanced-check OK")
"""

WORKER_ORACLE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as E
from repro.core import engine_dist as ED
from repro.data import make_synthetic_cifar, partition_positive_labels
from repro.models import resnet as R
from repro.optim import sgd_momentum

V = 8
cfg = R.ResNetConfig(depth=8, num_classes=V, width=8)
key = jax.random.PRNGKey(0)
tx, ty, ex, ey = make_synthetic_cifar(key, num_classes=V,
                                      train_per_class=16, test_per_class=8,
                                      hw=8)
data = partition_positive_labels(tx, ty, V)
split = E.make_resnet_split(cfg)
opt = sgd_momentum(0.05, momentum=0.9, weight_decay=5e-4)
st0 = E.init_dcml_state(jax.random.PRNGKey(0), lambda k: R.init(k, cfg),
                        V, opt, opt)
st0_host = jax.tree_util.tree_map(np.asarray, st0)
mesh = ED.make_data_mesh(8)
data_sh = ED.shard_client_data(data, mesh)

def fresh():
    return ED.shard_dcml_state(
        jax.tree_util.tree_map(jnp.asarray, st0_host), mesh)

ke = jax.random.PRNGKey(1)
single = jax.jit(lambda k, s, a: E.sfpl_epoch(
    k, s, data, split, opt, opt, num_clients=V, batch_size=8, alpha=a),
    static_argnums=2)

# plan-path parity vs the DenseTake oracle: forward loss trajectories AND
# the gradient trajectories (client params after the epoch reflect the
# full shuffle -> server grad -> route-back round trip) for every
# mode x alpha x pipeline cell
for alpha in (0.25, 1.0):
    st_ref = jax.tree_util.tree_map(jnp.asarray, st0_host)
    st_ref, l_ref = single(ke, st_ref, alpha)
    l_ref = np.asarray(l_ref)
    for mode in ("balanced", "uniform"):
        for pipe in ("sync", "double_buffered"):
            ep = ED.make_sfpl_epoch_sharded(
                split, opt, opt, data_sh, mesh=mesh, num_clients=V,
                batch_size=8, alpha=alpha, collector_mode=mode,
                collector_pipeline=pipe)
            st, l = ep(ke, fresh())
            d = float(np.abs(np.asarray(l) - l_ref).max())
            assert d <= 1e-5, (alpha, mode, pipe, d)
            for a, b in zip(jax.tree_util.tree_leaves(st_ref["cp"]),
                            jax.tree_util.tree_leaves(st["cp"])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5)
            print(f"oracle-parity OK alpha={alpha} mode={mode} "
                  f"pipe={pipe} ({d:.2e})")
print("all-oracle-parity OK")
"""


def _run_worker(tmp_path, name, src, timeout):
    script = tmp_path / name
    script.write_text(src)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


@pytest.mark.parametrize("_", [0])
def test_plan_exchange_semantics(_, tmp_path):
    """Dense plans, plan gradients, kernelized gathers, and the overflow
    no-clobber fix at 8 forced host devices."""
    out = _run_worker(tmp_path, "worker_plan.py", WORKER_PLAN, 420)
    for token in ("dense-plan OK", "plan-grad OK", "plan-kernel OK",
                  "no-clobber OK", "balanced-check OK"):
        assert token in out, out


@pytest.mark.parametrize("_", [0])
def test_plan_path_matches_dense_oracle(_, tmp_path):
    """Forward + gradient trajectory parity vs the DenseTake oracle across
    mode x alpha x pipeline at 8 forced host devices (<= 1e-5)."""
    out = _run_worker(tmp_path, "worker_oracle.py", WORKER_ORACLE, 580)
    assert "all-oracle-parity OK" in out, out


def test_plan_exchange_is_one_collective_per_direction():
    """Jaxpr inspection: the plan exchange lowers to exactly ONE
    all_to_all forward, exactly TWO for forward+backward (one per
    direction) — no pos/valid collectives — and neither the exchange nor
    the plan build contains a single sort."""
    from repro.core.collector_dist import (build_route_plans,
                                           exact_pair_cap, plan_shuffle)
    mesh = ED.make_data_mesh(1)
    n = 16
    x = jnp.zeros((n, 3))
    perm = jax.random.permutation(jax.random.PRNGKey(0), n)
    cap = exact_pair_cap(n, 1)
    plans = build_route_plans(perm, 1, cap=cap, may_drop=False)

    fwd_jaxpr = str(jax.make_jaxpr(
        lambda v, pl: plan_shuffle(v, pl, mesh=mesh))(x, plans))
    assert fwd_jaxpr.count("all_to_all") == 1, fwd_jaxpr
    assert fwd_jaxpr.count("sort[") == 0, fwd_jaxpr

    grad_jaxpr = str(jax.make_jaxpr(lambda v, pl: jax.grad(
        lambda u: plan_shuffle(u, pl, mesh=mesh).sum())(v))(x, plans))
    assert grad_jaxpr.count("all_to_all") == 2, grad_jaxpr
    assert grad_jaxpr.count("sort[") == 0, grad_jaxpr

    plan_jaxpr = str(jax.make_jaxpr(
        lambda p: build_route_plans(p, 1, cap=cap, may_drop=False))(perm))
    assert plan_jaxpr.count("sort[") == 0, plan_jaxpr
    assert plan_jaxpr.count("all_to_all") == 0, plan_jaxpr


def test_dense_plan_allocates_no_pos_valid_buffers():
    """The balanced dense path carries ONLY the two gather index maps:
    no position array, no validity mask, no overflow counter, and the
    send buffer has zero slack padding (n_shards * cap == b)."""
    from repro.core.collector_dist import (build_route_plans,
                                           exact_pair_cap,
                                           make_balanced_perm)
    n, s = 64, 4
    perm = make_balanced_perm(jax.random.PRNGKey(0), n, s)
    cap = exact_pair_cap(n, s)
    fwd, bwd = build_route_plans(perm, s, cap=cap, may_drop=False)
    for plan in (fwd, bwd):
        assert plan.dense
        assert plan.overflow is None
        assert s * plan.cap == n // s          # zero slack padding
        leaves = jax.tree_util.tree_leaves(plan)
        assert len(leaves) == 2, leaves        # send_idx + recv_idx only
        # and the plan reproduces the oracle on one shard-slab layout
        x = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
    mesh = ED.make_data_mesh(1)
    from repro.core.collector_dist import plan_shuffle
    plans1 = build_route_plans(perm, 1, cap=exact_pair_cap(n, 1),
                               may_drop=False)
    out = jax.jit(lambda v, pl: plan_shuffle(v, pl, mesh=mesh))(x, plans1)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(x)[np.asarray(perm)])


def test_exact_pair_cap_matches_deterministic_loads():
    """exact_pair_cap == the measured max pair load of (grouped) balanced
    permutations — the invariant the dense path's drop-freeness rests on."""
    from repro.core.collector_dist import (exact_pair_cap, max_pair_load,
                                           make_balanced_perm,
                                           make_grouped_balanced_perm)
    assert exact_pair_cap(64, 8) == 1
    perm = make_balanced_perm(jax.random.PRNGKey(0), 64, 8)
    assert max_pair_load(perm, 8) == exact_pair_cap(64, 8)
    for rows in ([32, 32], [16, 16, 16, 16], [8] * 8):
        gperm = make_grouped_balanced_perm(jax.random.PRNGKey(1), 64, 8,
                                           rows)
        assert max_pair_load(gperm, 8) <= exact_pair_cap(64, 8, rows)
    # in-slab groups load the full slab on the diagonal
    assert exact_pair_cap(64, 8, [8] * 8) == 8


WORKER_SUBMESH_JAXPR = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import jax, jax.numpy as jnp, numpy as np
from repro.core import round as RD
from repro.core.round import streamed_shuffle

from repro.core.engine_dist import make_data_mesh
mesh = make_data_mesh(8)
coll = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                            mode="balanced", submesh=True)
n, d = 64, 3
b = n // 8
perm = coll.make_perm(jax.random.PRNGKey(0), n)
prep = coll.prepare(perm, n)
groups = len(coll.group_bounds(n))
assert groups == 4

# every per-group plan pair is DENSE: 2 index leaves, no overflow
# operand, slice-local capacity with zero slack (S * cap == b)
for fwd, bwd in prep.plans:
    for plan in (fwd, bwd):
        assert plan.slice_size == 2, plan.slice_size
        assert plan.dense and plan.overflow is None
        assert not plan.may_drop
        assert plan.slice_size * plan.cap == b, (plan.cap, b)
        assert len(jax.tree_util.tree_leaves(plan)) == 2
print("submesh-dense-plan OK")

x = jnp.zeros((n, d))
fwd_jaxpr = str(jax.make_jaxpr(
    lambda v, pr: streamed_shuffle(coll, pr, n, lambda g: v))(x, prep))
assert fwd_jaxpr.count("all_to_all") == groups, fwd_jaxpr
assert fwd_jaxpr.count("sort[") == 0, fwd_jaxpr
# zero slack padding at every grouped flush: each collective moves the
# per-shard (S=2, cap=4, d) bucket — exactly the b-row slab, no b_g + 1
shapes = re.findall(r"f32\[([\d,]+)\](?:\{[^}]*\})? = all_to_all", fwd_jaxpr)
assert len(shapes) == groups, fwd_jaxpr
for shape in shapes:
    s_, cap_, d_ = map(int, shape.split(","))
    assert (s_, cap_ * s_, d_) == (2, b, d), shape
print("submesh-one-collective OK")

back_jaxpr = str(jax.make_jaxpr(
    lambda v, pr: coll.route_back(v, pr, n))(x, prep))
assert back_jaxpr.count("all_to_all") == groups, back_jaxpr
assert back_jaxpr.count("sort[") == 0, back_jaxpr
print("submesh-route-back OK")
"""


@pytest.mark.parametrize("_", [0])
def test_submesh_stream_is_one_collective_per_group(_, tmp_path):
    """Jaxpr inspection at 8 forced host devices: the sub-mesh streamed
    path emits exactly ONE all_to_all per flush group (and per group on
    the route-back), zero sorts, and every per-group plan is dense —
    2 index leaves, no overflow operand, zero slack padding."""
    out = _run_worker(tmp_path, "worker_submesh_jaxpr.py",
                      WORKER_SUBMESH_JAXPR, 420)
    for token in ("submesh-dense-plan OK", "submesh-one-collective OK",
                  "submesh-route-back OK"):
        assert token in out, out


def test_submesh_plan_builder_is_sortfree():
    """The sub-mesh plan builder needs no mesh: structural checks run
    in-process. Plans are dense at the slice-local exact capacity and the
    builder's jaxpr contains no sort and no collective."""
    from repro.core.collector_dist import (build_submesh_route_plans,
                                           make_balanced_perm)
    n_shards, S, b = 8, 2, 8
    n_g = S * b
    sub = make_balanced_perm(jax.random.PRNGKey(0), n_g, S)
    fwd, bwd = build_submesh_route_plans(sub, 3, n_shards, S)
    for plan in (fwd, bwd):
        assert plan.dense and plan.slice_size == S
        assert plan.overflow is None and not plan.may_drop
        assert plan.cap == b // S                 # exact slice-local cap
        assert plan.send_idx.shape == (n_shards, b)
        assert len(jax.tree_util.tree_leaves(plan)) == 2
    # the embedded rows live exactly at the owning slice [3*S, 4*S)
    send = np.asarray(fwd.send_idx)
    outside = np.ones(n_shards, bool)
    outside[3 * S:4 * S] = False
    assert (send[outside] == 0).all()
    assert (send[~outside] != 0).any()
    jaxpr = str(jax.make_jaxpr(
        lambda p: build_submesh_route_plans(p, 3, n_shards, S))(sub))
    assert jaxpr.count("sort[") == 0, jaxpr
    assert jaxpr.count("all_to_all") == 0, jaxpr


def test_uniform_auto_slack_probing_is_cached():
    """The 16 host-side probe permutations run once per distinct
    (n, shards, groups, probes, seed, margin) key — re-tracing a jitted
    epoch must not repeat them."""
    from repro.core.collector_dist import (_uniform_auto_slack_cached,
                                           uniform_auto_slack)
    _uniform_auto_slack_cached.cache_clear()
    a = uniform_auto_slack(96, 4, [48, 48])
    before = _uniform_auto_slack_cached.cache_info()
    assert before.misses == 1
    b = uniform_auto_slack(96, 4, [48, 48])
    after = _uniform_auto_slack_cached.cache_info()
    assert a == b
    assert after.hits == before.hits + 1
    assert after.misses == before.misses
    # distinct keys still probe
    uniform_auto_slack(96, 4)
    assert _uniform_auto_slack_cached.cache_info().misses == 2


def test_plan_payload_bytes_golden_across_wire_dtypes():
    """Golden wire-byte accounting at the bench cut width (D=512) for all
    three plan families. The numbers are EXACT: 64 exchanged rows cost
    64*512*4 f32 bytes, half that at bf16, and 64*(512+4) at a quantized
    wire (one byte per element plus the 4 bitcast f32-scale lanes packed
    into the payload) — below bf16 and ~0.252x of f32, inside the 0.3x
    budget the quantized exchange is sized against."""
    from repro.core.collector_dist import (build_route_plans,
                                           build_submesh_route_plans,
                                           exact_pair_cap,
                                           make_balanced_perm,
                                           plan_payload_bytes)
    from repro.core.round import StreamingAllToAll
    n, s, d = 64, 8, 512
    perm = make_balanced_perm(jax.random.PRNGKey(0), n, s)
    whole, _ = build_route_plans(perm, s, cap=exact_pair_cap(n, s),
                                 may_drop=False)
    sub = make_balanced_perm(jax.random.PRNGKey(1), 16, 2)
    submesh, _ = build_submesh_route_plans(sub, 3, s, 2)

    class _FakeMesh:
        axis_names = ("data",)
        devices = np.empty((8,), dtype=object)

    coll = StreamingAllToAll(mesh=_FakeMesh(), num_clients=8, alpha=0.5)
    prep = coll.prepare(coll.make_perm(jax.random.PRNGKey(0), n), n)
    grouped = [p for p, _ in prep.plans]

    # every plan family exchanges 64 (padded) rows at this layout, so the
    # golden bytes coincide; what the test pins is the per-dtype row cost
    golden = {None: 131072, "float32": 131072, "bfloat16": 65536,
              "int8": 33024, "float8_e4m3": 33024}
    for plan in [whole, submesh] + grouped:
        for wire, want in golden.items():
            got = plan_payload_bytes(plan, d, 4, wire_dtype=wire)
            assert got == want, (wire, got, want)
    b32 = golden["float32"]
    assert golden["int8"] < golden["bfloat16"]          # beats bf16
    assert golden["int8"] <= 0.3 * b32                  # 0.252x of f32
    assert golden["int8"] == 64 * (d + 4)               # rows + scale lanes
    # per-row accounting scales with the feature width, not the plan
    assert plan_payload_bytes(whole, 16, 4, wire_dtype="int8") == 64 * 20


def test_quantized_exchange_is_one_collective_in_wire_dtype():
    """Jaxpr proof for the quantized path: the int8-wire exchange still
    lowers to exactly ONE all_to_all forward (TWO for forward+backward
    when the backward leg is also quantized), zero sorts, and the
    payload operand itself is in the wire dtype with the packed scale
    lanes as trailing columns — ``i8[S, cap, d+4]``."""
    import re

    from repro.core.collector_dist import (build_route_plans,
                                           exact_pair_cap, plan_shuffle)
    # an all_to_all's (dtype, shape), past any {V:...} varying-axes mark
    a2a_ops = r"(\w+)\[([\d,]+)\](?:\{[^}]*\})? = all_to_all"
    mesh = ED.make_data_mesh(1)
    n, d = 16, 3
    x = jnp.zeros((n, d))
    perm = jax.random.permutation(jax.random.PRNGKey(0), n)
    plans = build_route_plans(perm, 1, cap=exact_pair_cap(n, 1),
                              may_drop=False)

    fwd_jaxpr = str(jax.make_jaxpr(lambda v, pl: plan_shuffle(
        v, pl, mesh=mesh, wire_dtype="int8"))(x, plans))
    assert fwd_jaxpr.count("all_to_all") == 1, fwd_jaxpr
    assert fwd_jaxpr.count("sort[") == 0, fwd_jaxpr
    ops = re.findall(a2a_ops, fwd_jaxpr)
    assert ops == [("i8", f"1,{n},{d + 4}")], ops

    # quantized fwd + quantized bwd: both payloads in the wire dtype
    grad_jaxpr = str(jax.make_jaxpr(lambda v, pl: jax.grad(
        lambda u: plan_shuffle(u, pl, mesh=mesh, wire_dtype="int8",
                               wire_dtype_bwd="int8").sum())(v))(x, plans))
    assert grad_jaxpr.count("all_to_all") == 2, grad_jaxpr
    assert grad_jaxpr.count("sort[") == 0, grad_jaxpr
    ops = re.findall(a2a_ops, grad_jaxpr)
    assert ops == [("i8", f"1,{n},{d + 4}")] * 2, ops

    # default exact backward: the VJP collective stays f32
    grad_exact = str(jax.make_jaxpr(lambda v, pl: jax.grad(
        lambda u: plan_shuffle(u, pl, mesh=mesh,
                               wire_dtype="int8").sum())(v))(x, plans))
    ops = re.findall(a2a_ops, grad_exact)
    assert ("f32", f"1,{n},{d}") in ops, ops


WORKER_SUBMESH_QUANT_JAXPR = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import jax, jax.numpy as jnp, numpy as np
from repro.core import round as RD
from repro.core.round import streamed_shuffle

from repro.core.engine_dist import make_data_mesh
mesh = make_data_mesh(8)
coll = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                            mode="balanced", submesh=True,
                            wire_dtype="int8", wire_dtype_bwd="int8")
n, d = 64, 3
b = n // 8
perm = coll.make_perm(jax.random.PRNGKey(0), n)
prep = coll.prepare(perm, n)
groups = len(coll.group_bounds(n))
assert groups == 4

x = jnp.zeros((n, d))
fwd_jaxpr = str(jax.make_jaxpr(
    lambda v, pr: streamed_shuffle(coll, pr, n, lambda g: v))(x, prep))
assert fwd_jaxpr.count("all_to_all") == groups, fwd_jaxpr
assert fwd_jaxpr.count("sort[") == 0, fwd_jaxpr
# one collective per flush group, payload IN the wire dtype with the
# scale lanes packed on: i8 (S=2, cap=4, d+4) — still zero slack rows
ops = re.findall(r"(\w+)\[([\d,]+)\](?:\{[^}]*\})? = all_to_all", fwd_jaxpr)
assert len(ops) == groups, fwd_jaxpr
for dt, shape in ops:
    assert dt == "i8", (dt, shape)
    s_, cap_, d_ = map(int, shape.split(","))
    assert (s_, cap_ * s_, d_) == (2, b, d + 4), shape
print("submesh-quant-one-collective OK")

back_jaxpr = str(jax.make_jaxpr(
    lambda v, pr: coll.route_back(v, pr, n))(x, prep))
assert back_jaxpr.count("all_to_all") == groups, back_jaxpr
assert back_jaxpr.count("sort[") == 0, back_jaxpr
ops = re.findall(r"(\w+)\[([\d,]+)\](?:\{[^}]*\})? = all_to_all", back_jaxpr)
assert len(ops) == groups and all(dt == "i8" for dt, _ in ops), ops
print("submesh-quant-route-back OK")
"""


@pytest.mark.parametrize("_", [0])
def test_submesh_quantized_stream_keeps_collective_structure(_, tmp_path):
    """Jaxpr inspection at 8 forced host devices: the int8-wire sub-mesh
    stream keeps exactly ONE all_to_all per flush group on the forward
    AND the quantized route-back, zero sorts, with the payload operand in
    the wire dtype carrying d+4 columns (rows + packed scale lanes)."""
    out = _run_worker(tmp_path, "worker_submesh_quant_jaxpr.py",
                      WORKER_SUBMESH_QUANT_JAXPR, 420)
    for token in ("submesh-quant-one-collective OK",
                  "submesh-quant-route-back OK"):
        assert token in out, out

"""Multi-host ("pod", "data") collector mesh.

Four layers, cheapest first:

  1. in-process unit coverage of the pod plumbing — ``collector_axis``
     resolution, tuple-axis ``mesh_axis_size``, pod validation in
     ``make_data_mesh`` / ``check_sfpl_layout``, and the
     ``StreamingAllToAll`` pod-locality gate (a sub-mesh slice straddling
     pods must fall back to the whole-mesh exchange, LOGGED, and
     ``submesh=True`` must raise — never a silent drop);
  2. an in-process (1, 1) pod-mesh epoch pinned to the dense oracle — the
     tuple-axis code path (``P(("pod", "data"))`` placement, tuple-axis
     ``all_to_all``) without any subprocess;
  3. single-process subprocesses with 8 forced devices: the (2, 4) pod
     mesh differential (isolates 2-D-mesh bugs from distributed-runtime
     bugs) and the jaxpr proof that the pod axis adds NO collectives —
     per-cell all_to_all counts identical between the (8,) and (2, 4)
     meshes, zero sorts on the exchange path;
  4. the tentpole: tests/_multihost.py spawns 2 REAL coordinated JAX
     processes x 4 forced CPU devices (gloo collectives) and pins the
     sharded epoch's losses AND post-epoch client/server param trees
     (the integral of every routed-back gradient) within 1e-5 of the
     single-device oracle across {sync, double_buffered fallback,
     sub-mesh} x alpha {0.5, 1.0}, on BOTH processes.
"""
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine_dist as ED
from repro.core import round as RD
from repro.core.collector_dist import axis_tuple, mesh_axis_size


def _fake_mesh(shape, names=("pod", "data")):
    return SimpleNamespace(axis_names=names, devices=np.zeros(shape))


# --------------------------------------------------------------------------
# 1. in-process pod plumbing


def test_collector_axis_resolution():
    pod = _fake_mesh((2, 4))
    flat = _fake_mesh((8,), names=("data",))
    assert ED.collector_axis(pod) == ("pod", "data")
    assert ED.collector_axis(flat) == "data"
    assert mesh_axis_size(pod, ("pod", "data")) == 8
    assert mesh_axis_size(pod, "data") == 4
    assert mesh_axis_size(pod, "pod") == 2
    assert axis_tuple("data") == ("data",)
    assert axis_tuple(("pod", "data")) == ("pod", "data")


def test_make_data_mesh_pod_validation():
    for pods in (3, 0, -1):
        with pytest.raises(ValueError, match="divide num_shards"):
            ED.make_data_mesh(8, pods=pods)


def test_layout_check_pod_validation():
    with pytest.raises(ValueError, match="divide n_shards"):
        ED.check_sfpl_layout(8, 8, 8, pods=3)
    # alpha=0.5 over 8 shards -> two groups spanning 4 shards each; with 4
    # pods the 4-shard slice straddles the 2-shard pods, so demanding
    # sub-mesh routing must raise eagerly...
    with pytest.raises(ValueError, match="pod-local"):
        ED.check_sfpl_layout(8, 8, 8, alpha=0.5, pods=4,
                             collector_submesh=True,
                             collector_pipeline="double_buffered")
    # ...but the layout itself stays valid: the streamed exchange falls
    # back to the probed-slack whole-mesh path
    assert ED.check_sfpl_layout(
        8, 8, 8, alpha=0.5, pods=4,
        collector_pipeline="double_buffered") == [32, 32]
    # pod-local slice (4 shards per pod, slice of 4) qualifies
    assert ED.check_sfpl_layout(
        8, 8, 8, alpha=0.5, pods=2, collector_submesh=True,
        collector_pipeline="double_buffered") == [32, 32]
    # whole-mesh slice (one global flush) qualifies on any pod split
    assert ED.check_sfpl_layout(
        8, 8, 8, alpha=1.0, pods=4, collector_submesh=True,
        collector_pipeline="double_buffered") == [64]


def test_fit_shards_honours_pods():
    assert ED.fit_shards(8, 8, pods=2, max_shards=8) == 8
    # pods=3: the 3- and 6-shard candidates fail the client divisibility
    # check, so the fallback is one shard per pod — never an unbuildable
    # mesh
    assert ED.fit_shards(8, 8, pods=3, max_shards=8) == 3
    assert ED.fit_shards(7, 3, pods=2, max_shards=8) == 2


def test_submesh_slices_pod_locality_gate(caplog):
    # (4, 2) mesh: 8 shards, 2 per pod. alpha=0.5 -> slice of 4 shards
    # straddles pods: auto mode falls back with a logged warning...
    coll = RD.StreamingAllToAll(mesh=_fake_mesh((4, 2)), num_clients=8,
                                axis=("pod", "data"), alpha=0.5)
    with caplog.at_level("WARNING", logger="repro.core.round"):
        assert coll.submesh_slices(64) is None
    assert any("straddles the pod boundary" in r.getMessage()
               for r in caplog.records)
    # ...and submesh=True raises, naming the pod boundary
    strict = RD.StreamingAllToAll(mesh=_fake_mesh((4, 2)), num_clients=8,
                                  axis=("pod", "data"), alpha=0.5,
                                  submesh=True)
    with pytest.raises(ValueError, match="straddles the pod boundary"):
        strict.submesh_slices(64)
    # pod-local slice (slice 4 == shards per pod) stays sub-mesh routed
    local = RD.StreamingAllToAll(mesh=_fake_mesh((2, 4)), num_clients=8,
                                 axis=("pod", "data"), alpha=0.5)
    assert local.submesh_slices(64) == 4
    # one global flush is the whole mesh on any pod split
    whole = RD.StreamingAllToAll(mesh=_fake_mesh((4, 2)), num_clients=8,
                                 axis=("pod", "data"), alpha=1.0)
    assert whole.submesh_slices(64) == 8


# --------------------------------------------------------------------------
# 2. in-process (1, 1) pod-mesh differential (tuple-axis path, no
# subprocess)


def _tiny_problem(num_clients=4, batch_size=4):
    from repro.core import engine as E
    from repro.data import make_synthetic_cifar, partition_positive_labels
    from repro.models import resnet as R
    from repro.optim import sgd_momentum
    cfg = R.ResNetConfig(depth=8, num_classes=num_clients, width=8)
    tx, ty, _, _ = make_synthetic_cifar(
        jax.random.PRNGKey(0), num_classes=num_clients,
        train_per_class=2 * batch_size, test_per_class=batch_size, hw=8)
    data = partition_positive_labels(tx, ty, num_clients)
    split = E.make_resnet_split(cfg)
    opt = sgd_momentum(0.05, momentum=0.9, weight_decay=5e-4)
    st0 = E.init_dcml_state(jax.random.PRNGKey(0),
                            lambda k: R.init(k, cfg), num_clients, opt, opt)
    host = jax.tree_util.tree_map(np.asarray, st0)
    fresh = lambda: jax.tree_util.tree_map(jnp.asarray, host)
    return E, data, split, opt, fresh


def _tree_maxdiff(a, b, fetch=np.asarray):
    return max(float(np.abs(fetch(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def test_pod_mesh_single_device_differential():
    V = B = 4
    E, data, split, opt, fresh = _tiny_problem(V, B)
    ke = jax.random.PRNGKey(1)
    st_ref, l_ref = jax.jit(lambda k, s: E.sfpl_epoch(
        k, s, data, split, opt, opt, num_clients=V,
        batch_size=B))(ke, fresh())

    mesh = ED.make_data_mesh(1, pods=1)
    assert ED.collector_axis(mesh) == ("pod", "data")
    sts = ED.shard_dcml_state(fresh(), mesh)
    epoch = ED.make_sfpl_epoch_sharded(
        split, opt, opt, ED.shard_client_data(data, mesh), mesh=mesh,
        num_clients=V, batch_size=B)
    sts, ls = epoch(ke, sts)
    assert float(np.abs(np.asarray(ls) - np.asarray(l_ref)).max()) < 1e-5
    assert _tree_maxdiff(sts["cp"], st_ref["cp"]) < 1e-5
    assert _tree_maxdiff(sts["sp"], st_ref["sp"]) < 1e-5


# --------------------------------------------------------------------------
# 3. single-process subprocesses: (2, 4) differential + jaxpr proof

WORKER_POD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as E
from repro.core import engine_dist as ED
from repro.data import make_synthetic_cifar, partition_positive_labels
from repro.models import resnet as R
from repro.optim import sgd_momentum

V, B = 8, 8
cfg = R.ResNetConfig(depth=8, num_classes=V, width=8)
tx, ty, _, _ = make_synthetic_cifar(jax.random.PRNGKey(0), num_classes=V,
                                    train_per_class=16, test_per_class=8,
                                    hw=8)
data = partition_positive_labels(tx, ty, V)
split = E.make_resnet_split(cfg)
opt = sgd_momentum(0.05, momentum=0.9, weight_decay=5e-4)
st0 = E.init_dcml_state(jax.random.PRNGKey(0), lambda k: R.init(k, cfg),
                        V, opt, opt)
host = jax.tree_util.tree_map(np.asarray, st0)
fresh = lambda: jax.tree_util.tree_map(jnp.asarray, host)
ke = jax.random.PRNGKey(1)
oracle = jax.jit(lambda k, s, a: E.sfpl_epoch(
    k, s, data, split, opt, opt, num_clients=V, batch_size=B, alpha=a),
    static_argnums=(2,))

mesh = ED.make_data_mesh(8, pods=2)
assert dict(mesh.shape) == {"pod": 2, "data": 4}, dict(mesh.shape)
data_dev = ED.shard_client_data(data, mesh)
md = lambda a, b: max(
    float(np.abs(np.asarray(x) - np.asarray(y)).max())
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)))

for name, alpha, kw in [
        ("sync-a1.0", 1.0, {}),
        ("submesh-a0.5", 0.5, dict(collector_pipeline="double_buffered",
                                   collector_submesh=True))]:
    st_ref, l_ref = oracle(ke, fresh(), alpha)
    sts = ED.shard_dcml_state(fresh(), mesh)
    ep = ED.make_sfpl_epoch_sharded(split, opt, opt, data_dev, mesh=mesh,
                                    num_clients=V, batch_size=B,
                                    alpha=alpha, **kw)
    sts, ls = ep(ke, sts)
    dl = float(np.abs(np.asarray(ls) - np.asarray(l_ref)).max())
    dcp, dsp = md(sts["cp"], st_ref["cp"]), md(sts["sp"], st_ref["sp"])
    assert dl < 1e-5 and dcp < 1e-5 and dsp < 1e-5, (name, dl, dcp, dsp)
    print("pod-oracle OK", name, flush=True)
print("all-pod-oracle OK")
"""

WORKER_JAXPR = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.core import round as RD

N, D = 64, 3
x = jnp.zeros((N, D))
perm = jnp.arange(N)

def counts(mesh, axis, alpha, streaming, submesh=None):
    if streaming:
        coll = RD.StreamingAllToAll(mesh=mesh, num_clients=8, axis=axis,
                                    alpha=alpha, submesh=submesh)
    else:
        coll = RD.MeshAllToAll(mesh=mesh, num_clients=8, axis=axis,
                               alpha=alpha)
    run = lambda v, p: coll.permute(v, coll.prepare(p, N))
    fwd = str(jax.make_jaxpr(run)(x, perm))
    w = jnp.ones((N, D))
    bwd = str(jax.make_jaxpr(
        jax.grad(lambda v: jnp.sum(run(v, perm) * w)))(x))
    return (fwd.count("all_to_all"), bwd.count("all_to_all"),
            fwd.count("sort["), bwd.count("sort["))

from repro.core.engine_dist import make_data_mesh
mesh1 = make_data_mesh(8)
mesh2 = make_data_mesh(8, pods=2)

# per-cell collective counts must be IDENTICAL between the 1-D and the
# pod mesh — the pod axis adds no all_to_alls — and the exchange path
# stays sort-free everywhere
for alpha, streaming, submesh in [(1.0, False, None), (0.5, False, None),
                                  (1.0, True, None), (0.5, True, True),
                                  (0.5, True, False)]:
    c1 = counts(mesh1, "data", alpha, streaming, submesh)
    c2 = counts(mesh2, ("pod", "data"), alpha, streaming, submesh)
    assert c1 == c2, (alpha, streaming, submesh, c1, c2)
    assert c1[2] == c1[3] == 0, (alpha, streaming, submesh, c1)
    assert c1[0] >= 1 and c1[1] > c1[0], (alpha, streaming, submesh, c1)
    print("jaxpr-parity OK", alpha, streaming, submesh, c1[:2],
          flush=True)
print("all-jaxpr OK")
"""


def _run_worker(tmp_path, code, tokens, timeout=540):
    w = tmp_path / "worker.py"
    w.write_text(code)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, str(w)], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    for tok in tokens:
        assert tok in r.stdout, r.stdout + r.stderr
    return r.stdout


def test_pod_mesh_single_process_differential(tmp_path):
    _run_worker(tmp_path, WORKER_POD,
                ["pod-oracle OK sync-a1.0", "pod-oracle OK submesh-a0.5",
                 "all-pod-oracle OK"])


def test_pod_axis_jaxpr_collective_count(tmp_path):
    _run_worker(tmp_path, WORKER_JAXPR, ["all-jaxpr OK"])


# --------------------------------------------------------------------------
# 4. the tentpole: 2 coordinated processes x 4 devices each


def _pod_matrix_worker():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import engine as E
    from repro.core import engine_dist as ED
    from repro.data import make_synthetic_cifar, partition_positive_labels
    from repro.launch import multihost
    from repro.models import resnet as R
    from repro.optim import sgd_momentum

    V, B = 8, 8
    cfg = R.ResNetConfig(depth=8, num_classes=V, width=8)
    tx, ty, _, _ = make_synthetic_cifar(
        jax.random.PRNGKey(0), num_classes=V, train_per_class=16,
        test_per_class=8, hw=8)
    data = partition_positive_labels(tx, ty, V)
    split = E.make_resnet_split(cfg)
    opt = sgd_momentum(0.05, momentum=0.9, weight_decay=5e-4)
    st0 = E.init_dcml_state(jax.random.PRNGKey(0),
                            lambda k: R.init(k, cfg), V, opt, opt)
    host = jax.tree_util.tree_map(np.asarray, st0)
    fresh = lambda: jax.tree_util.tree_map(jnp.asarray, host)
    ke = jax.random.PRNGKey(1)
    # the oracle runs UNsharded inside each process — a per-host
    # single-device reference, identical on every host by determinism
    oracle = jax.jit(lambda k, s, a: E.sfpl_epoch(
        k, s, data, split, opt, opt, num_clients=V, batch_size=B,
        alpha=a), static_argnums=(2,))

    mesh = multihost.make_pod_mesh()
    assert dict(mesh.shape) == {"pod": 2, "data": 4}, dict(mesh.shape)
    assert ED.collector_axis(mesh) == ("pod", "data")
    data_dev = ED.shard_client_data(data, mesh)

    cells = [
        ("sync-a1.0", 1.0, {}),
        ("sync-a0.5", 0.5, {}),
        ("dbuf-a1.0", 1.0, dict(collector_pipeline="double_buffered",
                                collector_submesh=False)),
        ("dbuf-a0.5", 0.5, dict(collector_pipeline="double_buffered",
                                collector_submesh=False)),
        ("submesh-a1.0", 1.0, dict(collector_pipeline="double_buffered",
                                   collector_submesh=True)),
        ("submesh-a0.5", 0.5, dict(collector_pipeline="double_buffered",
                                   collector_submesh=True)),
    ]
    refs, out = {}, {}
    for name, alpha, kw in cells:
        if alpha not in refs:
            refs[alpha] = oracle(ke, fresh(), alpha)
        st_ref, l_ref = refs[alpha]
        sts = ED.shard_dcml_state(fresh(), mesh)
        epoch = ED.make_sfpl_epoch_sharded(
            split, opt, opt, data_dev, mesh=mesh, num_clients=V,
            batch_size=B, alpha=alpha, **kw)
        sts, ls = epoch(ke, sts)
        diff = lambda a, b: float(
            np.abs(multihost.host_value(a) - np.asarray(b)).max())
        md = lambda a, b: max(
            diff(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                       jax.tree_util.tree_leaves(b)))
        out[name] = dict(
            loss_diff=diff(ls, l_ref),
            client_diff=md(sts["cp"], st_ref["cp"]),
            server_diff=md(sts["sp"], st_ref["sp"]),
            losses=multihost.host_value(ls))
    return out


def test_multihost_differential_matrix(tmp_path):
    pytest.importorskip("cloudpickle")
    from _multihost import run_multiprocess
    results = run_multiprocess(_pod_matrix_worker, num_processes=2,
                               devices_per_process=4)
    assert len(results) == 2
    cells = sorted(results[0])
    assert cells == sorted(results[1])
    for name in cells:
        for pid, res in enumerate(results):
            cell = res[name]
            assert cell["loss_diff"] < 1e-5, (name, pid, cell)
            assert cell["client_diff"] < 1e-5, (name, pid, cell)
            assert cell["server_diff"] < 1e-5, (name, pid, cell)
        # both processes observed the identical global loss trajectory
        np.testing.assert_array_equal(results[0][name]["losses"],
                                      results[1][name]["losses"])

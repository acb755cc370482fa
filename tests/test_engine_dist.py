"""Mesh-sharded SFPL engine: numerical interchangeability with the
single-device engine under 8 forced host devices (subprocess, since the
device count must be fixed before jax initializes)."""
import os
import subprocess
import sys

import numpy as np
import pytest

WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as E
from repro.core import engine_dist as ED
from repro.data import make_synthetic_cifar, partition_positive_labels
from repro.models import resnet as R
from repro.optim import sgd_momentum

V = 8                       # clients == classes, one client per shard
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

# single-device reference trajectory
ref_step = jax.jit(lambda k, s: E.sfpl_epoch(
    k, s, data, split, opt, opt, num_clients=V, batch_size=8,
    bn_mode="cmsd"))
st = st0
key = jax.random.PRNGKey(1)
epoch_keys, ref_losses = [], []
for _ in range(2):
    key, ke = jax.random.split(key)
    epoch_keys.append(ke)
    st, l = ref_step(ke, st)
    ref_losses.append(np.asarray(l))
ref = np.concatenate(ref_losses)

# sharded engine, same seed: the collector swaps the uniform pool shuffle
# for balanced all_to_all blocks; SFPL's server update is
# permutation-invariant, so trajectories must agree to float tolerance.
mesh = ED.make_data_mesh(8)
data_sh = ED.shard_client_data(data, mesh)

def fresh_state():
    st = jax.tree_util.tree_map(jnp.asarray, st0_host)
    return ED.shard_dcml_state(st, mesh)

epoch = ED.make_sfpl_epoch_sharded(split, opt, opt, data_sh, mesh=mesh,
                                   num_clients=V, batch_size=8,
                                   check_capacity=True)
st = fresh_state()
sh_losses = []
for ke in epoch_keys:
    st, l = epoch(ke, st)      # donated carry: hot buffers reused in place
    sh_losses.append(np.asarray(l))
sh = np.concatenate(sh_losses)
diff = float(np.abs(ref - sh).max())
assert diff < 1e-4, (diff, ref, sh)
print(f"trajectory-parity OK ({diff:.2e})")

# FedAvg'd client params must match too (all-reduce over the sharded axis)
st_ref = st0
for ke in epoch_keys:
    st_ref, _ = ref_step(ke, st_ref)
for a, b in zip(jax.tree_util.tree_leaves(st_ref["cp"]),
                jax.tree_util.tree_leaves(st["cp"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
print("params-parity OK")

# Pallas kernel on the local bucket permute: identical losses
epoch_k = ED.make_sfpl_epoch_sharded(split, opt, opt, data_sh, mesh=mesh,
                                     num_clients=V, batch_size=8,
                                     use_kernel=True)
stk, lk = epoch_k(epoch_keys[0], fresh_state())
dk = float(np.abs(np.asarray(lk) - ref_losses[0]).max())
assert dk < 1e-4, dk
print(f"kernel-parity OK ({dk:.2e})")
"""

WORKER_SCHEMES = r"""
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

def fresh_dense():
    return jax.tree_util.tree_map(jnp.asarray, st0_host)

def fresh_sharded():
    return ED.shard_dcml_state(fresh_dense(), mesh)

ke = jax.random.split(jax.random.PRNGKey(1))[1]

# alpha<1: per-flush-group balanced exchanges on the mesh must track the
# single-device flush-group shuffle (the SFPL server update is
# permutation-invariant within the pool)
for alpha in (0.25, 0.5):
    dense = jax.jit(lambda k, s, a=alpha: E.sfpl_epoch(
        k, s, data, split, opt, opt, num_clients=V, batch_size=8, alpha=a))
    _, l_d = dense(ke, fresh_dense())
    epoch = ED.make_sfpl_epoch_sharded(split, opt, opt, data_sh, mesh=mesh,
                                       num_clients=V, batch_size=8,
                                       alpha=alpha, check_capacity=True)
    _, l_s = epoch(ke, fresh_sharded())
    d = float(np.abs(np.asarray(l_d) - np.asarray(l_s)).max())
    assert d < 1e-4, (alpha, d)
print("alpha-parity OK")

# paper-faithful uniform collector mode with auto-sized slack
dense1 = jax.jit(lambda k, s: E.sfpl_epoch(
    k, s, data, split, opt, opt, num_clients=V, batch_size=8))
_, l_ref = dense1(ke, fresh_dense())
epoch_u = ED.make_sfpl_epoch_sharded(split, opt, opt, data_sh, mesh=mesh,
                                     num_clients=V, batch_size=8,
                                     collector_mode="uniform")
_, l_u = epoch_u(ke, fresh_sharded())
du = float(np.abs(np.asarray(l_ref) - np.asarray(l_u)).max())
assert du < 1e-4, du
print("uniform-parity OK")

# sharded SFLv2: server stream sharded over the batch axis, sequential
# client visitation (the catastrophic-forgetting order) preserved. The
# sharded batch reduces in another float order, and SFLv2's sequential
# single-class chain amplifies that ~1e-7 noise with every server update
# (32 updates reach ~4e-3), so the horizon is one epoch of one batch per
# client (8 updates) — after a first step that must be bit-identical.
data_v2 = {k: v[:, :8] for k, v in data.items()}
sfl = jax.jit(lambda k, s: E.sflv2_epoch(
    k, s, data_v2, split, opt, opt, num_clients=V, batch_size=8))
sfl_sh = ED.make_sflv2_epoch_sharded(split, opt, opt, data_v2, mesh=mesh,
                                     num_clients=V, batch_size=8)
ke2 = jax.random.split(jax.random.PRNGKey(2), 2)[0]
st_d, l_d = sfl(ke2, fresh_dense())
st_s, l_s = sfl_sh(ke2, fresh_dense())
l_d, l_s = np.asarray(l_d), np.asarray(l_s)
assert l_d[0, 0] == l_s[0, 0], (l_d[0, 0], l_s[0, 0])
ds = float(np.abs(l_d - l_s).max())
assert ds < 1e-4, ds
for a, b in zip(jax.tree_util.tree_leaves(st_d["sp"]),
                jax.tree_util.tree_leaves(st_s["sp"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
print("sflv2-parity OK")
"""


@pytest.mark.parametrize("_", [0])
def test_sharded_engine_matches_single_device(_, tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stdout + res.stderr
    for token in ("trajectory-parity OK", "params-parity OK",
                  "kernel-parity OK"):
        assert token in res.stdout, res.stdout


@pytest.mark.parametrize("_", [0])
def test_sharded_schemes_match_single_device(_, tmp_path):
    """alpha<1 flush groups, the uniform collector mode, and sharded SFLv2
    all track their single-device counterparts at 8 forced host devices."""
    script = tmp_path / "worker_schemes.py"
    script.write_text(WORKER_SCHEMES)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stdout + res.stderr
    for token in ("alpha-parity OK", "uniform-parity OK",
                  "sflv2-parity OK"):
        assert token in res.stdout, res.stdout


class _FakeMesh:
    """Enough mesh surface for the eager validators (axis_names + device
    grid shape), usable in the single-device pytest process."""
    axis_names = ("data",)
    devices = np.empty((8,), dtype=object)


def test_sharded_engine_layout_validation():
    """Unshardable layouts are rejected eagerly (ValueError before any
    device work): flush groups must cover whole shard slabs, and the SFLv2
    batch axis must divide over the mesh."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine_dist as ED
    mesh = _FakeMesh()
    data = {"x": jnp.zeros((4, 8, 2)), "y": jnp.zeros((4, 8), jnp.int32)}
    with pytest.raises(ValueError, match="divide evenly"):
        ED.sfpl_epoch_sharded(
            jax.random.PRNGKey(0), {}, data, None, None, None, mesh=mesh,
            num_clients=4, batch_size=8)
    # N=16 over 8 shards -> 8-row slabs; alpha=0.2 makes 3-client (12-row)
    # flush groups that straddle slab boundaries
    with pytest.raises(ValueError, match="flush group"):
        ED.sfpl_epoch_sharded(
            jax.random.PRNGKey(0), {}, data, None, None, None, mesh=mesh,
            num_clients=16, batch_size=4, alpha=0.2)
    # aligned 4-shard groups, but the 3-row slab cannot split into 4 blocks
    with pytest.raises(ValueError, match="balanced exchange"):
        ED.sfpl_epoch_sharded(
            jax.random.PRNGKey(0), {}, data, None, None, None, mesh=mesh,
            num_clients=8, batch_size=3, alpha=0.5)
    with pytest.raises(ValueError, match="batch_size"):
        ED.sflv2_epoch_sharded(
            jax.random.PRNGKey(0), {}, data, None, None, None, mesh=mesh,
            num_clients=8, batch_size=12)


def test_check_sfpl_layout_accepts_aligned_groups():
    """The acceptance layout (8 clients, 8 shards, B=8) validates for one
    global flush and for alpha in {0.25, 0.5} grouped flushes."""
    from repro.core.engine_dist import check_sfpl_layout
    assert check_sfpl_layout(8, 8, 8) == [64]
    assert check_sfpl_layout(8, 8, 8, alpha=0.5) == [32, 32]
    assert check_sfpl_layout(8, 8, 8, alpha=0.25) == [16, 16, 16, 16]
    assert check_sfpl_layout(8, 8, 8, alpha=0.25,
                             collector_mode="uniform") == [16] * 4
    # groups living inside one slab need no exchange and are accepted
    assert check_sfpl_layout(8, 8, 2, alpha=0.25) == [16] * 4
    # uniform mode has no alignment requirement (slack is probed)
    assert check_sfpl_layout(16, 4, 8, alpha=0.2,
                             collector_mode="uniform") == [12] * 5 + [4]

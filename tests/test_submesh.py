"""Differential property suite for the sub-mesh streaming exchange.

The tentpole claim: routing each flush group's exchange only over its
owning shard slice (dense plans + ``axis_index_groups``) changes the
DATAFLOW, never the values. Every test here pins the new path to an
oracle that does not share its code:

  * trajectory parity — the sub-mesh streamed sharded epoch (and the
    uniform whole-mesh streamed fallback) against the single-device
    sync dense oracle ``engine.sfpl_epoch`` (``DenseTake``: one
    ``jnp.take``, no mesh, no plans), for forward loss AND the
    client/server parameters the gradients update, across
    mode x alpha x forced 8/16 host devices;
  * a host-side numpy simulation of the grouped ``all_to_all``
    semantics replaying sub-mesh route plans over randomized
    (slice, slab, group) shapes — forward reproduces ``x[perm]`` on the
    group's rows and the backward plan inverts it, without ever
    launching a collective;
  * the streamed uniform fallback's slack probing is memoized on the
    group row counts actually used (one probe per distinct size).

Device-farm legs run in subprocesses (the forced host device count must
be set before jax initializes), mirroring tests/test_streaming.py.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from _propshim import given, settings, strategies as st
from repro.core import engine_dist as ED

WORKER_TEMPLATE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as E
from repro.core import engine_dist as ED
from repro.data import make_synthetic_cifar, partition_positive_labels
from repro.models import resnet as R
from repro.optim import sgd_momentum

NDEV = %(ndev)d
V = NDEV  # one client per class, one per shard
B = %(batch)d  # slab b = B rows/shard; alpha=1.0 needs b %% NDEV == 0
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
mesh = ED.make_data_mesh(NDEV)
data_sh = ED.shard_client_data(data, mesh)

def fresh_sharded():
    return ED.shard_dcml_state(
        jax.tree_util.tree_map(jnp.asarray, st0_host), mesh)

def fresh_single():
    return jax.tree_util.tree_map(jnp.asarray, st0_host)

keys = list(jax.random.split(jax.random.PRNGKey(1), %(nkeys)d))

# the sync dense oracle: every client on one device, the collector a
# dense jnp.take -- no mesh, no route plans, no streaming (the SFPL
# server update is permutation-invariant, so every collector mode's
# trajectory must match it)
def oracle(alpha):
    ep = jax.jit(lambda k, s: E.sfpl_epoch(
        k, s, data, split, opt, opt, num_clients=V, batch_size=B,
        alpha=alpha))
    s, losses = fresh_single(), []
    for ke in keys:
        s, l = ep(ke, s)
        losses.append(np.asarray(l))
    return s, np.stack(losses)

for alpha in (0.25, 0.5, 1.0):
    ref_st, ref_losses = oracle(alpha)
    # balanced + submesh=True: the dense slice-confined exchange is
    # REQUIRED (prepare raises if the layout were to disqualify);
    # uniform + submesh=None: the whole-mesh streamed fallback with
    # per-group probed slack (uniform never qualifies for sub-mesh)
    for mode, submesh in (("balanced", True), ("uniform", None)):
        ep = ED.make_sfpl_epoch_sharded(
            split, opt, opt, data_sh, mesh=mesh, num_clients=V,
            batch_size=B, alpha=alpha, collector_mode=mode,
            collector_pipeline="double_buffered", collector_submesh=submesh)
        s, losses = fresh_sharded(), []
        for ke in keys:
            s, l = ep(ke, s)
            losses.append(np.asarray(l))
        d = float(np.abs(np.stack(losses) - ref_losses).max())
        assert d <= 1e-5, (alpha, mode, d)
        # client AND server parameters after the epochs: the round-trip
        # through issue/complete, the server grad, and the explicit
        # route_back de-shuffle all feed these
        for part in ("cp", "sp"):
            for a, b in zip(jax.tree_util.tree_leaves(ref_st[part]),
                            jax.tree_util.tree_leaves(s[part])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5,
                                           err_msg=f"{alpha} {mode} {part}")
        print(f"submesh-oracle OK ndev={NDEV} alpha={alpha} mode={mode} "
              f"({d:.2e})", flush=True)
print("all-submesh-oracle OK")
"""


def _run_worker(tmp_path, ndev, nkeys, batch, timeout):
    script = tmp_path / f"worker_submesh_{ndev}.py"
    script.write_text(WORKER_TEMPLATE
                      % {"ndev": ndev, "nkeys": nkeys, "batch": batch})
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "all-submesh-oracle OK" in res.stdout, res.stdout


def test_submesh_matches_dense_oracle_8dev(tmp_path):
    """Sub-mesh streamed (balanced) and whole-mesh streamed fallback
    (uniform) trajectories vs the single-device sync dense oracle:
    loss + client/server params <= 1e-5 over alpha {0.25, 0.5, 1.0} at
    8 forced host devices."""
    _run_worker(tmp_path, ndev=8, nkeys=2, batch=8, timeout=560)


def test_submesh_matches_dense_oracle_16dev(tmp_path):
    """The same differential matrix at 16 forced host devices (16
    clients, slices of 4/8/16 shards across the alphas)."""
    _run_worker(tmp_path, ndev=16, nkeys=1, batch=16, timeout=560)


# --------------------------------------------------------------------------
# host-side simulation of the grouped collective: plans replayed in numpy


def _simulate_plan_exchange(x, plan, n_shards):
    """Replay one plan exchange with the documented ``all_to_all``
    semantics, no devices: within each ``axis_index_groups`` slice, the
    receive block ``recv[j]`` on member ``s`` is member ``j``'s send
    bucket at position ``local_rank(s)``."""
    from repro.core import collector_dist as CD
    n, d = x.shape
    b = n // n_shards
    S = plan.slice_size or n_shards
    cap = plan.cap
    send = np.asarray(plan.send_idx)
    ridx = np.asarray(plan.recv_idx)
    groups = (CD.submesh_axis_groups(n_shards, S) if plan.slice_size
              else [list(range(n_shards))])
    bucket = np.stack([x[s * b:(s + 1) * b][send[s]].reshape(S, cap, d)
                       for s in range(n_shards)])
    out = np.zeros((n_shards, b, d), x.dtype)
    for members in groups:
        for rank, s in enumerate(members):
            recv = np.stack([bucket[j, rank] for j in members])
            flat = recv.reshape(S * cap, d)
            if plan.may_drop:
                flat = np.concatenate(
                    [flat, np.zeros((1, d), x.dtype)])
            out[s] = flat[ridx[s]]
    return out.reshape(n, d)


# (n_shards, slice_size) pairs covering 1-shard slices, partial slices,
# and the whole-mesh-as-one-slice degenerate case
_SHAPES = [(4, 1), (4, 2), (4, 4), (8, 2), (8, 4), (8, 8)]


@settings(deadline=None, max_examples=12)
@given(shape=st.sampled_from(_SHAPES),
       cap=st.sampled_from([1, 2, 3]),
       seed=st.sampled_from([0, 7]))
def test_submesh_plans_reproduce_perm_on_host(shape, cap, seed):
    """Property over randomized (slice, slab, capacity) layouts: the
    embedded sub-mesh plans, replayed under host-simulated grouped
    all_to_all semantics, reproduce ``x_g[sub_perm]`` exactly on every
    group's rows, are DENSE (no pad row, no overflow), and the backward
    plan inverts the forward one. Sub-perms are drawn from
    ``make_balanced_perm`` — the dense exact-capacity contract only
    holds for balanced block permutations (exactly what the engine's
    ``make_grouped_balanced_perm`` feeds the sub-mesh path); a uniform
    draw can route 3 rows into a 2-row bucket."""
    from repro.core.collector_dist import (build_submesh_route_plans,
                                           make_balanced_perm)
    n_shards, S = shape
    b = S * cap                      # slab rows; cap = b / S exactly
    n = n_shards * b
    n_g = S * b                      # rows per flush group
    n_groups = n_shards // S
    rng = np.random.default_rng(1000 * seed + 10 * n_shards + S)
    x = rng.standard_normal((n, 3)).astype(np.float32)
    expect = np.zeros_like(x)
    back = np.zeros_like(x)
    for g in range(n_groups):
        sub_perm = np.asarray(make_balanced_perm(
            jax.random.PRNGKey(7919 * seed + 31 * g + n_shards),
            n_g, S)).astype(np.int32)
        fwd, bwd = build_submesh_route_plans(
            jax.numpy.asarray(sub_perm), g, n_shards, S)
        for plan in (fwd, bwd):
            assert plan.dense and plan.slice_size == S
            assert plan.overflow is None and not plan.may_drop
            assert plan.cap == cap
            assert plan.send_idx.shape == (n_shards, b)
            assert plan.recv_idx.shape == (n_shards, b)
        r0, r1 = g * n_g, (g + 1) * n_g
        out = _simulate_plan_exchange(x, fwd, n_shards)
        expect[r0:r1] = out[r0:r1]
        np.testing.assert_array_equal(out[r0:r1], x[r0:r1][sub_perm])
        # backward plan applied to the shuffled rows recovers the source
        y = np.zeros_like(x)
        y[r0:r1] = out[r0:r1]
        inv = _simulate_plan_exchange(y, bwd, n_shards)
        back[r0:r1] = inv[r0:r1]
    # stitched over all groups: the full grouped permutation, inverted
    np.testing.assert_array_equal(back, x)
    assert (expect != 0).any()


def test_whole_mesh_simulation_matches_jax_oracle():
    """Anchor the host simulation itself: on whole-mesh plans it must
    agree with the real ``plan_shuffle`` on a 1-shard mesh (the only
    mesh available in-process), so the sub-mesh property above is not
    tested against a broken model of the collective."""
    from repro.core.collector_dist import build_route_plans, plan_shuffle
    mesh = ED.make_data_mesh(1)
    n = 12
    rng = np.random.default_rng(5)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    perm = rng.permutation(n).astype(np.int32)
    plans = build_route_plans(jax.numpy.asarray(perm), 1, cap=n,
                              may_drop=True)
    real = jax.jit(lambda x: plan_shuffle(x, plans, mesh=mesh))(x)
    sim = _simulate_plan_exchange(x, plans[0], 1)
    np.testing.assert_array_equal(np.asarray(real), sim)
    np.testing.assert_array_equal(sim, x[perm])


# --------------------------------------------------------------------------
# streamed uniform fallback: slack probing memoized on group sizes used


def test_streamed_uniform_slack_cached_per_group_size():
    """The streamed uniform fallback probes ``uniform_auto_slack`` at
    each flush group's OWN row count: one cache miss per distinct size,
    hits for every same-sized group and every re-prepare."""
    from repro.core import round as RD
    from repro.core.collector_dist import _uniform_auto_slack_cached

    mesh = ED.make_data_mesh(1)
    coll = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                                mode="uniform")
    n = 8 * 6
    rows = coll.group_rows(n)
    assert len(rows) == 4 and len(set(rows)) == 1  # 4 equal groups
    perm = jax.numpy.arange(n)

    _uniform_auto_slack_cached.cache_clear()
    before = _uniform_auto_slack_cached.cache_info()
    coll.prepare(perm, n)
    after = _uniform_auto_slack_cached.cache_info()
    # one probe for the single distinct group size, reused by the other
    # three same-sized groups
    assert after.misses - before.misses == 1, after
    assert after.hits - before.hits == len(rows) - 1, after

    coll.prepare(perm, n)  # re-trace / second step: all hits
    again = _uniform_auto_slack_cached.cache_info()
    assert again.misses == after.misses, again
    assert again.hits - after.hits == len(rows), again


def test_submesh_knob_validation():
    """``submesh=True`` on a non-qualifying layout raises with the
    disqualifying condition named; ``submesh=False`` forces the
    fallback; the sync pipeline rejects the knob outright."""
    from repro.core import round as RD

    mesh = ED.make_data_mesh(1)
    uni = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                               mode="uniform", submesh=True)
    with pytest.raises(ValueError, match="balanced"):
        uni.submesh_slices(48)
    slk = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                               mode="balanced", submesh=True,
                               stream_slack=2.0)
    with pytest.raises(ValueError, match="slack"):
        slk.submesh_slices(48)
    off = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                               mode="balanced", submesh=False)
    assert off.submesh_slices(48) is None
    auto = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=0.25,
                                mode="balanced")
    # 12-row groups inside one 48-row slab: no slice structure -> fallback
    assert auto.submesh_slices(48) is None
    one = RD.StreamingAllToAll(mesh=mesh, num_clients=8, alpha=1.0,
                               mode="balanced")
    assert one.submesh_slices(48) == 1  # one global flush over 1 shard
    placement = RD.DataMesh(mesh, "data")
    with pytest.raises(ValueError, match="double_buffered"):
        placement.collector(8, pipeline="sync", submesh=True)

"""Explicit shard_map collector: run in a subprocess with 8 host devices
(the device count must be fixed before jax initializes, so these tests
spawn a worker script)."""
import os
import subprocess
import sys

import numpy as np
import pytest
from _propshim import given, settings, strategies as st

WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.collector_dist import (
    shuffle_shard_map, make_balanced_perm, assert_pair_capacity,
    max_pair_load, pair_capacity)
from repro.core.collector import inverse_permutation

from repro.core.engine_dist import make_data_mesh
mesh = make_data_mesh(8)
N, D = 64, 5
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (N, D))
xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data")))

# uniform random permutation (slack buffer covers imbalance)
perm = jax.random.permutation(jax.random.fold_in(key, 1), N)
out = shuffle_shard_map(xs, perm, mesh=mesh, slack=8.0)
np.testing.assert_allclose(np.asarray(out), np.asarray(x)[np.asarray(perm)],
                           rtol=1e-6)
print("uniform-perm OK")

# balanced permutation is drop-free at slack=1 (and passes the in-graph check)
bperm = make_balanced_perm(jax.random.fold_in(key, 2), N, 8)
assert sorted(np.asarray(bperm).tolist()) == list(range(N))
out2 = shuffle_shard_map(xs, bperm, mesh=mesh, slack=1.0,
                         check_capacity=True)
np.testing.assert_allclose(np.asarray(out2),
                           np.asarray(x)[np.asarray(bperm)], rtol=1e-6)
print("balanced-perm OK")

# de-shuffle = shuffle with the inverse permutation
back = shuffle_shard_map(out2, inverse_permutation(bperm), mesh=mesh,
                         slack=1.0)
np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-6)
print("deshuffle OK")

# autodiff through the sharded gather IS the gradient de-shuffle
w = jnp.arange(float(N))[:, None]
g = jax.grad(lambda v: jnp.sum(
    shuffle_shard_map(v, bperm, mesh=mesh, slack=1.0) * w))(xs)
inv = np.argsort(np.asarray(bperm))
np.testing.assert_allclose(np.asarray(g),
                           np.tile(inv[:, None], (1, D)), rtol=1e-6)
print("autodiff-deshuffle OK")

# Pallas collector_permute kernel on the local bucket permute
out_k = shuffle_shard_map(xs, bperm, mesh=mesh, slack=1.0, use_kernel=True)
np.testing.assert_allclose(np.asarray(out_k),
                           np.asarray(x)[np.asarray(bperm)], rtol=1e-6)
g_k = jax.grad(lambda v: jnp.sum(
    shuffle_shard_map(v, bperm, mesh=mesh, slack=1.0, use_kernel=True)
    * w))(xs)
np.testing.assert_allclose(np.asarray(g_k), np.asarray(g), rtol=1e-6)
print("kernel-path OK")

# balanced perm mixes shards: every output shard must hold rows from
# every source shard (the IID-simulation property)
src_shard = np.asarray(bperm) // 8
for s in range(8):
    got = set(src_shard[s * 8:(s + 1) * 8].tolist())
    assert len(got) == 8, (s, got)
print("mixing OK")

# grouped balanced perm (alpha<1 flush groups): exchange at the auto-sized
# slack is exact and passes the in-graph capacity check
from repro.core.collector_dist import (
    make_grouped_balanced_perm, grouped_perm_slack)
rows = [32, 32]                      # two flush groups of 4 shards each
gperm = make_grouped_balanced_perm(jax.random.fold_in(key, 3), N, 8, rows)
gslack = grouped_perm_slack(N, 8, rows)
outg = shuffle_shard_map(xs, gperm, mesh=mesh, slack=gslack,
                         check_capacity=True)
np.testing.assert_allclose(np.asarray(outg),
                           np.asarray(x)[np.asarray(gperm)], rtol=1e-6)
print("grouped-perm OK")

# uniform perm at the probe-sized slack: exact, capacity check on
from repro.core.collector_dist import uniform_auto_slack
uslack = uniform_auto_slack(N, 8)
outu = shuffle_shard_map(xs, perm, mesh=mesh, slack=uslack,
                         check_capacity=True)
np.testing.assert_allclose(np.asarray(outu),
                           np.asarray(x)[np.asarray(perm)], rtol=1e-6)
print("auto-slack OK")

# --- capacity regression: adversarial perm at slack=1.0 ----------------
# (LAST: the deliberately-triggered in-graph callback errors surface
# asynchronously and would poison later computations)
# every output shard pulls ALL its rows from one source shard -> per-pair
# load b=8 against capacity 2.
adv = jnp.roll(jnp.arange(N), -8)
assert max_pair_load(adv, 8) == 8
assert pair_capacity(N, 8, 1.0) == 2
try:
    assert_pair_capacity(adv, 8, slack=1.0)
    raise SystemExit("host guard did not raise")
except ValueError:
    print("capacity-host-guard OK")

# without the check, overflow rows are dropped (zero-filled output) — and
# ONLY the overflow rows: the route plan sends them to an OOB slot, so the
# cap=2 in-capacity rows of each bucket land intact (the old exchange let
# each overflow clobber the slot cap-1 row, losing 7 of 8 rows per shard)
bad = np.asarray(shuffle_shard_map(xs, adv, mesh=mesh, slack=1.0))
oracle = np.asarray(x)[np.asarray(adv)]
assert not np.allclose(bad, oracle)
zero = np.abs(bad).sum(axis=1) == 0
assert zero.sum() == 8 * 6, zero.sum()
np.testing.assert_allclose(bad[~zero], oracle[~zero], rtol=1e-6)
print("capacity-silent-drop OK")

# with check_capacity=True the jitted program itself raises
try:
    r = shuffle_shard_map(xs, adv, mesh=mesh, slack=1.0,
                          check_capacity=True)
    r.block_until_ready()
    raise SystemExit("in-graph check did not raise")
except Exception as e:
    assert "capacity exceeded" in str(e) or "CpuCallback" in str(e), e
    print("capacity-ingraph OK")
"""


@pytest.mark.parametrize("_", [0])
def test_shard_map_collector(_, tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    for token in ("uniform-perm OK", "balanced-perm OK", "deshuffle OK",
                  "autodiff-deshuffle OK", "kernel-path OK", "mixing OK",
                  "capacity-host-guard OK", "capacity-silent-drop OK",
                  "capacity-ingraph OK", "grouped-perm OK",
                  "auto-slack OK"):
        assert token in res.stdout, res.stdout


def test_local_permute_order_in_range():
    import jax
    from repro.core.collector_dist import make_balanced_perm
    for seed, s in [(0, 2), (1, 4), (2, 8), (3, 8)]:
        n = s * s * 4
        b = n // s
        perms = [np.random.default_rng(seed).permutation(n),
                 np.asarray(make_balanced_perm(jax.random.PRNGKey(seed),
                                               n, s))]
        for perm in perms:
            inv = np.argsort(perm)
            for sid in range(s):
                out_pos = inv[np.arange(b) + sid * b]
                order = np.argsort(out_pos // b)
                assert order.min() >= 0
                assert order.max() < b
                assert np.array_equal(np.sort(order), np.arange(b))


@settings(max_examples=10, deadline=None)
@given(s_g=st.sampled_from([1, 2, 4]), groups=st.integers(2, 4),
       m=st.integers(1, 3))
def test_grouped_perm_never_mixes_flush_groups(s_g, groups, m):
    """Sharded flush groups are sealed: every row of a grouped balanced
    permutation stays inside its group's contiguous range, and within a
    multi-shard group the exchange is exactly balanced."""
    import jax
    from repro.core.collector_dist import (
        make_grouped_balanced_perm, pair_load)
    b = s_g * m                       # per-shard slab, divisible by s_g
    num_shards = s_g * groups
    n = num_shards * b
    rows = [s_g * b] * groups
    perm = np.asarray(make_grouped_balanced_perm(
        jax.random.PRNGKey(s_g * 100 + groups * 10 + m), n, num_shards,
        rows))
    assert sorted(perm.tolist()) == list(range(n))
    start = 0
    for size in rows:
        seg = perm[start:start + size]
        assert seg.min() >= start
        assert seg.max() < start + size
        start += size
    load = pair_load(perm, num_shards)
    for g in range(groups):
        blk = load[g * s_g:(g + 1) * s_g, g * s_g:(g + 1) * s_g]
        np.testing.assert_array_equal(blk, np.full((s_g, s_g), b // s_g))
    assert load.sum() == n            # nothing routed across groups


def test_grouped_perm_slack_covers_exact_loads():
    """The auto-sized slack holds the deterministic bucket loads of grouped
    balanced permutations, and resolves to the drop-free 1.0 for one
    global flush."""
    from repro.core.collector_dist import (
        grouped_perm_slack, max_pair_load, make_grouped_balanced_perm,
        pair_capacity)
    import jax
    assert grouped_perm_slack(64, 8, [64]) == 1.0
    for rows in ([32, 32], [16, 16, 16, 16], [8] * 8):
        slack = grouped_perm_slack(64, 8, rows)
        perm = make_grouped_balanced_perm(jax.random.PRNGKey(0), 64, 8,
                                          rows)
        assert max_pair_load(perm, 8) <= pair_capacity(64, 8, slack)


def test_grouped_perm_in_slab_groups():
    """Flush groups smaller than a shard slab shuffle in place: sealed,
    valid, diagonal loads covered by the auto slack."""
    import jax
    from repro.core.collector_dist import (
        make_grouped_balanced_perm, grouped_perm_slack, pair_load,
        pair_capacity)
    rows = [8, 8, 8, 8]
    perm = np.asarray(make_grouped_balanced_perm(
        jax.random.PRNGKey(0), 32, 2, rows))
    assert sorted(perm.tolist()) == list(range(32))
    start = 0
    for size in rows:
        seg = perm[start:start + size]
        assert seg.min() >= start
        assert seg.max() < start + size
        start += size
    load = pair_load(perm, 2)
    np.testing.assert_array_equal(load, np.diag([16, 16]))
    assert load.max() <= pair_capacity(32, 2,
                                       grouped_perm_slack(32, 2, rows))


def test_uniform_auto_slack_covers_probe_loads():
    from repro.core.collector_dist import (
        uniform_auto_slack, pair_capacity, max_pair_load)
    n, s = 64, 8
    cap = pair_capacity(n, s, uniform_auto_slack(n, s))
    rng = np.random.default_rng(0)
    for _ in range(16):
        assert max_pair_load(rng.permutation(n), s) < cap
    # grouped probing respects flush boundaries and still fits
    cap_g = pair_capacity(n, s, uniform_auto_slack(n, s, [32, 32]))
    assert cap_g >= 2


def test_pair_load_host_helpers():
    """pair_load math needs no devices: identity perm is diagonal, the
    rolled perm concentrates a full slab on one pair."""
    from repro.core.collector_dist import (
        pair_load, max_pair_load, pair_capacity, assert_pair_capacity)
    n, s = 32, 4
    ident = np.arange(n)
    load = pair_load(ident, s)
    assert load.sum() == n
    np.testing.assert_array_equal(load, np.diag([n // s] * s))
    adv = np.roll(ident, -(n // s))
    assert max_pair_load(adv, s) == n // s
    assert pair_capacity(n, s, 1.0) == n // s // s + 1
    with pytest.raises(ValueError, match="drop rows"):
        assert_pair_capacity(adv, s, slack=1.0)
    # generous slack passes
    assert_pair_capacity(adv, s, slack=float(s))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 9999), tail=st.sampled_from(["slab", "in_slab"]))
def test_grouped_perm_seals_mixed_layouts(seed, tail):
    """Sealing is a property of EVERY valid flush layout, not just the
    uniform-span ones: a multi-slab balanced group followed by
    single-slab or in-slab groups stays sealed under arbitrary keys, and
    no row ever routes across a group boundary."""
    import jax
    from repro.core.collector_dist import (make_grouped_balanced_perm,
                                           pair_load)
    num_shards, b = 4, 8
    n = num_shards * b
    rows = [2 * b] + ([b, b] if tail == "slab" else [b // 2] * 4)
    perm = np.asarray(make_grouped_balanced_perm(
        jax.random.PRNGKey(seed), n, num_shards, rows))
    assert sorted(perm.tolist()) == list(range(n))
    start = 0
    for size in rows:
        seg = perm[start:start + size]
        assert seg.min() >= start and seg.max() < start + size
        start += size
    load = pair_load(perm, num_shards)
    # the leading 2-slab group is an exactly balanced exchange between
    # shards 0 and 1; the tail groups never leave their own slab
    np.testing.assert_array_equal(load[:2, :2],
                                  np.full((2, 2), b // 2))
    np.testing.assert_array_equal(load[2:, 2:], np.diag([b, b]))
    assert load.sum() == n


@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([2, 4, 8]), mult=st.sampled_from([1, 2]),
       grouped=st.booleans())
def test_uniform_auto_slack_probe_stream_never_exceeded(s, mult, grouped):
    """The probed uniform cap is never exceeded by ANY permutation of the
    probe's own sample stream (rng seed 0, 16 draws, flush structure
    honoured) — the margin row keeps every draw strictly inside. The
    sampled perms ARE the probe's (re-drawn from its seed): the bound is
    empirical, so fresh random draws are exactly what the forced-on
    in-graph capacity check exists for."""
    from repro.core.collector_dist import (max_pair_load, pair_capacity,
                                           uniform_auto_slack)
    n = s * s * 4 * mult
    sizes = [n // 2, n // 2] if grouped else None
    cap = pair_capacity(n, s, uniform_auto_slack(n, s, sizes))
    rng = np.random.default_rng(0)
    for _ in range(16):
        if sizes:
            parts, start = [], 0
            for size in sizes:
                parts.append(rng.permutation(size) + start)
                start += size
            perm = np.concatenate(parts)
        else:
            perm = rng.permutation(n)
        assert max_pair_load(perm, s) < cap


@settings(max_examples=8, deadline=None)
@given(span=st.sampled_from([1, 2, 4]), shards=st.sampled_from([4, 8]),
       mult=st.sampled_from([1, 2]))
def test_balanced_stream_slack_probe_stream_never_exceeded(span, shards,
                                                           mult):
    """The streamed whole-mesh fallback's probed balanced cap covers every
    draw of the probe's own permutation family (balanced over ``span``
    blocks, uniform in place at span <= 1, measured against the fine
    slabs), and the slack never exceeds the capacity-safe ``shards``
    ceiling it replaces."""
    from repro.core.collector_dist import (_np_balanced_perm,
                                           balanced_stream_slack,
                                           max_pair_load, pair_capacity)
    n = span * span * shards * mult
    slack = balanced_stream_slack(n, shards, span)
    assert slack <= shards
    cap = pair_capacity(n, shards, slack)
    rng = np.random.default_rng(0)
    for _ in range(16):
        perm = (_np_balanced_perm(rng, n, span) if span > 1
                else rng.permutation(n))
        assert max_pair_load(perm, shards) < cap

"""Explicit-collective distributed collector (shard_map + all_to_all).

`collector.distributed_shuffle` lets XLA choose the collectives for the
global permutation gather. This module is the paper-faithful explicit
schedule — Algorithm 1's collect -> shuffle -> scatter written as
`shard_map` with `jax.lax.all_to_all` — organised around a precomputed
**route plan**:

  1. every data shard (client group) holds a (B_local, ...) slab of smashed
     data;
  2. because the permutation is REPLICATED, the routing metadata — the
     scatter-based O(n) inverse permutation, each row's destination shard,
     its slot in the send bucket, and the receive-side placement — is built
     ONCE per permutation (``build_route_plans``) and shared by the forward
     exchange, the custom-VJP backward exchange, and the streaming
     collector's ``route_back``;
  3. the exchange itself is gather -> ONE ``all_to_all`` -> gather: the
     plan's ``send_idx`` gathers rows directly into send-bucket layout, the
     collective ships the buckets, and ``recv_idx`` gathers received rows
     into output order. No positions or validity masks ever travel over
     the wire — receive placement is derived locally from the plan.

Balanced and grouped-balanced permutations get a **dense fast path**: their
per-pair bucket loads are deterministic (exactly b/S_g rows between the
shards of a flush group), so the plan is built at the exact capacity
(``exact_pair_cap``) with ``may_drop=False`` — zero slack padding for one
global flush, no overflow accounting, no pad row, and both sides of the
exchange are pure row gathers (the shapes the Pallas ``bucket_permute`` /
``unbucket_permute`` kernels fuse into one-pass HBM copies).

The same plan machinery with the inverse permutation is the de-shuffle, so
the gradient routing of Algorithm 1 is one more plan exchange — and because
``plan_shuffle`` registers the backward plan as its custom-VJP residual,
autodiff through the forward shuffle reuses the metadata instead of
re-deriving it (no argsort anywhere on the exchange path; tested in
tests/test_route_plan.py).

Capacity note: a random permutation may route more rows from one source
shard to one destination shard than the bucket holds; slack-buffered plans
(``may_drop=True``) use a per-pair capacity of ``cap = int(B_local *
slack) // n_shards + 1``. Overflowing rows are routed to an out-of-bounds
slot (never clobbering an in-capacity row) and arrive as zeros unless
checked:

  * ``max_pair_load(perm, n_shards)`` — host-side: the worst (src, dst)
    bucket load of a permutation; compare against ``pair_capacity``.
  * ``assert_pair_capacity(perm, ...)`` — host-side hard failure.
  * ``shuffle_shard_map(..., check_capacity=True)`` — in-graph
    ``jax.debug.callback`` on the plan's replicated overflow count that
    raises from inside the jitted program.

Streaming (double-buffered) collector: the exchange is also exposed as two
halves so a software pipeline can put client compute between them —
``plan_exchange_issue`` buckets a slab's rows and hands them to
``all_to_all`` (the in-flight buffer slot), ``plan_exchange_complete``
places the received rows. The slot carries its plan, and the whole shuffle
keeps the inverse-permutation routing: the backward pass is one more
issue/complete exchange with the plan built from the inverse permutation.

Sub-mesh streaming: when the grouped layout QUALIFIES — every flush group
covers the same number ``S`` of whole shard slabs, with ``b % S == 0``
(``submesh_slice_size``) — the streaming path recovers the dense fast path
too. Group ``g``'s rows live exactly on shards ``[g*S, (g+1)*S)``, so its
exchange never needs the rest of the mesh: ``build_submesh_route_plans``
builds a DENSE per-group plan (``may_drop=False``, cap exactly ``b/S``,
no overflow counter, no pad row) whose collective is one ``all_to_all``
restricted to the owning shard slice via ``axis_index_groups``
(``submesh_axis_groups``). The plan's index arrays keep the full-mesh
``(n_shards, b)`` shape so the exchange still runs as ONE pool-width
shard_map — shards outside the slice exchange zero-index garbage within
their own slice and their output rows are masked off by the caller.

Shape/layout contract (all entry points):

  * ``x``: ``(N, ...)`` with dim 0 sharded into ``n_shards`` equal
    ``b = N // n_shards``-row slabs over the mesh ``axis`` — a bare axis
    name on the 1-D mesh, or the pod-major name tuple ``("pod", "data")``
    of the 2-D multi-host mesh, whose flattened (pod-major) device index
    is the shard index (``mesh_axis_size`` multiplies the named sizes and
    ``_plan_collective`` scopes each plan's collective: whole-mesh plans
    run one ``all_to_all`` over the name tuple; pod-local sub-mesh plans
    run over the inner axis only under ``axis_index_groups``);
  * ``perm``: ``(N,)`` int, replicated; output row ``i`` is ``x[perm[i]]``;
  * slack/capacity: each (src, dst) shard pair exchanges at most
    ``pair_capacity(N, n_shards, slack)`` rows — or exactly
    ``exact_pair_cap(N, n_shards, group_sizes)`` on the dense path —

    >>> pair_capacity(64, 8, 1.0)   # slack-buffered: b/S + 1 per pair
    2
    >>> exact_pair_cap(64, 8)       # dense balanced: exactly b/S per pair
    1
    >>> grouped_perm_slack(64, 8, [64])   # one global balanced flush
    1.0
    >>> int(pair_load(np.arange(8), 4).max())   # identity perm: diagonal
    2
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.platform import interpret
from repro.core.wire import (SCALE_BYTES, SCALE_LANES, WIRE_DTYPES,
                             is_quantized, pack_scales, resolve_wire_dtype,
                             unpack_scales, wire_itemsize)


def make_balanced_perm(key, n, num_shards):
    """Permutation that sends exactly B_local/num_shards rows between every
    (src, dst) shard pair: shuffle within shards, exchange equal blocks,
    shuffle within shards again — the composition is the collector shuffle
    actually deployed (IID-simulation quality equals a uniform shuffle after
    two rounds, see tests)."""
    assert n % num_shards == 0
    b = n // num_shards
    assert b % num_shards == 0
    k1, k2, k3 = jax.random.split(key, 3)

    def shard_shuffle(key):
        keys = jax.random.split(key, num_shards)
        return jnp.concatenate([
            jax.random.permutation(keys[i], b) + i * b
            for i in range(num_shards)])

    p1 = shard_shuffle(k1)
    # block exchange: row j of shard i goes to shard (j mod S), position
    # determined by source
    blk = b // num_shards
    src = jnp.arange(n)
    shard = src // b
    pos = src % b
    dst_shard = pos // blk
    dst_pos = (pos % blk) + shard * blk
    p2 = dst_shard * b + dst_pos
    p3 = shard_shuffle(k3)
    # compose: out[i] = x[p1[p2[p3[i]]]]
    return p1[p2[p3]]


def group_fits_slabs(start, size, b):
    """Whether a contiguous flush group of ``size`` rows at ``start`` can
    be permuted without crossing a shard slab mid-group: it either covers
    whole ``b``-row slabs (balanced exchange) or lives entirely inside one
    (in-place shuffle, no exchange). The single predicate shared by the
    eager layout validator and the perm builder."""
    aligned = start % b == 0 and size % b == 0
    in_slab = start // b == (start + size - 1) // b
    return aligned, in_slab


def make_grouped_balanced_perm(key, n, num_shards, group_sizes):
    """Per-flush-group balanced permutations aligned to shard boundaries.

    ``group_sizes`` are contiguous row counts (summing to n) of the
    collector's flush groups (``collector.flush_group_sizes`` times the
    per-client rows). Rows never cross a group boundary — the sharded
    counterpart of ``collector.make_flush_perm`` — and within each group
    spanning S_g whole shards the permutation is a balanced exchange that
    routes exactly b/S_g rows between every shard pair of the group. A
    group contained in a single shard slab shuffles uniformly in place
    (no exchange). Requires every group to cover whole slabs or live
    inside one, and b divisible by S_g.

    Contract: ``key`` a PRNG key, ``n`` the pooled row count, and the
    returned ``(n,)`` permutation maps every row inside its own group —

    >>> import jax
    >>> p = make_grouped_balanced_perm(jax.random.PRNGKey(0), 16, 2,
    ...                                [8, 8])
    >>> bool((jnp.sort(p[:8]) == jnp.arange(8)).all())
    True
    """
    if len(group_sizes) <= 1:
        return make_balanced_perm(key, n, num_shards)
    b = n // num_shards
    parts, start = [], 0
    for f, size in enumerate(group_sizes):
        aligned, in_slab = group_fits_slabs(start, size, b)
        assert aligned or in_slab, (start, size, b)
        kf = jax.random.fold_in(key, f)
        if aligned and size // b > 1:
            sub = make_balanced_perm(kf, size, size // b)
        else:
            sub = jax.random.permutation(kf, size)
        parts.append(sub + start)
        start += size
    return jnp.concatenate(parts)


def grouped_perm_slack(n, num_shards, group_sizes):
    """Slack sizing the exchange buckets for a grouped balanced permutation:
    a group spanning S_g whole shards loads b/S_g rows on each of its shard
    pairs; groups inside a single slab keep all rows resident (self-pair
    load up to b). The buffer must hold the worst load. One global flush at
    b % S == 0 resolves to exactly 1.0, the drop-free balanced default."""
    b = n // num_shards
    return exact_pair_cap(n, num_shards, group_sizes) * num_shards / b


def exact_pair_cap(n, num_shards, group_sizes=None):
    """Exact worst (src, dst) bucket load of a (grouped) balanced
    permutation — deterministic by construction, so a plan built at this
    capacity is drop-free with ZERO slack padding (``may_drop=False``,
    the dense fast path). A group spanning S_g whole shards loads exactly
    b/S_g rows per pair inside the group; a group living inside one slab
    keeps all its rows resident (self-pair load b).

    >>> exact_pair_cap(64, 8)          # one global flush: b/S
    1
    >>> exact_pair_cap(64, 8, [32, 32])
    2
    """
    b = n // num_shards
    sizes = list(group_sizes) if group_sizes else [n]
    return max((b // (size // b)) if size % b == 0 else b
               for size in sizes)


def submesh_slice_size(n, n_shards, group_sizes):
    """Shards per owning slice when the grouped layout qualifies for the
    sub-mesh streaming exchange, else ``None``.

    Qualifies iff every flush group covers the SAME number ``S`` of whole
    ``b = n // n_shards``-row shard slabs (so contiguous groups partition
    the mesh axis into equal slices, group ``g`` owning shards
    ``[g*S, (g+1)*S)``) and ``b % S == 0`` (the balanced sub-permutation
    exchanges exactly ``b/S`` rows per in-slice shard pair — the dense,
    zero-slack capacity). One global flush qualifies trivially with the
    slice being the whole mesh.

    >>> submesh_slice_size(64, 8, [16, 16, 16, 16])   # S_g = 2 per group
    2
    >>> submesh_slice_size(64, 8, [64])               # one global flush
    8
    >>> submesh_slice_size(64, 8, [32, 16, 16]) is None  # unequal spans
    True
    """
    b = n // n_shards
    sizes = list(group_sizes) if group_sizes else [n]
    if any(size % b for size in sizes):
        return None                     # a group straddles a slab boundary
    spans = {size // b for size in sizes}
    if len(spans) != 1:
        return None                     # axis_index_groups need equal sizes
    slice_size = spans.pop()
    if b % slice_size or n_shards % slice_size:
        return None
    return slice_size


def submesh_axis_groups(n_shards, slice_size):
    """``axis_index_groups`` partitioning the mesh axis into contiguous
    ``slice_size``-shard slices — each flush group's ``all_to_all`` runs
    only within its owning slice."""
    return [list(range(j, j + slice_size))
            for j in range(0, n_shards, slice_size)]


def _np_balanced_perm(rng, n, num_shards):
    """Host-side replica of ``make_balanced_perm``'s structure (shard
    shuffles composed with the equal-block exchange) for load probing —
    same distribution, numpy-generated."""
    b = n // num_shards

    def shard_shuffle():
        return np.concatenate([rng.permutation(b) + i * b
                               for i in range(num_shards)])

    p1 = shard_shuffle()
    blk = b // num_shards
    src = np.arange(n)
    shard = src // b
    pos = src % b
    p2 = (pos // blk) * b + (pos % blk) + shard * blk
    p3 = shard_shuffle()
    return p1[p2[p3]]


@functools.lru_cache(maxsize=None)
def _balanced_stream_slack_cached(n, num_shards, span, probes, seed, margin):
    rng = np.random.default_rng(seed)
    worst = 0
    for _ in range(probes):
        perm = (_np_balanced_perm(rng, n, span) if span > 1
                else rng.permutation(n))
        worst = max(worst, max_pair_load(perm, num_shards))
    b = n // num_shards
    # never exceed the capacity-safe default slack = num_shards
    # (cap = b + 1 per pair holds ANY permutation of the group)
    return min((worst + margin) * num_shards / b, float(num_shards))


def balanced_stream_slack(n, num_shards, span, *, probes=16, seed=0,
                          margin=1):
    """Auto-size the streamed whole-mesh fallback's exchange slack for one
    BALANCED flush group by probing ``max_pair_load`` over sample draws of
    the group's actual permutation family: ``span`` is the number of
    original shard slabs the group covers — its grouped-balanced
    sub-permutation is a balanced exchange over ``span`` blocks
    (``make_grouped_balanced_perm``), measured here against the ``n //
    num_shards``-row FINE slabs the fallback re-shards the group into
    (``span <= 1`` groups shuffle uniformly in place). The bound is
    empirical — pair it with ``check_capacity=True`` — clamped at the old
    capacity-safe ``num_shards`` default so it can only shrink the buffer,
    and memoized like ``uniform_auto_slack`` so re-traces never re-probe."""
    return _balanced_stream_slack_cached(n, num_shards, span, probes, seed,
                                         margin)


@functools.lru_cache(maxsize=None)
def _uniform_auto_slack_cached(n, num_shards, group_sizes, probes, seed,
                               margin):
    rng = np.random.default_rng(seed)
    sizes = list(group_sizes) if group_sizes else [n]
    worst = 0
    for _ in range(probes):
        parts, start = [], 0
        for size in sizes:
            parts.append(rng.permutation(size) + start)
            start += size
        worst = max(worst, max_pair_load(np.concatenate(parts), num_shards))
    b = n // num_shards
    return (worst + margin) * num_shards / b


def uniform_auto_slack(n, num_shards, group_sizes=None, *, probes=16,
                       seed=0, margin=1):
    """Auto-size the exchange slack for paper-faithful uniform shuffles by
    probing ``max_pair_load`` over sample permutations (honouring flush
    groups when given) and padding by ``margin`` rows. The bound is
    empirical, not worst-case — pair it with ``check_capacity=True`` so an
    unlucky draw raises instead of silently dropping rows.

    The host-side probing is memoized on ``(n, num_shards, group_sizes,
    probes, seed, margin)``, so re-tracing a jitted epoch never re-runs
    the ``probes`` sample permutations."""
    key = tuple(group_sizes) if group_sizes is not None else None
    return _uniform_auto_slack_cached(n, num_shards, key, probes, seed,
                                      margin)


def axis_tuple(axis):
    """Collector mesh axis as a tuple of axis names: the 1-D mesh passes a
    bare string (``"data"``), the 2-D multi-host mesh a pod-major tuple
    (``("pod", "data")``) whose flattened index is the shard index."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def mesh_axis_size(mesh, axis):
    """Number of shards along ``axis`` of a mesh — the product of the named
    sizes when ``axis`` is a tuple (the flattened pod-major shard count of
    a 2-D ``("pod", "data")`` collector mesh)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for name in axis_tuple(axis):
        out *= sizes[name]
    return out


def pair_capacity(n, n_shards, slack):
    """Rows the exchange buffer holds per (src, dst) shard pair."""
    b = n // n_shards
    return int(b * slack) // n_shards + 1


def pair_load(perm, n_shards):
    """Host-side (src, dst) bucket-load matrix of a permutation.

    ``load[s, d]`` = rows that shard ``s`` must ship to shard ``d`` under
    ``out[i] = x[perm[i]]`` with both arrays row-sharded into ``n_shards``
    equal slabs."""
    perm = np.asarray(perm)
    n = perm.shape[0]
    assert n % n_shards == 0, (n, n_shards)
    b = n // n_shards
    dst = np.arange(n) // b          # destination shard of each output row
    src = perm // b                  # source shard of the row it pulls
    load = np.zeros((n_shards, n_shards), np.int64)
    np.add.at(load, (src, dst), 1)
    return load


def max_pair_load(perm, n_shards):
    """Worst bucket load — a perm is drop-free iff this <= pair_capacity."""
    return int(pair_load(perm, n_shards).max())


def assert_pair_capacity(perm, n_shards, *, slack):
    """Host-side guard: raise before launching an exchange that would drop
    rows."""
    n = np.asarray(perm).shape[0]
    cap = pair_capacity(n, n_shards, slack)
    worst = max_pair_load(perm, n_shards)
    if worst > cap:
        raise ValueError(
            f"collector exchange would drop rows: max (src, dst) load "
            f"{worst} exceeds capacity {cap} (n={n}, shards={n_shards}, "
            f"slack={slack}); raise slack or use make_balanced_perm")


def _raise_on_overflow(count):
    if int(count) > 0:
        raise RuntimeError(
            f"shuffle_shard_map dropped {int(count)} rows: per-pair bucket "
            f"capacity exceeded — raise slack or use make_balanced_perm")


# --------------------------------------------------------------------------
# route plans


@dataclasses.dataclass(frozen=True)
class RoutePlan:
    """Precomputed routing metadata of one exchange direction.

    Built once per (replicated) permutation and shared across every use of
    that direction — the forward exchange, the custom-VJP backward
    exchange, and the streaming collector's ``route_back``. Both exchange
    sides are pure row gathers driven by the plan:

      * ``send_idx``: ``(n_shards, n_shards * cap)`` int32 — on shard
        ``s``, flattened (destination shard, bucket slot) -> local source
        row. Slots no row occupies point at row 0; they are never read on
        the receive side, so no masking or zero-fill of the send buffer is
        needed.
      * ``recv_idx``: ``(n_shards, b)`` int32 — on shard ``d``, local
        output row -> flattened (source shard, bucket slot) of the
        received block. On slack-buffered plans (``may_drop=True``) a
        dropped row points at the appended zero pad row ``n_shards*cap``.
      * ``overflow``: replicated count of rows exceeding ``cap`` (the rows
        a ``check_capacity`` callback reports); ``None`` on dense plans,
        whose loads are deterministic.

    Static metadata: ``n`` (global rows), ``n_shards``, ``cap`` (bucket
    rows per shard pair), ``may_drop``. ``slice_size`` is ``None`` for a
    whole-mesh exchange; a sub-mesh plan (``build_submesh_route_plans``)
    sets it to the owning slice's shard count ``S`` and the collective
    runs under ``axis_index_groups`` of that width. ``dense`` means the
    send buffer has zero slack padding: the participating shard count
    times ``cap`` equals the ``b``-row slab, with drops impossible.
    """
    send_idx: jax.Array
    recv_idx: jax.Array
    overflow: Optional[jax.Array]
    n: int
    n_shards: int
    cap: int
    may_drop: bool
    slice_size: Optional[int] = None

    @property
    def dense(self):
        shards = self.slice_size or self.n_shards
        return (not self.may_drop
                and shards * self.cap == self.n // self.n_shards)


jax.tree_util.register_dataclass(
    RoutePlan, data_fields=["send_idx", "recv_idx", "overflow"],
    meta_fields=["n", "n_shards", "cap", "may_drop", "slice_size"])


def inverse_permutation_scatter(perm):
    """O(n) scatter-based inverse permutation: ``inv[perm[i]] = i``.

    Replaces the exchange path's ``argsort`` (O(n log n), and previously
    re-derived on every call, forward and backward)."""
    n = perm.shape[0]
    return jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))


def _build_one_plan(out_pos, n_shards, cap, may_drop):
    """Plan of the exchange whose source row ``g`` lands at global output
    position ``out_pos[g]`` (i.e. ``out_pos`` is the inverse of the
    permutation being applied). O(n * n_shards), no sorts."""
    n = out_pos.shape[0]
    b = n // n_shards
    g = jnp.arange(n, dtype=jnp.int32)
    src_shard = g // b
    local_row = g % b
    dest = (out_pos // b).astype(jnp.int32)
    # rank of row g within its (src_shard, dest) bucket, in ascending-g
    # order: a per-slab running count of destinations (one-hot cumsum) —
    # both exchange sides read the same rank, so any consistent order works
    oh = jax.nn.one_hot(dest.reshape(n_shards, b), n_shards,
                        dtype=jnp.int32)
    cum = jnp.cumsum(oh, axis=1)
    rank = (jnp.take_along_axis(
        cum, dest.reshape(n_shards, b, 1), axis=2) - 1).reshape(n)
    ok = rank < cap
    # overflowing rows go to an OOB slot and are DROPPED by the scatter —
    # they can never clobber an in-capacity row's slot
    slot = jnp.where(ok, dest * cap + rank, n_shards * cap)
    send_idx = jnp.zeros((n_shards, n_shards * cap), jnp.int32).at[
        src_shard, slot].set(local_row, mode="drop")
    out_local = jnp.where(ok, out_pos % b, b)
    init = (jnp.full((n_shards, b), n_shards * cap, jnp.int32)
            if may_drop else jnp.zeros((n_shards, b), jnp.int32))
    recv_idx = init.at[dest, out_local].set(src_shard * cap + rank,
                                            mode="drop")
    overflow = jnp.sum(~ok).astype(jnp.int32) if may_drop else None
    return RoutePlan(send_idx, recv_idx, overflow, int(n), n_shards,
                     int(cap), bool(may_drop))


def build_route_plan(perm, n_shards, *, cap, may_drop=True):
    """Forward-direction plan of ``out[i] = x[perm[i]]``.

    Contract: ``may_drop=False`` asserts the permutation's max (src, dst)
    pair load is <= ``cap`` (true by construction for (grouped-)balanced
    perms at ``exact_pair_cap``); routing under a violating perm is
    undefined — keep ``may_drop=True`` (and ``check_capacity``) for any
    permutation whose loads are not deterministic."""
    perm = perm.astype(jnp.int32)
    return _build_one_plan(inverse_permutation_scatter(perm), n_shards,
                           cap, may_drop)


def build_route_plans(perm, n_shards, *, cap, may_drop=True):
    """(forward, backward) plans of a permutation, sharing one O(n)
    inverse: the backward exchange applies ``argsort(perm)``, whose
    inverse is ``perm`` itself — so BOTH plans come from the same two
    arrays and the gradient de-shuffle re-derives nothing. The bucket-load
    matrix of the inverse permutation is the transpose of the forward
    one, so one ``cap`` covers both directions."""
    perm = perm.astype(jnp.int32)
    inv = inverse_permutation_scatter(perm)
    fwd = _build_one_plan(inv, n_shards, cap, may_drop)
    bwd = _build_one_plan(perm, n_shards, cap, may_drop)
    return fwd, bwd


def _embed_slice_plan(plan, slice_index, n_shards):
    """Embed a slice-local dense plan (built over ``S = plan.n_shards``
    shards) into full-mesh-shaped ``(n_shards, b)`` index arrays at rows
    ``[slice_index * S, (slice_index + 1) * S)``. Shards outside the slice
    keep zero indices: within their own slice's collective they gather and
    scatter garbage whose output rows the caller masks off."""
    slice_size = plan.n_shards
    b = plan.recv_idx.shape[1]
    j0 = slice_index * slice_size
    embed = lambda idx: jnp.zeros((n_shards, b), jnp.int32).at[
        j0:j0 + slice_size].set(idx)
    return RoutePlan(embed(plan.send_idx), embed(plan.recv_idx), None,
                     n_shards * b, n_shards, plan.cap, False,
                     slice_size=slice_size)


def build_submesh_route_plans(sub_perm, slice_index, n_shards, slice_size):
    """(forward, backward) DENSE plans of flush group ``slice_index``'s
    sub-permutation, routed only over the group's owning ``slice_size``-
    shard slice (sub-mesh streaming — the layout must satisfy
    ``submesh_slice_size``).

    ``sub_perm`` is the group's ``(n_g,)`` permutation in group-local
    coordinates (``n_g = slice_size * b``). The slice-local exchange is
    built exactly like the whole-mesh dense path — exact per-pair capacity
    ``b / slice_size``, ``may_drop=False``, no overflow counter, no pad
    row — then embedded into full-mesh-shaped index arrays so the exchange
    runs as one pool-width shard_map whose collective carries
    ``axis_index_groups`` of the slice width. Both plans share one O(n_g)
    scatter inverse, exactly like ``build_route_plans``."""
    sub_perm = sub_perm.astype(jnp.int32)
    n_g = sub_perm.shape[0]
    b = n_g // slice_size
    cap = b // slice_size
    inv = inverse_permutation_scatter(sub_perm)
    fwd = _build_one_plan(inv, slice_size, cap, False)
    bwd = _build_one_plan(sub_perm, slice_size, cap, False)
    return (_embed_slice_plan(fwd, slice_index, n_shards),
            _embed_slice_plan(bwd, slice_index, n_shards))


# --------------------------------------------------------------------------
# plan-driven exchange: gather -> ONE all_to_all -> gather


def _shard_map_maybe_norep(local, *, mesh, in_specs, out_specs, norep):
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if norep:
        # pallas_call has no replication rule; the kernel only touches
        # per-shard rows so skipping the check is sound
        return jax.shard_map(local, **kwargs, check_vma=False)
    return jax.shard_map(local, **kwargs)


def _gather_rows(x, idx, *, use_kernel, bucket_shape=None):
    """Row gather ``x[idx]``, optionally through the fused Pallas kernels:
    ``bucket_shape=(S, cap)`` routes through the two-level ``bucket_permute``
    (send side), ``None`` through the flat ``unbucket_permute`` mirror
    (receive side)."""
    if use_kernel and jnp.issubdtype(x.dtype, jnp.floating):
        from repro.kernels.collector_permute.ops import (bucket_permute,
                                                         unbucket_permute)
        if bucket_shape is not None:
            return bucket_permute(x, idx.reshape(bucket_shape),
                                  interpret=interpret())
        return unbucket_permute(x, idx, interpret=interpret())
    return x[idx]


def _resolve_wire(x_dtype, wire_dtype):
    """Effective wire dtype name for one payload, or ``None`` to ship it
    as-is: no wire dtype requested, a NON-FLOATING payload (the label pool
    rides the same plans — int rows never quantize, mirroring the kernel
    gate), or a wire dtype the payload already is in (the bf16 compute
    path ships bf16 natively; re-casting would be a no-op)."""
    wire = resolve_wire_dtype(wire_dtype)
    if wire is None or not jnp.issubdtype(x_dtype, jnp.floating):
        return None
    if jnp.dtype(WIRE_DTYPES[wire]) == jnp.dtype(x_dtype):
        return None
    return wire


def _quant_send_payload(x_loc, send_idx, S, cap, wire, use_kernel):
    """Send side of a quantized exchange: fused quantize-gather of the
    local rows into bucket order, with each row's f32 scale bitcast into
    ``SCALE_LANES`` trailing one-byte columns — ``(S*cap, d + LANES)``,
    ONE wire-dtype operand for the ``all_to_all`` (the scale sidecar
    never becomes a second collective)."""
    if use_kernel:
        from repro.kernels.quant_permute.ops import quant_bucket_permute
        q, scales = quant_bucket_permute(
            x_loc, send_idx.reshape(S, cap), wire_dtype=wire,
            interpret=interpret())
    else:
        from repro.kernels.quant_permute.ref import quant_bucket_permute_ref
        x2 = x_loc.reshape(x_loc.shape[0], -1)
        q, scales = quant_bucket_permute_ref(x2, send_idx, wire)
    return jnp.concatenate([q, pack_scales(scales, wire)], axis=1)


def _dequant_recv_payload(flat, recv_idx, wire, out_dtype, feat_shape,
                          use_kernel):
    """Receive side: split the flat ``(R, d + LANES)`` wire block back
    into rows and scales, and fused dequantize-gather into output order
    in the compute dtype. The slack pad row is all-zero — its packed
    scale unpacks to 0.0, so dropped rows dequantize to exact zeros."""
    d = flat.shape[1] - SCALE_LANES
    q, lanes = flat[:, :d], flat[:, d:]
    scales = unpack_scales(lanes)
    if use_kernel:
        from repro.kernels.quant_permute.ops import dequant_unbucket_permute
        out2 = dequant_unbucket_permute(
            q, scales, recv_idx, out_dtype=jnp.dtype(out_dtype),
            interpret=interpret())
    else:
        from repro.kernels.quant_permute.ref import (
            dequant_unbucket_permute_ref)
        out2 = dequant_unbucket_permute_ref(q, scales, recv_idx, out_dtype)
    return out2.reshape((recv_idx.shape[0],) + feat_shape)


def _plan_exchange_spec(plan):
    """(bucket shard count, cap) shaping a plan's send/receive buckets:
    whole-mesh plans exchange ``(n_shards, cap)`` blocks, sub-mesh plans
    ``(slice_size, cap)`` blocks confined to the owning slice."""
    if plan.slice_size is None:
        return plan.n_shards, plan.cap
    return plan.slice_size, plan.cap


def _plan_collective(plan, mesh, axis):
    """(collective axis name(s), axis_index_groups) of a plan's
    ``all_to_all`` on ``mesh``.

    Whole-mesh plans run over the full collector axis — the bare axis name
    on a 1-D mesh, the pod-major name tuple on a 2-D ``("pod", "data")``
    mesh (participants flatten pod-major, matching the
    ``P(("pod", "data"))`` dim-0 sharding, so the flattened shard index IS
    the plan's shard index). Sub-mesh plans confine each flush group's
    collective to its owning contiguous slice:

      * 1-D mesh: ``axis_index_groups`` partitioning the whole axis into
        ``slice_size``-shard slices;
      * 2-D mesh, slice within a pod (``per_pod % slice_size == 0``): the
        collective runs over the INNER (data) axis only, with
        ``axis_index_groups`` partitioning ``[0, per_pod)`` — every pod
        exchanges its own slices simultaneously, no cross-pod traffic;
      * a slice straddling pods has no grouped-collective expression and
        must be disqualified upstream (``StreamingAllToAll.submesh_slices``
        gates it to the whole-mesh fallback) — reaching here raises.
    """
    names = axis_tuple(axis)
    if plan.slice_size is None or plan.slice_size == plan.n_shards:
        coll = names[0] if len(names) == 1 else names
        return coll, None
    if len(names) == 1:
        return names[0], submesh_axis_groups(plan.n_shards, plan.slice_size)
    inner = mesh_axis_size(mesh, names[-1])
    if inner % plan.slice_size:
        raise ValueError(
            f"sub-mesh slice of {plan.slice_size} shards straddles the "
            f"pod boundary (per-pod axis {names[-1]!r} holds {inner} "
            f"shards) — the layout gate must route this group over the "
            f"whole-mesh fallback")
    return names[-1], submesh_axis_groups(inner, plan.slice_size)


def plan_payload_bytes(plan, row_elems, itemsize, *, wire_dtype=None):
    """Wire bytes of ONE collective under a plan: every one of the
    ``n_shards`` participating shards ships its ``(S, cap)`` bucket block
    — ``S = slice_size`` under sub-mesh ``axis_index_groups``, else the
    whole axis — of ``row_elems``-element rows at ``itemsize`` bytes per
    element. Shapes are dtype-independent, so a bf16 exchange is exactly
    half the f32 bytes at a matched plan.

    ``wire_dtype`` overrides ``itemsize`` with the wire format's exact
    accounting: rows ship at the wire itemsize, and quantized wires add
    ``SCALE_BYTES`` per row (the bitcast f32 scale lanes packed into the
    payload operand) — int8 rows cost ``row_elems + 4`` bytes against
    f32's ``4 * row_elems``."""
    S, cap = _plan_exchange_spec(plan)
    rows = plan.n_shards * S * cap
    wire = resolve_wire_dtype(wire_dtype)
    if wire is None:
        return rows * row_elems * itemsize
    row_bytes = row_elems * wire_itemsize(wire)
    if is_quantized(wire):
        row_bytes += SCALE_BYTES
    return rows * row_bytes


def plan_exchange(x, plan, *, mesh, axis="data", use_kernel=False,
                  check_capacity=False, wire_dtype=None):
    """One full exchange under a route plan: bucket-gather this shard's
    rows into send layout, ship them with ONE ``all_to_all``, and gather
    the received block into output order. Not differentiable on its own —
    ``plan_shuffle`` supplies the VJP from the backward plan, and the
    streaming collector routes gradients explicitly.

    Deliberately NOT composed from ``plan_exchange_issue`` +
    ``plan_exchange_complete``: the sync exchange keeps both gathers and
    the collective in one shard_map region (one SPMD program, no sharded
    bucket intermediate crossing a shard_map boundary); the split halves
    exist so the streaming pipeline can put compute between them.
    tests/test_streaming.py pins the composition row-for-row equal.

    A sub-mesh plan (``plan.slice_size = S``) exchanges ``(S, cap)``
    buckets under ``axis_index_groups`` of the slice width instead —
    on a pool-width input only the owning slice's output rows are
    meaningful; the caller masks the rest. ``axis`` may be the pod-major
    name tuple of a 2-D mesh (``_plan_collective`` picks the collective
    scope).

    ``wire_dtype`` narrows the payload that crosses the collective (see
    ``core.wire``): bf16 is a cast around the unchanged exchange;
    int8/fp8 swap the two gathers for the fused quantize/dequantize
    gathers, with the per-row f32 scales bitcast into ``SCALE_LANES``
    trailing payload columns — still exactly ONE ``all_to_all``, its
    operand in the wire dtype. Non-floating payloads (the label pool)
    ship as-is regardless."""
    S, cap = _plan_exchange_spec(plan)
    coll_axis, groups = _plan_collective(plan, mesh, axis)
    check = check_capacity and plan.overflow is not None
    wire = _resolve_wire(x.dtype, wire_dtype)
    quant = wire is not None and is_quantized(wire)
    out_dtype, feat_shape = x.dtype, x.shape[1:]

    def local(x_loc, send_idx, recv_idx, *overflow):
        if check:
            # raised inside EVERY shard's program, so all collective
            # participants abort together instead of deadlocking the
            # all_to_all rendezvous on the survivors
            jax.debug.callback(_raise_on_overflow, overflow[0])
        if quant:
            payload = _quant_send_payload(x_loc, send_idx[0], S, cap,
                                          wire, use_kernel)
            recv = jax.lax.all_to_all(
                payload.reshape((S, cap, payload.shape[1])), coll_axis,
                0, 0, tiled=False, axis_index_groups=groups)
            flat = recv.reshape((S * cap, payload.shape[1]))
            if plan.may_drop:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)])
            return _dequant_recv_payload(flat, recv_idx[0], wire,
                                         out_dtype, feat_shape, use_kernel)
        x_w = x_loc.astype(WIRE_DTYPES[wire]) if wire else x_loc
        bucket = _gather_rows(x_w, send_idx[0], use_kernel=use_kernel,
                              bucket_shape=(S, cap))
        recv = jax.lax.all_to_all(
            bucket.reshape((S, cap) + x_loc.shape[1:]), coll_axis, 0, 0,
            tiled=False, axis_index_groups=groups)
        flat = recv.reshape((S * cap,) + x_loc.shape[1:])
        if plan.may_drop:
            flat = jnp.concatenate(
                [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)])
        out = _gather_rows(flat, recv_idx[0], use_kernel=use_kernel)
        return out.astype(out_dtype) if wire else out

    ex = _shard_map_maybe_norep(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)) + ((P(),) if check else ()),
        out_specs=P(axis), norep=use_kernel)
    args = (x, plan.send_idx, plan.recv_idx)
    return ex(*args + ((plan.overflow,) if check else ()))


def plan_exchange_issue(x, plan, *, mesh, axis="data", use_kernel=False,
                        check_capacity=False, wire_dtype=None):
    """First (issue) half of the split exchange: bucket-gather this shard's
    rows by destination and hand them to ``all_to_all``.

    Returns the in-flight buffer slot — ``(recv, plan, wire_ctx)`` where
    ``recv`` is the received bucket block (leading dim sharded over
    ``axis``) and ``wire_ctx`` is ``None`` or the static ``(wire name,
    compute dtype, feature shape)`` the completion side needs to undo the
    wire format — under a quantized wire ``recv`` is the packed
    wire-dtype block (rows + bitcast scale lanes), so neither the compute
    dtype nor the feature shape is recoverable from the array itself.
    The payload stays ONE array: positions and validity never travel over
    the wire, the completion side derives placement from the plan.
    Nothing about the slot depends on later compute, so a scheduler is
    free to overlap the collective with whatever runs between ``issue``
    and ``complete`` — the hook the double-buffered streaming collector
    pipelines client forwards into. A sub-mesh plan's collective runs
    under ``axis_index_groups`` of the owning slice's width."""
    S, cap = _plan_exchange_spec(plan)
    coll_axis, groups = _plan_collective(plan, mesh, axis)
    check = check_capacity and plan.overflow is not None
    wire = _resolve_wire(x.dtype, wire_dtype)
    quant = wire is not None and is_quantized(wire)
    ctx = None if wire is None else (wire, x.dtype, x.shape[1:])

    def local(x_loc, send_idx, *overflow):
        if check:
            jax.debug.callback(_raise_on_overflow, overflow[0])
        if quant:
            payload = _quant_send_payload(x_loc, send_idx[0], S, cap,
                                          wire, use_kernel)
            return jax.lax.all_to_all(
                payload.reshape((S, cap, payload.shape[1])), coll_axis,
                0, 0, tiled=False, axis_index_groups=groups)
        x_w = x_loc.astype(WIRE_DTYPES[wire]) if wire else x_loc
        bucket = _gather_rows(x_w, send_idx[0], use_kernel=use_kernel,
                              bucket_shape=(S, cap))
        return jax.lax.all_to_all(
            bucket.reshape((S, cap) + x_loc.shape[1:]), coll_axis, 0, 0,
            tiled=False, axis_index_groups=groups)

    issue = _shard_map_maybe_norep(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis)) + ((P(),) if check else ()),
        out_specs=P(axis), norep=use_kernel)
    return issue(*(x, plan.send_idx)
                 + ((plan.overflow,) if check else ())), plan, ctx


def plan_exchange_complete(slot, *, mesh, axis="data", use_kernel=False):
    """Second (complete) half: gather the received bucket block of a
    ``plan_exchange_issue`` slot into local output order, undoing the
    slot's wire format (cast back, or unpack scales + fused dequantize
    gather) into the compute dtype it was issued from."""
    recv, plan, ctx = slot
    S, cap = _plan_exchange_spec(plan)
    wire = None if ctx is None else ctx[0]
    quant = wire is not None and is_quantized(wire)

    def local(recv, recv_idx):
        flat = recv.reshape((S * cap,) + recv.shape[2:])
        if plan.may_drop:
            flat = jnp.concatenate(
                [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)])
        if quant:
            _, out_dtype, feat_shape = ctx
            return _dequant_recv_payload(flat, recv_idx[0], wire,
                                         out_dtype, feat_shape, use_kernel)
        out = _gather_rows(flat, recv_idx[0], use_kernel=use_kernel)
        return out.astype(ctx[1]) if wire else out

    complete = _shard_map_maybe_norep(
        local, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=P(axis), norep=use_kernel)
    return complete(recv, plan.recv_idx)


def plan_shuffle(x, plans, *, mesh, axis="data", use_kernel=False,
                 check_capacity=False, wire_dtype=None, wire_dtype_bwd=None):
    """Differentiable plan exchange: ``plans`` is the ``(forward,
    backward)`` pair from ``build_route_plans``. The registered VJP is the
    plan exchange with the BACKWARD plan (Algorithm 1's de-shuffle) —
    carried as the custom-VJP residual, so the backward pass issues one
    more ``all_to_all`` and re-derives no routing metadata. The VJP is
    registered at this level — not inside the shard_map body — because
    per-shard (data-dependent) custom_vjp residuals do not survive
    shard_map transposition with replication checking off.

    ``wire_dtype`` narrows the forward payload; gradients are
    STRAIGHT-THROUGH w.r.t. the dequantized values — the backward
    exchange routes cotangents of what the receiver actually saw, and is
    itself exact unless ``wire_dtype_bwd`` opts the gradient rows into a
    narrow wire too (the two legs are independent knobs because gradient
    rows are usually the more quantization-sensitive leg)."""
    impl = functools.partial(plan_exchange, mesh=mesh, axis=axis,
                             use_kernel=use_kernel)

    @jax.custom_vjp
    def shuf(x, fwd_plan, bwd_plan):
        return impl(x, fwd_plan, check_capacity=check_capacity,
                    wire_dtype=wire_dtype)

    def shuf_fwd(x, fwd_plan, bwd_plan):
        return impl(x, fwd_plan, check_capacity=check_capacity,
                    wire_dtype=wire_dtype), bwd_plan

    def shuf_bwd(bwd_plan, g):
        # exact for drop-free plans; under bucket overflow the forward
        # already lost rows (see check_capacity), so exactness is moot
        return impl(g, bwd_plan, wire_dtype=wire_dtype_bwd), None, None

    shuf.defvjp(shuf_fwd, shuf_bwd)
    return shuf(x, *plans)


# --------------------------------------------------------------------------
# perm-level entry points (plan built on the fly)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "slack", "use_kernel", "check_capacity"))
def shuffle_shard_map(x, perm, *, mesh, axis="data", slack=2.0,
                      use_kernel=False, check_capacity=False):
    """x: (N, ...) sharded over ``axis`` on dim 0; perm: (N,) replicated.

    Returns x[perm] with the same sharding, via one explicit all_to_all.

    Convenience wrapper over the plan machinery for callers holding a bare
    permutation: builds the (forward, backward) plans at the slack-derived
    capacity and applies ``plan_shuffle``. The round engine builds plans
    itself (``round.MeshAllToAll.prepare``) so one plan pair serves the
    label permute, the activation permute, and the backward exchange.

    ``use_kernel`` routes the local bucket gathers through the Pallas
    ``bucket_permute``/``unbucket_permute`` kernels (interpret-mode
    off-TPU); ``check_capacity`` adds an in-graph ``jax.debug.callback``
    that raises if any (src, dst) bucket overflows instead of zero-filling
    the overflowing rows."""
    n = x.shape[0]
    n_shards = mesh_axis_size(mesh, axis)
    cap = pair_capacity(n, n_shards, slack)
    plans = build_route_plans(perm, n_shards, cap=cap, may_drop=True)
    return plan_shuffle(x, plans, mesh=mesh, axis=axis,
                        use_kernel=use_kernel,
                        check_capacity=check_capacity)


def exchange_issue(x, perm, *, mesh, axis="data", slack=2.0,
                   use_kernel=False, check_capacity=False):
    """Perm-level convenience for ``plan_exchange_issue``: builds the
    forward plan at the slack-derived capacity and issues the exchange.
    Returns the in-flight ``(recv, plan, wire_ctx)`` slot."""
    n = x.shape[0]
    n_shards = mesh_axis_size(mesh, axis)
    cap = pair_capacity(n, n_shards, slack)
    plan = build_route_plan(perm, n_shards, cap=cap, may_drop=True)
    return plan_exchange_issue(x, plan, mesh=mesh, axis=axis,
                               use_kernel=use_kernel,
                               check_capacity=check_capacity)


def exchange_complete(slot, n, *, mesh, axis="data"):
    """Perm-level convenience for ``plan_exchange_complete``; ``n`` is the
    global row count of the shuffled array (checked against the slot's
    plan). ``exchange_complete(exchange_issue(x, perm, ...), x.shape[0],
    ...)`` equals ``shuffle_shard_map(x, perm, ...)`` row for row."""
    _, plan, _ = slot
    assert plan.n == n, (plan.n, n)
    return plan_exchange_complete(slot, mesh=mesh, axis=axis)

"""Mesh-sharded round engines (the paper's schemes at fleet scale).

``engine.sfpl_epoch`` simulates every client on one device; the server-side
update over the pooled smashed-data batch is the scaling bottleneck (the
same framing as SplitFed, arXiv:2004.12088). The entrypoints here run the
SAME step bodies as the single-device engine — ``repro.core.round`` — with
a ``DataMesh`` placement over a ``("data",)`` axis, or over the 2-D
multi-host ``("pod", "data")`` mesh (``make_data_mesh(..., pods=...)``
after ``launch.multihost.initialize``), whose pod-major flattened device
index is the collector shard index:

  * SFPL: client params / BN state / optimizer state are sharded on the
    leading client axis; the pooled smashed stack (N*B rows, client-major)
    inherits that sharding; the collector shuffle is ONE explicit
    ``jax.lax.all_to_all`` per exchange direction (``MeshAllToAll``
    strategy over a per-step precomputed ``RoutePlan`` — rows only, no
    position/validity traffic). Gradient DE-shuffling is not coded
    anywhere: the server loss is a function of the pre-shuffle pooled
    stack, so autodiff emits the exchange under the plan's backward half.
    Collector modes: "balanced" (drop-free block permutations; per-flush-
    group when ``alpha < 1``, aligned to shard boundaries) and "uniform"
    (paper-faithful uniform shuffle, slack auto-sized from probe
    ``max_pair_load`` with the in-graph capacity check forced on).
    Collector pipelines: "sync" (one blocking exchange per step — the
    parity oracle) and "double_buffered" (the paper's threshold-queue
    collector streamed: per-flush-group issue/complete exchanges
    overlapping the next group's client forward, final group drained
    after the loop). See docs/ARCHITECTURE.md for the dataflow.
  * SFLv2: the deliberate sequential client visitation (the catastrophic-
    forgetting mechanism under study) is preserved; the per-client batch
    axis — and with it the server-side stream — is sharded instead.

Numerics: the SFPL server update is permutation-invariant (mean loss +
batch-stat BN over the whole pool), so swapping the uniform pool shuffle
for balanced exchanges leaves the loss trajectory unchanged up to float
reduction order — every sharded entrypoint matches its single-device
counterpart within 1e-4 on the same seed (tests/test_engine_dist.py,
8 forced host devices).

``make_sfpl_epoch_sharded`` / ``make_sflv2_epoch_sharded`` jit the epoch
with the carried state DONATED, so parameter/optimizer buffers are updated
in place shard-by-shard.
"""
from __future__ import annotations

import jax

from repro.core import collector as C
from repro.core import round as RD
from repro.core.collector_dist import (group_fits_slabs, mesh_axis_size,
                                       submesh_slice_size)
from repro.core.engine import (SplitModel, jit_epoch,  # noqa: F401
                               make_client_update)
from repro.core.wire import resolve_wire_dtype


def make_data_mesh(num_shards=None, *, pods=None, axis="data",
                   pod_axis="pod"):
    """Collector mesh over (up to) all visible devices.

    ``pods=None`` (default) builds the historical 1-D ``(num_shards,)``
    mesh over ``axis``. With ``pods`` set, the mesh is the 2-D multi-host
    topology ``(pods, num_shards // pods)`` over ``(pod_axis, axis)`` —
    one pod per host process when built after
    ``launch.multihost.initialize`` (``jax.make_mesh`` orders devices
    process-major, so pod ``p`` is process ``p``'s local devices). The
    collector axis of a pod mesh is the name TUPLE ``(pod_axis, axis)``
    (``collector_axis`` resolves it), flattening pod-major to the shard
    index.

    >>> make_data_mesh(4, pods=3)
    Traceback (most recent call last):
        ...
    ValueError: pods=3 must be >= 1 and divide num_shards=4 (each pod \
holds an equal contiguous slice of the flattened shard axis)
    """
    num_shards = num_shards or len(jax.devices())
    if pods is None:
        return make_auto_mesh((num_shards,), (axis,))
    if pods < 1 or num_shards % pods:
        raise ValueError(
            f"pods={pods} must be >= 1 and divide num_shards="
            f"{num_shards} (each pod holds an equal contiguous slice of "
            f"the flattened shard axis)")
    return make_auto_mesh((pods, num_shards // pods), (pod_axis, axis))


def make_auto_mesh(shape, names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: shardings are
    placed by ``NamedSharding``/``shard_map`` and propagated by the
    partitioner, never carried in array types (jax's default Explicit axes
    reject the vmapped per-client conv's reshape). Every mesh of the
    repo is built here."""
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def collector_axis(mesh, *, axis="data", pod_axis="pod"):
    """The mesh axis (name or pod-major name tuple) the collector shards
    over: ``(pod_axis, axis)`` on a pod mesh, the bare ``axis`` on the
    1-D mesh. Every ``axis=None`` entrypoint below resolves through
    this, so callers never spell the tuple by hand."""
    return (pod_axis, axis) if pod_axis in mesh.axis_names else axis


def _resolve_axis(mesh, axis):
    return collector_axis(mesh) if axis is None else axis


def shard_dcml_state(st, mesh, *, axis=None):
    """Place a ``init_dcml_state`` tree on the mesh: client-stacked leaves
    sharded on their leading (client) axis, server leaves replicated.
    ``axis=None`` resolves via ``collector_axis`` (the pod-major tuple on
    a pod mesh); on a multi-host mesh each process contributes its
    addressable slice of the replicated host tree."""
    return RD.DataMesh(mesh, _resolve_axis(mesh, axis)).place_state(st)


def shard_client_data(data, mesh, *, axis=None):
    """Shard the per-client dataset {"x": (N, n, ...), "y": (N, n)} over the
    client axis (``axis=None``: ``collector_axis`` resolution)."""
    return RD.DataMesh(mesh, _resolve_axis(mesh, axis)).place_data(data)


def check_sfpl_layout(num_clients, batch_size, n_shards, *, alpha=1.0,
                      collector_mode="balanced",
                      collector_pipeline="sync",
                      collector_submesh=None, pods=None,
                      participation=None, wire_dtype=None,
                      wire_dtype_bwd=None):
    """Eager validation of the sharded SFPL layout; raises ValueError with
    an actionable message before any device work.

    Requirements: clients divide evenly over shards. In balanced mode,
    every flush group of the ``alpha`` accumulation threshold must cover
    whole shard slabs (so the grouped permutation never crosses a shard
    mid-group) or live entirely inside one slab (no exchange needed), and
    each multi-shard group's shard count must divide the slab so equal
    blocks can be exchanged. Uniform mode has no alignment requirement —
    its slack is probed from the actual flush-group structure. The
    ``double_buffered`` pipeline additionally needs every flush group's
    row count divisible by the shard count (each group is row-sharded
    over the whole mesh for its own issue/complete exchange) — UNLESS
    the layout qualifies for sub-mesh routing (``collector_submesh`` not
    ``False``, balanced mode, ``collector_dist.submesh_slice_size``),
    where each group's exchange is confined to its owning shard slice and
    the whole-mesh divisibility is moot. ``collector_submesh=True``
    demands qualification and raises otherwise.

    ``pods`` declares the 2-D ``("pod", "data")`` topology the shards run
    on (``make_data_mesh(n_shards, pods=...)``): it must divide
    ``n_shards``, and sub-mesh qualification tightens to POD-LOCAL slices
    — the owning slice must be the whole mesh or divide the per-pod shard
    count, since a slice straddling pods has no grouped-collective
    expression. Non-qualifying pod layouts are still valid (the streamed
    exchange falls back to the probed-slack whole-mesh path, logged), but
    ``collector_submesh=True`` raises on them.

    ``participation`` (optional elastic-participation mask,
    ``(num_clients,)`` or ``(steps, num_clients)``) is validated against
    the flush-group structure — wrong length, or any flush group left
    with zero surviving clients, raises a ValueError naming the group
    (``collector.check_participation``).

    ``wire_dtype`` / ``wire_dtype_bwd`` (the exchange wire-format knobs
    — see ``core.wire``) are name-checked here too, so a launcher typo
    fails with the supported set before any device work:

    >>> check_sfpl_layout(8, 8, 8, wire_dtype="int4")  # doctest: +ELLIPSIS
    Traceback (most recent call last):
        ...
    ValueError: unknown wire_dtype 'int4': expected one of ...

    Returns the flush-group row counts of the accepted layout:

    >>> check_sfpl_layout(8, 8, 8, wire_dtype="int8")
    [64]
    >>> check_sfpl_layout(8, 8, 8)
    [64]
    >>> check_sfpl_layout(8, 8, 8, alpha=0.5,
    ...     participation=[1, 1, 1, 1, 0, 0, 0, 0])  # doctest: +ELLIPSIS
    Traceback (most recent call last):
        ...
    ValueError: participation mask drops ALL clients of flush group 1 ...
    >>> check_sfpl_layout(8, 8, 8, alpha=0.5)
    [32, 32]
    >>> check_sfpl_layout(8, 8, 8, alpha=0.25, collector_submesh=True,
    ...                   collector_pipeline="double_buffered")
    [16, 16, 16, 16]
    >>> check_sfpl_layout(8, 8, 8, alpha=0.5, pods=2,
    ...                   collector_pipeline="double_buffered")
    [32, 32]
    >>> check_sfpl_layout(8, 8, 4, alpha=0.5, pods=4,
    ...                   collector_submesh=True,
    ...                   collector_pipeline="double_buffered")
    Traceback (most recent call last):
        ...
    ValueError: collector_submesh=True needs collector_mode='balanced' \
and every flush group covering the same number of whole shard slabs, \
with the slab divisible by that span — pod-local (the whole mesh, or \
dividing the 1 shards per pod) when pods=4; got mode='balanced', group \
sizes [32, 32] over 4 shards (num_clients=8, batch_size=8, alpha=0.5)
    """
    resolve_wire_dtype(wire_dtype)
    resolve_wire_dtype(wire_dtype_bwd)
    if num_clients % n_shards:
        raise ValueError(
            f"num_clients={num_clients} must divide evenly over "
            f"{n_shards} shards")
    if participation is not None:
        C.check_participation(num_clients, participation, alpha=alpha)
    if pods is not None and (pods < 1 or n_shards % pods):
        raise ValueError(
            f"pods={pods} must be >= 1 and divide n_shards={n_shards} "
            f"(each pod holds an equal contiguous slice of the flattened "
            f"shard axis)")
    n_pool = num_clients * batch_size
    b = n_pool // n_shards
    rows = [c * batch_size
            for c in C.flush_group_sizes(num_clients, alpha)]
    if collector_pipeline == "double_buffered":
        slices = submesh_slice_size(n_pool, n_shards, rows)
        if (slices is not None and pods is not None
                and slices != n_shards
                and (n_shards // pods) % slices):
            slices = None        # slice straddles a pod: whole-mesh path
        sub_ok = (collector_submesh is not False
                  and collector_mode == "balanced"
                  and slices is not None)
        if collector_submesh and not sub_ok:
            pod_req = ("" if pods is None else
                       f" — pod-local (the whole mesh, or dividing the "
                       f"{n_shards // pods} shards per pod) when "
                       f"pods={pods}")
            raise ValueError(
                f"collector_submesh=True needs collector_mode='balanced' "
                f"and every flush group covering the same number of whole "
                f"shard slabs, with the slab divisible by that span"
                f"{pod_req}; got "
                f"mode={collector_mode!r}, group sizes {rows} over "
                f"{n_shards} shards (num_clients={num_clients}, "
                f"batch_size={batch_size}, alpha={alpha})")
        bad = [size for size in rows if size % n_shards]
        if bad and not sub_ok:
            raise ValueError(
                f"double_buffered collector needs every flush group's row "
                f"count divisible by the {n_shards} shards (each group is "
                f"row-sharded over the whole mesh for its own exchange), "
                f"or a balanced layout qualifying for sub-mesh routing; "
                f"got group sizes {rows} (num_clients={num_clients}, "
                f"batch_size={batch_size}, alpha={alpha})")
    if collector_mode != "balanced":
        return rows
    start = 0
    for size in rows:
        aligned, in_slab = group_fits_slabs(start, size, b)
        if not (aligned or in_slab):
            raise ValueError(
                f"flush group of {size} rows at offset {start} is not "
                f"aligned to the {b}-row shard slabs: choose alpha/"
                f"num_clients/batch_size so every flush group covers whole "
                f"shards, or use collector_mode='uniform' (num_clients="
                f"{num_clients}, batch_size={batch_size}, shards="
                f"{n_shards}, alpha={alpha})")
        s_g = size // b
        if aligned and s_g > 1 and b % s_g:
            raise ValueError(
                f"balanced exchange needs the {b}-row shard slab divisible "
                f"by the {s_g} shards each flush group spans "
                f"(num_clients={num_clients}, batch_size={batch_size}, "
                f"shards={n_shards}, alpha={alpha})")
        start += size
    return rows


def fit_shards(num_clients, batch_size, *, scheme="sfpl", alpha=1.0,
               collector_mode="balanced", collector_pipeline="sync",
               collector_submesh=None, pods=None, max_shards=None,
               participation=None, wire_dtype=None, wire_dtype_bwd=None):
    """Largest shard count (up to the visible devices) the layout supports
    — shared by the launch drivers so every entrypoint degrades to a
    smaller mesh instead of crashing on indivisible configurations. With
    ``pods`` set, only shard counts divisible into ``pods`` equal pod
    slices are considered (``make_data_mesh(s, pods=pods)`` must be
    buildable), and sub-mesh qualification is checked pod-locally.

    ``participation`` and the wire-dtype names are validated ONCE up
    front (both checks are shard-independent): a bad mask or a wire
    typo raises immediately instead of being swallowed by the
    per-shard-count search and silently degrading to the 1-shard
    fallback."""
    resolve_wire_dtype(wire_dtype)
    resolve_wire_dtype(wire_dtype_bwd)
    if participation is not None:
        C.check_participation(num_clients, participation, alpha=alpha)
    max_shards = max_shards or len(jax.devices())
    for s in range(max_shards, 0, -1):
        if pods is not None and s % pods:
            continue
        if scheme == "sflv2":
            if batch_size % s == 0:
                return s
            continue
        try:
            check_sfpl_layout(num_clients, batch_size, s, alpha=alpha,
                              collector_mode=collector_mode,
                              collector_pipeline=collector_pipeline,
                              collector_submesh=collector_submesh,
                              pods=pods)
            return s
        except ValueError:
            continue
    # minimal fallback: one shard per pod (a (pods, 1) mesh), one shard
    # total on the 1-D mesh
    return pods if pods else 1


def sfpl_epoch_sharded(key, st, data, split: SplitModel, opt_c, opt_s, *,
                       mesh, num_clients, batch_size, bn_mode="cmsd",
                       alpha=1.0, use_kernel=None, slack=None,
                       check_capacity=False, axis=None,
                       collector_mode="balanced",
                       collector_pipeline="sync", stream_slack=None,
                       collector_submesh=None, participation=None,
                       wire_dtype=None, wire_dtype_bwd=None):
    """Drop-in sharded replacement for ``engine.sfpl_epoch``.

    Shape/layout contract: ``st`` is an ``init_dcml_state`` tree placed by
    ``shard_dcml_state`` (client-stacked leaves sharded on their leading
    client axis, server leaves replicated); ``data`` is the
    ``{"x": (N, n, ...), "y": (N, n)}`` per-client set placed by
    ``shard_client_data``; ``num_clients`` must divide over the mesh's
    ``axis``. Returns ``(st, losses)`` with ``losses`` of shape
    ``(n // batch_size,)``.

    ``alpha < 1`` runs per-flush-group balanced permutations aligned to
    shard boundaries; ``collector_mode="uniform"`` swaps in the paper-
    faithful uniform shuffle with auto-sized slack. ``slack=None``
    auto-sizes the exchange buffers (1.0 for one balanced global flush).
    ``collector_pipeline="double_buffered"`` streams the collector: each
    flush group's all_to_all is issued while the next group's client
    forward computes (``RD.StreamingAllToAll``), with the final in-flight
    group drained after the loop; ``"sync"`` (default) is the blocking
    single-exchange parity oracle. ``collector_submesh`` controls sub-mesh
    routing for the streamed pipeline: ``None`` (default) activates it
    automatically when the balanced grouped layout qualifies — each flush
    group's exchange is then a dense, zero-slack collective confined to
    its owning shard slice via ``axis_index_groups`` — ``True`` demands it
    (ValueError otherwise), ``False`` forces the whole-mesh fallback.
    ``stream_slack`` overrides the whole-mesh streaming fallback's
    per-group buffer sizing (default: probed per distinct group size in
    BOTH modes — ``balanced_stream_slack`` clamped at the capacity-safe
    ``n_shards`` ceiling for balanced permutations, ``uniform_auto_slack``
    for uniform — memoized, with the in-graph capacity check forced on).
    ``use_kernel=None`` (auto, the default) fuses the
    exchange's local bucket gathers into the Pallas
    ``bucket_permute``/``unbucket_permute`` kernels on TPU — where the
    one-pass HBM copies win — and keeps the jnp gathers elsewhere;
    pass True/False to force.

    ``axis=None`` resolves via ``collector_axis``: the bare ``"data"``
    name on a 1-D mesh, the pod-major ``("pod", "data")`` tuple on a pod
    mesh (``make_data_mesh(..., pods=...)``), where the layout check runs
    with the mesh's pod count so sub-mesh routing only claims pod-local
    slices.

    ``participation`` masks absent clients for the epoch (elastic
    participation — see ``round.sfpl_round``). A concrete (host) mask is
    validated eagerly against the flush-group structure; a traced mask
    (already inside a jit) skips the eager check, which the jitting
    caller must then run itself (``make_sfpl_epoch_sharded`` does).

    ``wire_dtype`` / ``wire_dtype_bwd`` narrow the exchange payloads
    (``core.wire``): smashed rows (and optionally the routed-back
    gradient rows) quantize/cast right before each collective and are
    restored right after — per-row f32 scales ride the same collective
    as packed payload columns, so the one-``all_to_all``-per-direction
    contract is unchanged.
    """
    axis = _resolve_axis(mesh, axis)
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    n_shards = mesh_axis_size(mesh, axis)
    pods = (mesh_axis_size(mesh, names[0]) if len(names) > 1 else None)
    part_host = (participation
                 if participation is not None
                 and not isinstance(participation, jax.core.Tracer)
                 else None)
    check_sfpl_layout(num_clients, batch_size, n_shards, alpha=alpha,
                      collector_mode=collector_mode,
                      collector_pipeline=collector_pipeline,
                      collector_submesh=collector_submesh, pods=pods,
                      participation=part_host, wire_dtype=wire_dtype,
                      wire_dtype_bwd=wire_dtype_bwd)
    placement = RD.DataMesh(mesh, axis)
    st, losses = RD.sfpl_round(
        key, st, data, split, opt_c, opt_s, num_clients=num_clients,
        batch_size=batch_size, bn_mode=bn_mode,
        collector=placement.collector(
            num_clients, alpha=alpha, mode=collector_mode, slack=slack,
            use_kernel=use_kernel, check_capacity=check_capacity,
            pipeline=collector_pipeline, stream_slack=stream_slack,
            submesh=collector_submesh, wire_dtype=wire_dtype,
            wire_dtype_bwd=wire_dtype_bwd),
        participation=participation)
    return placement.constrain_state(st), losses


def make_sfpl_epoch_sharded(split: SplitModel, opt_c, opt_s, data, *,
                            mesh, num_clients, batch_size, **kw):
    """Jitted hot loop: ``(key, st[, participation]) -> (st, losses)``
    with the carried state donated, so the sharded param/opt buffers are
    reused in place (``engine.jit_epoch``: ``data`` is a jit argument, a
    ``participation`` mask is validated on the host — so fully-dropped
    flush groups, and with them the streamed skip fast path, cannot arise
    here — then traced; ``.jitted`` is the ``(key, st, data[,
    participation])`` function)."""
    def epoch(key, st, data, participation=None):
        return sfpl_epoch_sharded(key, st, data, split, opt_c, opt_s,
                                  mesh=mesh, num_clients=num_clients,
                                  batch_size=batch_size,
                                  participation=participation, **kw)
    return jit_epoch(epoch, data, num_clients=num_clients,
                     alpha=kw.get("alpha", 1.0))


def sflv2_epoch_sharded(key, st, data, split: SplitModel, opt_c, opt_s, *,
                        mesh, num_clients, batch_size, aggregate_bn=True,
                        axis=None):
    """Drop-in sharded replacement for ``engine.sflv2_epoch``: the server
    stream is sharded over the per-client batch axis while the sequential
    client-visitation order is preserved bit-for-bit. State and data stay
    replicated (the visitation loop touches one client at a time); call it
    under jit (``make_sflv2_epoch_sharded``) so the batch sharding
    constraints drive the partitioner.

    Shape/layout contract: ``st`` is an UNSHARDED ``init_dcml_state``
    tree and ``data`` the unsharded ``{"x": (N, n, ...), "y": (N, n)}``
    per-client set (contrast ``sfpl_epoch_sharded``); ``batch_size`` must
    divide over the mesh's ``axis``. Returns ``(st, losses)`` with
    ``losses`` of shape ``(N, n // batch_size)`` in visitation order."""
    axis = _resolve_axis(mesh, axis)
    n_shards = mesh_axis_size(mesh, axis)
    if batch_size % n_shards:
        raise ValueError(
            f"batch_size={batch_size} must divide evenly over {n_shards} "
            f"shards to shard the SFLv2 server stream")
    return RD.sflv2_round(
        key, st, data, split, opt_c, opt_s, num_clients=num_clients,
        batch_size=batch_size, aggregate_bn=aggregate_bn,
        placement=RD.DataMesh(mesh, axis))


def make_sflv2_epoch_sharded(split: SplitModel, opt_c, opt_s, data, *,
                             mesh, num_clients, batch_size, **kw):
    """Jitted hot loop: ``(key, st) -> (st, losses)``, state donated;
    ``data`` rides through the jit boundary as an argument
    (``engine.jit_epoch``)."""
    def epoch(key, st, data):
        return sflv2_epoch_sharded(key, st, data, split, opt_c, opt_s,
                                   mesh=mesh, num_clients=num_clients,
                                   batch_size=batch_size, **kw)
    return jit_epoch(epoch, data, num_clients=num_clients)

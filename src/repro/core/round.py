"""Placement-agnostic DCML round engine.

The paper's schemes previously lived as two parallel engine stacks —
``engine.py`` (single-device) and ``engine_dist.py`` (mesh-sharded SFPL) —
duplicating the per-step structure and diverging on collector semantics.
This module is the single implementation both delegate to:

  * a ``Placement`` says WHERE state and batches live: ``SingleDevice``
    or a ``DataMesh`` over a ``("data",)`` axis;
  * a ``CollectorStrategy`` says HOW Algorithm 1's collect-shuffle-scatter
    runs: ``DenseTake`` (one-device ``jnp.take``), ``MeshAllToAll``
    (explicit ``all_to_all`` with balanced, grouped-balanced, or uniform
    permutations and auto-sized exchange slack), or ``StreamingAllToAll``
    (the same exchange double-buffered per flush group: issue/complete
    halves with the next group's client forward between them, drained
    after the last group — the paper's threshold-queue collector as a
    two-slot software pipeline).

The mesh strategies are driven by precomputed **route plans**
(``collector_dist.RoutePlan``): because the permutation is replicated,
``prepare`` builds the routing metadata — O(n) scatter inverse, per-row
destination shard, bucket slot, receive placement — ONCE per step and
``sfpl_round`` threads the prepared permutation through the scan body, so
the label permute, the activation permute, the custom-VJP backward
exchange, and the streaming ``route_back`` all share it. Balanced and
grouped-balanced modes run the dense fast path (exact per-pair capacity,
no overflow accounting, zero slack padding for one global flush).

Gradient DE-shuffling is never hand-derived: ``DenseTake`` and
``MeshAllToAll`` expose a differentiable ``permute`` and the server loss
is taken as a function of the PRE-shuffle pooled stack, so autodiff emits
the inverse route (dense scatter or the plan exchange with the backward
plan) and hands each client exactly its own activation gradients.
``StreamingAllToAll`` assembles the shuffled pool outside the loss (the
forwards must interleave with the exchanges), so it routes explicitly —
``route_back`` is the identical exchange under the backward plans.

Shape contract shared by every strategy: the pool is client-major,
``(num_clients * batch_size, ...)`` with row ``c * batch_size + j`` being
sample ``j`` of client ``c``; ``make_perm`` returns a replicated ``(n,)``
permutation that never crosses flush-group boundaries —

>>> from repro.core.collector import flush_group_sizes
>>> flush_group_sizes(8, 0.25)     # alpha=0.25: four 2-client flushes
[2, 2, 2, 2]

Flush groups (the paper's ``alpha`` accumulation threshold) work on every
placement: ``DenseTake`` shuffles within contiguous client groups, and
``MeshAllToAll`` builds per-flush-group balanced permutations aligned to
shard boundaries (``collector_dist.make_grouped_balanced_perm``) with
slack sized to the worst group's bucket load.

SFLv2's deliberate sequential client visitation (the catastrophic-
forgetting mechanism under study) is preserved on every placement:
``sflv2_round`` shards the per-client batch axis — and with it the
server-side update stream, the scaling bottleneck in SplitFed's framing —
never the visitation loop.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collector as C
from repro.core.bn_policy import fedavg, aggregate_bn_state
from repro.core.collector_dist import (
    _resolve_wire, axis_tuple, balanced_stream_slack, build_route_plans,
    build_submesh_route_plans, exact_pair_cap, make_grouped_balanced_perm,
    mesh_axis_size, pair_capacity, plan_exchange, plan_exchange_complete,
    plan_exchange_issue, plan_payload_bytes, plan_shuffle,
    submesh_slice_size, uniform_auto_slack)
from repro.kernels.platform import auto_use_kernel

logger = logging.getLogger(__name__)


class PreparedPerm(NamedTuple):
    """A step's permutation with its precomputed routing: ``plans`` is the
    strategy-specific payload — ``None`` for ``DenseTake``, one
    ``(forward, backward)`` ``RoutePlan`` pair for ``MeshAllToAll``, and a
    per-flush-group tuple of pairs for ``StreamingAllToAll``. Built once
    per scan step (``collector.prepare``) and shared by every use of the
    permutation in that step: the label permute, the activation permute,
    the custom-VJP backward exchange, and the streaming route_back."""
    perm: jax.Array
    plans: object


def resolve_use_kernel(flag):
    """``None`` means auto: the fused Pallas bucket kernels are on where
    they win — compiled TPU lowering — and off elsewhere (off-TPU they
    only run in interpret mode, which the CPU-harness benchmarks show
    losing to the jnp gathers)."""
    return auto_use_kernel(flag)


# --------------------------------------------------------------------------
# placements

@dataclasses.dataclass(frozen=True)
class SingleDevice:
    """Everything on one device — the simulation default."""

    def place_state(self, st):
        return st

    def place_data(self, data):
        return data

    def constrain_batch(self, tree):
        return tree

    def collector(self, num_clients, *, alpha=1.0, use_kernel=False, **_):
        return DenseTake(num_clients=num_clients, alpha=alpha,
                         use_kernel=use_kernel)


SINGLE = SingleDevice()


def _global_put(a, sharding):
    """Place a host array under ``sharding`` — ``jax.device_put`` when this
    process addresses every device of the mesh, else assembled from
    per-device host slices (each process of a multi-host mesh holds the
    full replicated host value, so any index of it is addressable)."""
    if sharding.is_fully_addressable:
        return jax.device_put(a, sharding)
    return jax.make_array_from_callback(
        np.shape(a), sharding, lambda idx: np.asarray(a)[idx])


@dataclasses.dataclass(frozen=True)
class DataMesh:
    """A device mesh: client-stacked state and the pooled smashed batch are
    sharded over ``axis``; server state stays replicated. ``axis`` is a
    bare axis name on the 1-D ``("data",)`` mesh, or the pod-major name
    tuple ``("pod", "data")`` of the 2-D multi-host mesh — dim 0 then
    shards jointly over both axes, pod-major, so the flattened device
    index is the collector shard index."""
    mesh: object
    axis: object = "data"

    @property
    def n_shards(self):
        return mesh_axis_size(self.mesh, self.axis)

    def state_shardings(self, st):
        """Sharding of each subtree of an ``init_dcml_state`` tree:
        client-stacked leaves on their leading (client) axis, server
        leaves replicated."""
        shard = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        return {k: shard if k in ("cp", "cbn", "copt") else repl
                for k in st}

    def place_state(self, st):
        """Place an ``init_dcml_state`` tree per ``state_shardings``."""
        sh = self.state_shardings(st)
        return {k: jax.tree_util.tree_map(lambda a: _global_put(a, sh[k]), v)
                for k, v in st.items()}

    def constrain_state(self, st):
        """Pin a traced state tree to ``state_shardings``: an epoch then
        returns its state in the layout it took it in (the epoch-end
        FedAvg would otherwise leave the client params replicated), so
        the next epoch reuses the same executable."""
        sh = self.state_shardings(st)
        return {k: jax.lax.with_sharding_constraint(v, sh[k])
                for k, v in st.items()}

    def place_data(self, data):
        """Shard the per-client dataset {"x": (N, n, ...), "y": (N, n)} over
        the client axis."""
        shard = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda a: _global_put(a, shard), data)

    def constrain_batch(self, tree):
        """Shard the leading (batch) axis of every leaf — the SFLv2 server
        stream runs data-parallel over the mesh without touching the
        sequential visitation order."""
        def c(a):
            spec = P(self.axis) if a.ndim >= 1 else P()
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_map(c, tree)

    def collector(self, num_clients, *, alpha=1.0, mode="balanced",
                  slack=None, use_kernel=None, check_capacity=False,
                  pipeline="sync", stream_slack=None, submesh=None,
                  wire_dtype=None, wire_dtype_bwd=None):
        if pipeline not in ("sync", "double_buffered"):
            raise ValueError(f"unknown collector pipeline {pipeline!r}: "
                             f"expected 'sync' or 'double_buffered'")
        common = dict(mesh=self.mesh, num_clients=num_clients,
                      axis=self.axis, mode=mode, alpha=alpha,
                      slack=slack, use_kernel=use_kernel,
                      check_capacity=check_capacity,
                      wire_dtype=wire_dtype, wire_dtype_bwd=wire_dtype_bwd)
        if pipeline == "double_buffered":
            return StreamingAllToAll(stream_slack=stream_slack,
                                     submesh=submesh, **common)
        if submesh:
            raise ValueError(
                "collector_submesh applies to the double_buffered "
                "pipeline (the sync exchange is already dense for "
                "balanced permutations); drop the flag or use "
                "pipeline='double_buffered'")
        return MeshAllToAll(**common)


# --------------------------------------------------------------------------
# collector strategies

@dataclasses.dataclass(frozen=True)
class DenseTake:
    """Algorithm 1's collector as a dense gather on one device."""
    num_clients: int
    alpha: float = 1.0
    use_kernel: bool = False

    def make_perm(self, key, n):
        return C.make_flush_perm(key, n, self.num_clients, self.alpha)

    def prepare(self, perm, n):
        """A dense gather needs no routing metadata beyond the perm."""
        return PreparedPerm(perm, None)

    def permute(self, x, prep):
        perm = prep.perm if isinstance(prep, PreparedPerm) else prep
        if self.use_kernel and jnp.issubdtype(x.dtype, jnp.floating):
            return C.shuffle(x, perm, use_kernel=True)
        return jnp.take(x, perm, axis=0)

    def exchange_bytes(self, prep, row_elems, dtype):
        """Wire bytes of one pool shuffle: a single-device gather never
        crosses a device boundary."""
        return 0


@dataclasses.dataclass(frozen=True)
class MeshAllToAll:
    """Algorithm 1's collector as one explicit ``all_to_all`` per step,
    driven by a per-step route plan (``prepare``).

    ``mode``:
      * "balanced" — balanced block permutations (grouped when alpha < 1)
        whose per-pair bucket loads are deterministic, so the plan runs
        the DENSE fast path: exact capacity (``exact_pair_cap``), no
        overflow accounting, zero slack padding for one global flush;
      * "uniform"  — the paper-faithful uniform shuffle (identical perm
        distribution to ``DenseTake``), slack-buffered with the capacity
        auto-sized from probe ``max_pair_load`` draws and the in-graph
        capacity check forced on so an unlucky permutation raises instead
        of dropping rows.
    ``slack=None`` auto-sizes per mode; pass a float to override (which
    forces the slack-buffered plan shape even in balanced mode).
    ``use_kernel=None`` (auto) fuses the local bucket gathers into the
    Pallas kernels on TPU and keeps the jnp gathers elsewhere.
    ``wire_dtype`` narrows the smashed rows' on-wire dtype
    (``core.wire.WIRE_DTYPE_NAMES``) — quantized wires ship per-row f32
    scales as packed extra payload columns of the same collective;
    ``wire_dtype_bwd`` independently opts the routed-back gradient rows
    into a narrow wire (default exact f32/compute-dtype backward).
    """
    mesh: object
    num_clients: int
    axis: object = "data"
    mode: str = "balanced"
    alpha: float = 1.0
    slack: Optional[float] = None
    use_kernel: Optional[bool] = None
    check_capacity: bool = False
    wire_dtype: Optional[str] = None
    wire_dtype_bwd: Optional[str] = None

    pipelined = False

    def group_rows(self, n):
        per_client = n // self.num_clients
        return [c * per_client
                for c in C.flush_group_sizes(self.num_clients, self.alpha)]

    def plan_spec(self, n):
        """(cap, may_drop) of the step exchange's route plan. Balanced
        modes get the exact capacity; they only skip overflow accounting
        (the dense path) when the caller did NOT ask for the in-graph
        capacity check — ``check_capacity=True`` must keep its raise-on-
        overflow contract even against a mis-declared permutation."""
        n_shards = mesh_axis_size(self.mesh, self.axis)
        if self.slack is not None:
            return pair_capacity(n, n_shards, self.slack), True
        rows = self.group_rows(n)
        if self.mode == "uniform":
            slack = uniform_auto_slack(
                n, n_shards, rows if len(rows) > 1 else None)
            return pair_capacity(n, n_shards, slack), True
        return exact_pair_cap(n, n_shards, rows), self.check_capacity

    def make_perm(self, key, n):
        if self.mode == "uniform":
            return C.make_flush_perm(key, n, self.num_clients, self.alpha)
        n_shards = mesh_axis_size(self.mesh, self.axis)
        return make_grouped_balanced_perm(key, n, n_shards,
                                          self.group_rows(n))

    def prepare(self, perm, n):
        """Build the (forward, backward) route plans once; every permute
        and the VJP exchange of the step share them."""
        cap, may_drop = self.plan_spec(n)
        n_shards = mesh_axis_size(self.mesh, self.axis)
        return PreparedPerm(perm, build_route_plans(
            perm, n_shards, cap=cap, may_drop=may_drop))

    def _check(self):
        return self.check_capacity or (self.mode == "uniform"
                                       and self.slack is None)

    def _use_k(self, dtype):
        return (resolve_use_kernel(self.use_kernel)
                and jnp.issubdtype(dtype, jnp.floating))

    def _wire(self, dtype):
        """Effective wire of a ``dtype`` payload: ``None`` when rows ship
        as computed (no-op wires, non-float payloads like the label
        permute), else the resolved wire name."""
        return _resolve_wire(jnp.dtype(dtype), self.wire_dtype)

    def permute(self, x, prep):
        if not isinstance(prep, PreparedPerm):
            prep = self.prepare(prep, x.shape[0])
        return plan_shuffle(
            x, prep.plans, mesh=self.mesh, axis=self.axis,
            use_kernel=self._use_k(x.dtype), check_capacity=self._check(),
            wire_dtype=self.wire_dtype, wire_dtype_bwd=self.wire_dtype_bwd)

    def exchange_bytes(self, prep, row_elems, dtype):
        """Wire bytes of one forward pool exchange (the activation
        ``all_to_all``) for ``row_elems``-element rows in ``dtype`` —
        ``collector_dist.plan_payload_bytes`` of the step's forward plan,
        in the strategy's EFFECTIVE wire dtype (scale sidecar included
        for quantized wires). Plan shapes are dtype-independent, so bf16
        smashed data is exactly half the f32 payload at a matched
        config, and an int8 wire is a quarter plus 4 scale bytes/row."""
        return plan_payload_bytes(prep.plans[0], row_elems,
                                  jnp.dtype(dtype).itemsize,
                                  wire_dtype=self._wire(dtype))


@dataclasses.dataclass(frozen=True)
class StreamingAllToAll(MeshAllToAll):
    """The paper's threshold-queue collector as a two-slot software
    pipeline: each flush group is exchanged with its OWN all_to_all, split
    into issue/complete halves, so the exchange of group ``k`` is in
    flight while the client forward of group ``k+1`` computes.

    Semantics are identical to ``MeshAllToAll`` with the same ``mode`` /
    ``alpha`` — the per-group exchange moves exactly the rows the one big
    grouped exchange would (the grouped permutation never crosses flush
    groups), so the shuffled pool, and with it the loss trajectory, is
    bit-comparable to the synchronous path. What changes is the dataflow:
    ``sfpl_round`` produces the pool group by group and ``streamed_shuffle``
    keeps one filled buffer slot in flight, draining the last one after
    the loop.

    Because the shuffled pool is assembled OUTSIDE the server loss (the
    forwards must interleave with the exchanges), gradient routing is
    explicit here: ``route_back`` runs the same per-group exchange with
    the inverse permutation — exactly what autodiff emits for the
    synchronous strategy's in-loss ``permute``.

    ``submesh`` selects the group-structured SUB-MESH exchange: when the
    grouped-balanced layout qualifies (``collector_dist.
    submesh_slice_size`` — every flush group covers the same number ``S``
    of whole shard slabs and ``b % S == 0``), each group's collective is
    confined to its owning ``S``-shard slice via ``axis_index_groups``
    and the per-group plan is DENSE: exact capacity ``b/S`` per in-slice
    pair, no overflow counter, no pad row, zero slack — each group's send
    buffer is exactly the ``b``-row slab per shard instead of the
    whole-mesh fallback's ``n_g + n_shards`` rows. ``None`` (default)
    auto-enables
    it exactly when the layout qualifies; ``True`` raises on layouts that
    don't; ``False`` forces the whole-mesh fallback. The pool-width
    dataflow also changes: the full client forward runs once (each
    shard's clients ARE its groups' rows — the forward is already
    slice-local), and the per-group collectives on disjoint slices
    pipeline against each other and the completes.

    ``stream_slack`` sizes the whole-mesh fallback's per-group exchange
    buffers (setting it opts OUT of sub-mesh routing — the fallback
    re-shards each group over the whole mesh, where group permutations
    have non-deterministic loads under the ``b_g = n_g / n_shards``-row
    fine slabs). The default ``None`` auto-sizes by PROBING each distinct
    group size's actual permutation family: uniform groups through
    ``uniform_auto_slack``, balanced groups through
    ``balanced_stream_slack`` (sample balanced block exchanges measured
    against the fine slabs, clamped at the capacity-safe ``n_shards``
    ceiling they used to default to). Both probes are memoized per
    ``(n_g, n_shards)``-shaped key and both force the in-graph capacity
    check on, exactly like the sync uniform path, so an unlucky draw
    raises instead of dropping rows.

    Layout contract: every flush group's row count must divide by the
    shard count (each group is row-sharded over the whole mesh for its
    exchange) OR the layout must qualify for sub-mesh routing;
    ``engine_dist.check_sfpl_layout(...,
    collector_pipeline="double_buffered")`` validates this eagerly.
    """
    stream_slack: Optional[float] = None
    submesh: Optional[bool] = None

    pipelined = True

    def group_bounds(self, n):
        """Static (start, stop) row ranges of the flush groups in the
        client-major pool."""
        bounds, start = [], 0
        for size in self.group_rows(n):
            bounds.append((start, start + size))
            start += size
        return bounds

    def client_groups(self):
        """Static (first, last+1) client ranges of the flush groups."""
        out, c0 = [], 0
        for c in C.flush_group_sizes(self.num_clients, self.alpha):
            out.append((c0, c0 + c))
            c0 += c
        return out

    def submesh_slices(self, n):
        """Shards per owning slice when sub-mesh routing is active for a
        ``n``-row pool, else ``None`` (auto-resolution of the ``submesh``
        knob). ``submesh=True`` raises on non-qualifying layouts with the
        disqualifying condition named. On a 2-D ``("pod", "data")`` mesh a
        qualifying slice must additionally stay POD-LOCAL (whole mesh, or
        dividing the per-pod shard count): a slice straddling pods has no
        grouped-collective expression, so those layouts fall back to the
        probed-slack whole-mesh exchange — logged, never silently
        dropped."""
        if self.submesh is False:
            return None
        reason, slices = None, None
        if self.mode != "balanced":
            reason = ("sub-mesh routing needs the deterministic per-pair "
                      "loads of collector_mode='balanced'; uniform "
                      "permutations fall back to the slack-buffered "
                      "whole-mesh exchange")
        elif self.slack is not None or self.stream_slack is not None:
            reason = ("an explicit slack/stream_slack override forces the "
                      "slack-buffered whole-mesh plan shape")
        else:
            n_shards = mesh_axis_size(self.mesh, self.axis)
            slices = submesh_slice_size(n, n_shards, self.group_rows(n))
            if slices is None:
                reason = ("every flush group must cover the same number "
                          "of whole shard slabs, with the slab divisible "
                          "by that span (collector_dist."
                          "submesh_slice_size)")
            else:
                names = axis_tuple(self.axis)
                if len(names) > 1 and slices != n_shards:
                    inner = mesh_axis_size(self.mesh, names[-1])
                    if inner % slices:
                        reason = (
                            f"a {slices}-shard slice straddles the pod "
                            f"boundary (per-pod axis {names[-1]!r} holds "
                            f"{inner} shards) — cross-pod flush groups "
                            f"take the probed-slack whole-mesh exchange")
                        slices = None
                        if not self.submesh:
                            logger.warning(
                                "sub-mesh routing disabled: %s", reason)
        if slices is None and self.submesh:
            raise ValueError(
                f"collector_submesh=True but the layout does not qualify "
                f"for the sub-mesh streaming exchange: {reason} "
                f"(num_clients={self.num_clients}, alpha={self.alpha}, "
                f"n={n}, shards="
                f"{mesh_axis_size(self.mesh, self.axis)})")
        return slices

    def _check(self):
        # BOTH whole-mesh fallback auto slacks are PROBED per group size
        # now (empirical, not worst-case) — uniform via
        # ``uniform_auto_slack``, balanced via ``balanced_stream_slack`` —
        # so the in-graph capacity check is forced on whenever they may be
        # in play. Dense sub-mesh plans carry no overflow counter, so the
        # flag is inert on that path.
        return self.check_capacity or (self.slack is None
                                       and self.stream_slack is None)

    def _sub_slack(self, n_g, span=1):
        """Whole-mesh fallback slack for one ``n_g``-row flush group.
        ``span`` is the number of original shard slabs the group covers
        (the block count of its grouped-balanced sub-permutation)."""
        if self.stream_slack is not None:
            return self.stream_slack
        n_shards = mesh_axis_size(self.mesh, self.axis)
        if self.mode == "uniform":
            # probed at the group's own row count — the memo key
            # (n_g, n_shards) is shared by every same-sized group and
            # every re-trace, so the probe permutations run once
            return uniform_auto_slack(n_g, n_shards)
        # balanced fallback: probe the group's actual permutation family
        # (balanced over ``span`` blocks, uniform in-slab at span <= 1)
        # against the fine b_g-row slabs, clamped at the capacity-safe
        # slack = n_shards ceiling (cap = b_g + 1 per pair) it replaces —
        # memoized like the uniform probe, checked in-graph like it too.
        # The sub-mesh path replaces this entirely: its per-group plans
        # are dense (cap exactly b/S, no slack) because the group never
        # leaves its own slice.
        return balanced_stream_slack(n_g, n_shards, span)

    def _sub_perm(self, perm, bounds):
        r0, r1 = bounds
        return jax.lax.slice_in_dim(perm, r0, r1, axis=0) - r0

    def prepare(self, perm, n):
        """Per-flush-group (forward, backward) route plans, built once per
        step and shared by the issue/complete exchanges AND ``route_back``
        — the streamed counterpart of ``MeshAllToAll.prepare``. With
        sub-mesh routing active, every pair is DENSE
        (``build_submesh_route_plans``); otherwise each group gets
        slack-buffered whole-mesh plans at its own ``_sub_slack``."""
        n_shards = mesh_axis_size(self.mesh, self.axis)
        slices = self.submesh_slices(n)
        b = n // n_shards
        plans = []
        for g, bounds in enumerate(self.group_bounds(n)):
            sub = self._sub_perm(perm, bounds)
            if slices is not None:
                plans.append(build_submesh_route_plans(
                    sub, g, n_shards, slices))
            else:
                n_g = bounds[1] - bounds[0]
                # slab span of the group's sub-permutation: >1 only for
                # groups that got a balanced block exchange
                # (make_grouped_balanced_perm's aligned, multi-slab case)
                span = n_g // b if n_g % b == 0 else 1
                cap = pair_capacity(n_g, n_shards,
                                    self._sub_slack(n_g, span))
                plans.append(build_route_plans(sub, n_shards, cap=cap,
                                               may_drop=True))
        return PreparedPerm(perm, tuple(plans))

    @staticmethod
    def _plans_are_submesh(prep):
        return prep.plans[0][0].slice_size is not None

    def permute(self, x, prep, skip=None):
        """Blocking whole-pool shuffle under the per-group plans (used for
        the label pool, which never interleaves with client compute):
        each sealed flush group is one plan exchange. Sub-mesh plans take
        the whole pool (each exchange is confined to its slice by
        ``axis_index_groups``) and the group outputs are mask-combined;
        fallback plans take the group's rows and the outputs concatenate.
        ``skip`` (per-group bools — elastic participation) passes a fully
        dropped group's rows through unexchanged: every row is masked
        downstream, so the collective would only move dead payload."""
        n = x.shape[0]
        if not isinstance(prep, PreparedPerm):
            prep = self.prepare(prep, n)
        parts = []
        for g, (r0, r1) in enumerate(self.group_bounds(n)):
            rows = (x if self._plans_are_submesh(prep)
                    else jax.lax.slice_in_dim(x, r0, r1, axis=0))
            if skip and skip[g]:
                parts.append(rows)
                continue
            parts.append(plan_shuffle(
                rows, prep.plans[g],
                mesh=self.mesh, axis=self.axis,
                use_kernel=self._use_k(x.dtype),
                check_capacity=self._check(),
                wire_dtype=self.wire_dtype,
                wire_dtype_bwd=self.wire_dtype_bwd))
        return self.assemble(parts, prep, n)

    def assemble(self, parts, prep, n):
        """Combine per-group exchange outputs into the shuffled pool."""
        if self._plans_are_submesh(prep):
            return _combine_slices(parts, self.group_bounds(n))
        return _concat_parts(parts)

    def issue(self, rows, prep, g):
        """Launch flush group ``g``'s exchange; returns the in-flight
        buffer slot (``collector_dist.plan_exchange_issue``). ``rows`` is
        the group's pooled rows on the fallback path, the WHOLE pool on
        the sub-mesh path (where the plan's ``axis_index_groups`` confine
        the collective to group ``g``'s slice)."""
        return plan_exchange_issue(
            rows, prep.plans[g][0], mesh=self.mesh, axis=self.axis,
            use_kernel=self._use_k(rows.dtype),
            check_capacity=self._check(), wire_dtype=self.wire_dtype)

    def complete(self, slot):
        """Land an in-flight buffer slot: the group's shuffled rows. The
        kernel decision reads the slot's wire context, not the received
        buffer — under a quantized wire ``recv`` is the packed int8/fp8
        block, but the gather lands compute-dtype rows."""
        recv, _, ctx = slot
        dtype = recv.dtype if ctx is None else ctx[1]
        return plan_exchange_complete(
            slot, mesh=self.mesh, axis=self.axis,
            use_kernel=self._use_k(dtype))

    def exchange_bytes(self, prep, row_elems, dtype, skip=None):
        """Wire bytes of one forward pool exchange: the sum of the
        per-flush-group collectives' ``plan_payload_bytes`` in the
        strategy's effective wire dtype. ``skip`` (per-group bools —
        elastic participation) excludes groups whose exchange is
        statically skipped: a fully dropped flush group's rows pass
        through unexchanged, so no collective runs and no bytes cross
        the wire for it."""
        itemsize = jnp.dtype(dtype).itemsize
        wire = self._wire(dtype)
        return sum(plan_payload_bytes(plans[0], row_elems, itemsize,
                                      wire_dtype=wire)
                   for g, plans in enumerate(prep.plans)
                   if not (skip and skip[g]))

    def route_back(self, g_shuf, prep, n, skip=None):
        """Algorithm 1's de-shuffle, explicit: the per-group exchange with
        the BACKWARD plan of the shared ``prepare`` hands each client its
        own activation gradients — move-for-move what autodiff emits for
        the synchronous path, so trajectories stay bit-comparable.
        ``skip`` mirrors the forward skip of a fully dropped flush group
        (its gradient rows are exact zeros — nothing to route)."""
        if not isinstance(prep, PreparedPerm):
            prep = self.prepare(prep, n)
        submesh = self._plans_are_submesh(prep)
        parts = []
        for g, (r0, r1) in enumerate(self.group_bounds(n)):
            rows = (g_shuf if submesh
                    else jax.lax.slice_in_dim(g_shuf, r0, r1, axis=0))
            if skip and skip[g]:
                parts.append(rows)
                continue
            parts.append(plan_exchange(
                rows, prep.plans[g][1], mesh=self.mesh, axis=self.axis,
                use_kernel=self._use_k(g_shuf.dtype),
                wire_dtype=self.wire_dtype_bwd))
        return self.assemble(parts, prep, n)


def _concat_parts(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _combine_slices(parts, bounds):
    """Assemble pool-width sub-mesh exchange outputs: part ``g`` is valid
    only at rows ``bounds[g]`` (its owning slice's slabs — the other
    shards exchanged garbage within their own slices). A row-index masked
    select keeps every array in the pool's home sharding — concatenating
    slices of a sharded pool would force a re-layout — and is exact under
    autodiff: the cotangent reaching part ``g`` is zero outside its slice,
    so each backward exchange contributes only its own slice's gradients."""
    if len(parts) == 1:
        return parts[0]
    out = parts[0]
    rows = jnp.arange(out.shape[0])
    for (r0, r1), part in zip(bounds[1:], parts[1:]):
        mask = ((rows >= r0) & (rows < r1)).reshape(
            (-1,) + (1,) * (part.ndim - 1))
        out = jnp.where(mask, part, out)
    return out


def streamed_shuffle(collector, prep, n, produce_group, skip=None):
    """Two-slot software pipeline over flush groups.

    ``prep`` is the step's ``collector.prepare(perm, n)`` (a bare
    permutation is accepted and prepared on the spot).
    ``produce_group(g)`` returns flush group ``g``'s pooled rows (the
    client forward of that group, in ``sfpl_round``) — or, under sub-mesh
    plans, the whole pool (each exchange is confined to its slice by the
    plan's ``axis_index_groups``). The filled slot's exchange is ISSUED
    before the next group's rows are produced and COMPLETED after —
    issue(k) and produce(k+1) share no data dependence, so the all_to_all
    overlaps the next group's compute under a latency-hiding schedule;
    sub-mesh collectives additionally run on DISJOINT shard slices, so
    every in-flight group can progress simultaneously. The final
    in-flight slot is DRAINED after the loop (the epilogue
    tests/test_streaming.py property-checks: the last flush group is
    never dropped).

    ``skip`` (optional per-group bools — elastic participation) marks
    flush groups whose clients ALL dropped this epoch: their rows pass
    through unexchanged (every row is masked downstream) and the pipeline
    spends no collective on them. Groups with ANY survivor still run
    their full exchange — absent clients' rows travel and are masked.

    Returns the shuffled pool — row for row equal to
    ``collector.permute(pool, perm)`` on the synchronous strategy.
    """
    if not isinstance(prep, PreparedPerm):
        with jax.named_scope("sfpl.shuffle"):
            prep = collector.prepare(prep, n)
    bounds = collector.group_bounds(n)
    parts, slot = [], None
    for g in range(len(bounds)):
        ticket = passthrough = None
        if slot is not None:
            if skip and skip[g - 1]:
                passthrough = slot
            else:
                with jax.named_scope("sfpl.shuffle"):
                    ticket = collector.issue(slot, prep, g - 1)
        # outside the shuffle scope: the round scopes its client forward
        rows = produce_group(g)
        if ticket is not None:
            with jax.named_scope("sfpl.shuffle"):
                parts.append(collector.complete(ticket))
        elif passthrough is not None:
            parts.append(passthrough)
        slot = rows
    # drain epilogue: the last filled buffer is still in flight
    last = len(bounds) - 1
    with jax.named_scope("sfpl.shuffle"):
        if skip and skip[last]:
            parts.append(slot)
        else:
            parts.append(collector.complete(collector.issue(slot, prep,
                                                            last)))
        return collector.assemble(parts, prep, n)


# --------------------------------------------------------------------------
# shared step pieces

def make_client_update(split, opt_c):
    """Per-client local backprop + optimizer step given routed-back dA.

    Built ONCE per epoch (hoisted out of the scan body) and shared by every
    placement, so the engines stay numerically interchangeable by
    construction.
    """
    def client_upd(cp, cbn, copt, x, da, step):
        def f(cp_):
            a, ncs = split.client_fwd(cp_, cbn, x, True, None)
            return a, ncs
        _, vjp, ncs = jax.vjp(f, cp, has_aux=True)
        g_cp = vjp(da)[0]
        cp_new, copt_new = opt_c.update(g_cp, copt, cp, step)
        return cp_new, copt_new, ncs
    return client_upd


# --------------------------------------------------------------------------
# SFPL round (Algorithm 1 + 2), one body for every placement

def sfpl_round(key, st, data, split, opt_c, opt_s, *, num_clients,
               batch_size, bn_mode="cmsd", collector, participation=None):
    """One SFPL epoch: scan over the n // batch_size local batches.

    ``collector`` is the strategy object (``DenseTake`` / ``MeshAllToAll``)
    that realises the global collector; everything else — client forward,
    ONE server update over the pooled shuffled stack, gradient routing,
    local client updates, epoch-end ClientFedServer — is placement-
    agnostic. ``bn_mode`` selects the paper's aggregation variants:
    "cmsd" excludes BatchNorm from ClientFedServer, "rmsd" aggregates it.

    ``participation`` (optional bool mask, ``(num_clients,)`` for the
    whole epoch or ``(steps, num_clients)`` per step) is ELASTIC
    PARTICIPATION: absent clients' rows stay in the pool for static
    shapes but are masked out of the server update exactly — activations
    zeroed through ``jnp.where`` (exact zero cotangents), labels dropped
    to the loss's ignore index (the loss means over surviving rows), BN
    batch statistics weighted over valid rows only — their local updates
    are gated back to the pre-step state, and the epoch-end
    ClientFedServer averages over (and broadcasts to) the participants
    only. The trajectory therefore matches a dense run on just the
    surviving clients; the differential tests pin it at <= 1e-5. A
    STATIC epoch mask additionally lets the streamed pipeline skip the
    collective of any flush group whose clients all dropped (the mask
    must be concrete at trace time for that fast path; traced masks
    drain every group). The mask must keep >= 1 survivor per flush group
    — ``repro.core.collector.check_participation`` validates this
    eagerly on the host-side entrypoints.
    """
    n_local = data["x"].shape[1]
    steps = n_local // batch_size
    n_pool = num_clients * batch_size
    client_upd = make_client_update(split, opt_c)
    streamed = getattr(collector, "pipelined", False)
    # sub-mesh routing resolves eagerly (it only depends on the layout):
    # under it the client forward is NOT re-cut per group — each shard's
    # clients already are its groups' rows — so the full vmap runs once
    # and the per-group collectives pipeline over the pool
    submesh = streamed and collector.submesh_slices(n_pool) is not None
    cgroups = (collector.client_groups()
               if streamed and not submesh else None)

    part = part_static = None
    if participation is not None:
        if not isinstance(participation, jax.core.Tracer):
            part_static = np.asarray(participation).astype(bool)
        part = jnp.asarray(participation).astype(bool)
        if part.ndim not in (1, 2) or part.shape[-1] != num_clients:
            raise ValueError(
                f"participation mask must have shape ({num_clients},) or "
                f"(steps, {num_clients}); got {part.shape}")
    per_step_part = part is not None and part.ndim == 2
    skip = None
    if streamed and part_static is not None and part_static.ndim == 1:
        skip = tuple(not part_static[c0:c1].any()
                     for c0, c1 in collector.client_groups())
        if not any(skip):
            skip = None

    # Every op of a step carries at most one ``sfpl.<phase>`` scope (the
    # phases are siblings, never nested), so a profile attributes device
    # time by phase; autodiff marks a phase's backward as
    # ``transpose(jvp(sfpl.<phase>))``.
    def one_step(carry, idx):
        st, key = carry
        with jax.named_scope("sfpl.client_fwd"):
            xb = jax.lax.dynamic_slice_in_dim(data["x"], idx * batch_size,
                                              batch_size, axis=1)
        with jax.named_scope("sfpl.shuffle"):
            key, kperm = jax.random.split(key)
            yb = jax.lax.dynamic_slice_in_dim(data["y"], idx * batch_size,
                                              batch_size, axis=1)
            y_pool = yb.reshape((n_pool,))
            perm = collector.make_perm(kperm, n_pool)
            # routing metadata built ONCE per step from the replicated
            # perm; the label permute, activation permute, backward
            # exchange, and (streamed) route_back all reuse it
            prep = collector.prepare(perm, n_pool)
            y_shuf = (collector.permute(y_pool, prep, skip=skip)
                      if streamed else collector.permute(y_pool, prep))
            mask_c = valid_shuf = None
            if part is not None:
                mask_c = part[idx] if per_step_part else part
                # client-major row mask through the SAME permutation as
                # the pool; perm is replicated, so this is a local gather
                valid_shuf = jnp.take(jnp.repeat(mask_c, batch_size), perm)
        fwd = lambda cp, cs, x: split.client_fwd(cp, cs, x, True, None)

        def srv_loss_on(sp, a_shuf):
            with jax.named_scope("sfpl.server"):
                if valid_shuf is None:
                    loss, (nss, _) = split.server_loss(
                        sp, st["sbn"], a_shuf, y_shuf, True, None)
                else:
                    loss, (nss, _) = split.server_loss(
                        sp, st["sbn"], a_shuf, y_shuf, True, None,
                        valid=valid_shuf)
            return loss, nss

        if streamed and submesh:
            # sub-mesh streaming: the full client vmap IS the per-group
            # forward — each shard computes only its own clients, and a
            # group's clients live exactly on its owning slice — so the
            # pool assembles in home layout once and the two-slot
            # pipeline runs the per-group DENSE collectives over it,
            # each confined to its slice by the plan's axis_index_groups
            # (disjoint slices: all in-flight groups progress at once).
            with jax.named_scope("sfpl.client_fwd"):
                A, ncbn = jax.vmap(fwd)(st["cp"], st["cbn"], xb)
                a_pool = A.reshape((n_pool,) + A.shape[2:])
            a_shuf = streamed_shuffle(collector, prep, n_pool,
                                      lambda g: a_pool, skip=skip)
            (loss, nsbn), (g_sp, g_shuf) = jax.value_and_grad(
                srv_loss_on, argnums=(0, 1), has_aux=True)(
                st["sp"], a_shuf)
            with jax.named_scope("sfpl.shuffle"):
                g_pool = collector.route_back(g_shuf, prep, n_pool,
                                              skip=skip)
        elif streamed:
            # 1+2+3 pipelined: the client forward runs flush group by
            # flush group, and each filled group's all_to_all is in
            # flight while the next group computes (two-slot pipeline,
            # drained after the last group). The shuffled pool is
            # assembled outside the loss, so the de-shuffle is the
            # strategy's explicit inverse-perm exchange (route_back) —
            # move-for-move what autodiff emits on the sync path.
            A_parts, bn_parts = [], []

            def produce_group(g):
                c0, c1 = cgroups[g]
                sl = lambda t: jax.tree_util.tree_map(
                    lambda a: a[c0:c1], t)
                with jax.named_scope("sfpl.client_fwd"):
                    A_g, ncbn_g = jax.vmap(fwd)(
                        sl(st["cp"]), sl(st["cbn"]), xb[c0:c1])
                    A_parts.append(A_g)
                    bn_parts.append(ncbn_g)
                    return A_g.reshape((-1,) + A_g.shape[2:])

            a_shuf = streamed_shuffle(collector, prep, n_pool,
                                      produce_group, skip=skip)
            with jax.named_scope("sfpl.client_fwd"):
                A = _concat_parts(A_parts)
                ncbn = jax.tree_util.tree_map(
                    lambda *xs: _concat_parts(list(xs)), *bn_parts)
            (loss, nsbn), (g_sp, g_shuf) = jax.value_and_grad(
                srv_loss_on, argnums=(0, 1), has_aux=True)(
                st["sp"], a_shuf)
            with jax.named_scope("sfpl.shuffle"):
                g_pool = collector.route_back(g_shuf, prep, n_pool,
                                              skip=skip)
        else:
            # 1. client forward, parallel over the (possibly sharded)
            # client axis
            with jax.named_scope("sfpl.client_fwd"):
                A, ncbn = jax.vmap(fwd)(st["cp"], st["cbn"], xb)

                # 2. global collector: pool client-major (rows inherit
                # the client sharding, if any) and shuffle per the
                # strategy
                a_pool = A.reshape((n_pool,) + A.shape[2:])

            # 3. ONE server update on the shuffled stack. Differentiating
            # w.r.t. the PRE-shuffle pool makes autodiff emit the
            # de-shuffle (dense scatter or the backward-plan exchange):
            # g_pool arrives already routed back to source clients.
            def srv_loss(sp, a_pool):
                with jax.named_scope("sfpl.shuffle"):
                    a_shuf = collector.permute(a_pool, prep)
                return srv_loss_on(sp, a_shuf)
            (loss, nsbn), (g_sp, g_pool) = jax.value_and_grad(
                srv_loss, argnums=(0, 1), has_aux=True)(st["sp"], a_pool)
        with jax.named_scope("sfpl.server_opt"):
            sp_new, sopt_new = opt_s.update(g_sp, st["sopt"], st["sp"],
                                            st["step"])

        # 4. client backprop, parallel (dA is pooled like A)
        with jax.named_scope("sfpl.client_update"):
            dA = g_pool.reshape(A.shape)
            cp_new, copt_new, ncbn2 = jax.vmap(
                lambda cp, cbn, copt, x, da: client_upd(cp, cbn, copt, x,
                                                        da, st["step"]))(
                st["cp"], ncbn, st["copt"], xb, dA)
            if mask_c is not None:
                # Absent clients take NO local step: their activation
                # grads are already exact zeros, but the optimizer would
                # still move params (weight decay, momentum decay) and the
                # forward still advanced BN running stats — gate all three
                # back to the pre-step values so they match a run they
                # never joined.
                gate = lambda new, old: jax.tree_util.tree_map(
                    lambda nl, ol: jnp.where(
                        mask_c.reshape((-1,) + (1,) * (nl.ndim - 1)), nl,
                        ol),
                    new, old)
                cp_new = gate(cp_new, st["cp"])
                copt_new = gate(copt_new, st["copt"])
                ncbn2 = gate(ncbn2, st["cbn"])

        st = dict(st, cp=cp_new, cbn=ncbn2, sp=sp_new, sbn=nsbn,
                  copt=copt_new, sopt=sopt_new, step=st["step"] + 1)
        return (st, key), loss

    (st, _), losses = jax.lax.scan(one_step, (st, key), jnp.arange(steps))

    # 5. ClientFedServer: FedAvg across the client axis (an all-reduce when
    # sharded); BN treatment per bn_mode. Under elastic participation the
    # average runs over the epoch's participants only and is broadcast to
    # every client — absent clients rejoin on the fresh global model,
    # while their (excluded) local BN stays theirs.
    exclude = bn_mode == "cmsd"
    with jax.named_scope("sfpl.fedavg"):
        w = None
        if part is not None:
            epoch_mask = part if part.ndim == 1 else part.any(axis=0)
            w = epoch_mask.astype(jnp.float32)
        st = dict(st, cp=fedavg(st["cp"], weights=w, exclude_bn=exclude),
                  cbn=aggregate_bn_state(st["cbn"], aggregate=not exclude,
                                         weights=w))
    return st, losses


# --------------------------------------------------------------------------
# SFLv2 round (baseline under study), one body for every placement

def sflv2_round(key, st, data, split, opt_c, opt_s, *, num_clients,
                batch_size, aggregate_bn=True, placement=SINGLE):
    """One SFLv2 epoch: clients visited SEQUENTIALLY in random order — this
    catastrophic-forgetting structure is the object of study and is never
    parallelized. ``placement`` shards the per-client batch axis instead,
    so the server-side stream (the scaling bottleneck) runs data-parallel
    while the visitation order is bit-for-bit preserved."""
    n_local = data["x"].shape[1]
    steps = n_local // batch_size
    order = jax.random.permutation(key, num_clients)

    def per_client(carry, k):
        st = carry
        cp_k = jax.tree_util.tree_map(lambda a: a[k], st["cp"])
        cbn_k = jax.tree_util.tree_map(lambda a: a[k], st["cbn"])
        copt_k = jax.tree_util.tree_map(lambda a: a[k], st["copt"])
        xk = data["x"][k]
        yk = data["y"][k]

        def per_batch(inner, idx):
            cp, cbn, copt, sp, sbn, sopt, step = inner
            xb = jax.lax.dynamic_slice_in_dim(xk, idx * batch_size,
                                              batch_size, axis=0)
            yb = jax.lax.dynamic_slice_in_dim(yk, idx * batch_size,
                                              batch_size, axis=0)
            xb, yb = placement.constrain_batch((xb, yb))

            def f(cp_):
                a, ncs = split.client_fwd(cp_, cbn, xb, True, None)
                return a, ncs
            A, vjp, ncbn = jax.vjp(f, cp, has_aux=True)

            def srv_loss(sp_, a):
                loss, (nss, _) = split.server_loss(sp_, sbn, a, yb, True,
                                                   None)
                return loss, nss
            (loss, nsbn), (g_sp, g_a) = jax.value_and_grad(
                srv_loss, argnums=(0, 1), has_aux=True)(sp, A)
            sp_new, sopt_new = opt_s.update(g_sp, sopt, sp, step)
            g_cp = vjp(g_a)[0]
            cp_new, copt_new = opt_c.update(g_cp, copt, cp, step)
            return (cp_new, ncbn, copt_new, sp_new, nsbn, sopt_new,
                    step + 1), loss

        inner0 = (cp_k, cbn_k, copt_k, st["sp"], st["sbn"], st["sopt"],
                  st["step"])
        inner, losses = jax.lax.scan(per_batch, inner0, jnp.arange(steps))
        cp_k, cbn_k, copt_k, sp, sbn, sopt, step = inner
        put = lambda t, v: jax.tree_util.tree_map(
            lambda a, b: a.at[k].set(b), t, v)
        st = dict(st, cp=put(st["cp"], cp_k), cbn=put(st["cbn"], cbn_k),
                  copt=put(st["copt"], copt_k), sp=sp, sbn=sbn, sopt=sopt,
                  step=step)
        return st, losses

    st, losses = jax.lax.scan(per_client, st, order)
    st = dict(st, cp=fedavg(st["cp"], exclude_bn=False),
              cbn=aggregate_bn_state(st["cbn"], aggregate=aggregate_bn))
    return st, losses

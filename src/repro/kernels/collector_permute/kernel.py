"""Pallas-TPU kernels for the SFPL global-collector shuffle.

The collector's data movement is batched row gathers over the pooled
smashed-data tensor. On TPU each is a one-pass HBM->VMEM->HBM copy when
the gather indices are prefetched to SMEM and used in the *BlockSpec index
map* — every grid cell DMAs exactly its source tile, so no intermediate
materialization or scatter is needed (PrefetchScalarGridSpec pattern).

Three gathers share the pattern:

  * ``collector_permute_2d`` — the flat pool shuffle ``out[i] = x[perm[i]]``
    (single-device collector, and the legacy local permute);
  * ``bucket_permute_2d``    — the route-plan SEND side: gather local rows
    directly into send-bucket layout, ``out[s*cap + r] = x[idx[s, r]]``,
    via a TWO-LEVEL (destination bucket, slot) grid whose prefetched index
    map resolves both levels;
  * ``unbucket_permute_2d``  — its receive-side mirror: gather the flat
    received bucket block into local output order.

Each grid cell moves ONE row, so the kernels see the rows as ``(R, 1, D)``
with blocks ``(squeezed, 1, block_d)``: the block's last two dims then
equal the array's unit sublane dim and tile its lanes, which the TPU
lowering accepts for every dtype. A ``(1, block_d)`` block of an ``(R, D)``
array breaks its rule that the second-to-last block dim is a multiple of
8 (32 for int8) or the whole dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _permute_kernel(perm_ref, x_ref, o_ref):
    del perm_ref  # consumed by the index map, not the body
    o_ref[...] = x_ref[...]


def row_spec(width, index_map):
    """BlockSpec of one ``width``-lane slice of one row of an ``(R, 1, D)``
    operand; ``index_map(*grid_idx, *prefetch)`` returns ``(row, lane
    block)``."""
    def idx(*args):
        row, j = index_map(*args)
        return row, 0, j
    return pl.BlockSpec((pl.Squeezed(), 1, width), idx)


def _row_gather(x, idx, grid, src, dst, rows_out, block_d, name, interpret):
    """``out[dst(g)] = x[src(g)]`` lane block by lane block over ``grid``
    (plus a last axis walking the lane blocks); ``x`` is ``(R, D)``."""
    R, D = x.shape
    assert D % block_d == 0, (D, block_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid + (D // block_d,),
        in_specs=[row_spec(block_d, src)],
        out_specs=row_spec(block_d, dst),
    )
    out = pl.pallas_call(
        _permute_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_out, 1, D), x.dtype),
        interpret=interpret,
        name=name,
    )(idx.astype(jnp.int32), x.reshape(R, 1, D))
    return out.reshape(rows_out, D)


def collector_permute_2d(x, perm, *, block_d=512, interpret=False):
    """x: (R, D) pooled smashed data (row-major, one row per sample);
    perm: (R,) int32 destination->source map. Returns x[perm]."""
    return _row_gather(
        x, perm, (x.shape[0],),
        lambda i, j, perm: (perm[i], j), lambda i, j, perm: (i, j),
        x.shape[0], block_d, "sfpl_collector_permute", interpret)


def bucket_permute_2d(x, idx, *, block_d=512, interpret=False):
    """Route-plan send-side gather into bucket layout.

    x: (R, D) local rows; idx: (S, cap) int32 — the plan's two-level
    (destination shard, bucket slot) -> source row map (``RoutePlan.
    send_idx`` reshaped). Returns (S*cap, D) with
    ``out[s*cap + r] = x[idx[s, r]]`` — the exact send buffer the
    ``all_to_all`` ships, written in one pass: the grid iterates buckets
    then slots, and the prefetched index map resolves both levels to the
    source tile, so rows stream HBM->HBM without an intermediate
    sorted/stacked copy."""
    S, cap = idx.shape
    return _row_gather(
        x, idx, (S, cap),
        lambda s, r, j, idx: (idx[s, r], j),
        lambda s, r, j, idx: (s * cap + r, j),
        S * cap, block_d, "sfpl_bucket_permute", interpret)


def unbucket_permute_2d(x, idx, *, block_d=512, interpret=False):
    """Route-plan receive-side mirror of ``bucket_permute_2d``.

    x: (R, D) flat received bucket block (``S*cap`` rows, plus the zero
    pad row on slack-buffered plans); idx: (B,) int32 — the plan's
    ``recv_idx``: local output row -> flat (source shard, slot). Returns
    (B, D) with ``out[i] = x[idx[i]]`` — the shuffled output slab, again
    one DMA per tile with no scatter."""
    (B,) = idx.shape
    return _row_gather(
        x, idx, (B,),
        lambda i, j, idx: (idx[i], j), lambda i, j, idx: (i, j),
        B, block_d, "sfpl_unbucket_permute", interpret)

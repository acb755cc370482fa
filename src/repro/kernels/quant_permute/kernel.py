"""Pallas-TPU kernels fusing wire quantization into the collector gathers.

The quantized exchange needs two extra element-wise passes over the
smashed rows — scale into the wire grid before the ``all_to_all``, scale
back out after — and both land exactly where the route-plan gathers
already stream every row HBM->VMEM->HBM. Fusing them into the gather
kernels makes the wire conversion free of extra memory traffic:

  * ``quant_bucket_permute_2d`` — the SEND side: gather local rows into
    send-bucket layout (``bucket_permute_2d``'s two-level prefetched
    index map) and, in the same pass over each row tile, reduce the row
    amax, emit the int8/fp8 row, and write its f32 scale;
  * ``dequant_unbucket_permute_2d`` — the RECEIVE mirror: gather the
    flat received block into output order while multiplying each row by
    its (prefetched-index-selected) scale back into the compute dtype.

Both kernels take ONE WHOLE ROW per grid cell: the amax reduction needs
the whole row in VMEM, so the feature dim is not tiled. Rows travel as
``(R, 1, Dp)`` and the per-row scales as ``(R, 1, 1)``, with blocks
``(squeezed, 1, Dp)`` and ``(squeezed, 1, 1)`` — the layout the TPU
lowering accepts for one-row blocks (``collector_permute.kernel``). A
32x32x16 smashed row is 64 KiB in f32, far under VMEM pressure; reshape
upstream if a future cut layer breaks that assumption.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.collector_permute.kernel import row_spec


def _quant_kernel(qmax, round_to_int, idx_ref, x_ref, q_ref, s_ref):
    del idx_ref  # consumed by the index map, not the body
    row = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(row))
    inv = jnp.where(amax > 0, qmax / jnp.where(amax > 0, amax, 1.0), 0.0)
    y = row * inv
    if round_to_int:
        y = jnp.round(y)
    q_ref[...] = y.astype(q_ref.dtype)
    # reciprocal multiply, matching core.wire.quantize_rows bit-for-bit
    s_ref[...] = jnp.full(s_ref.shape, amax * jnp.float32(1.0 / qmax),
                          jnp.float32)


def _one_row(index_map):
    """``row_spec`` over the whole lane dim: ``index_map`` gives the
    row, the lane block is always 0."""
    return lambda *a: (index_map(*a), 0)


def quant_bucket_permute_2d(x, idx, wire_dtype, qmax, *, interpret=False):
    """Fused quantize + send-side bucket gather.

    x: (R, D) local float rows; idx: (S, cap) int32 two-level
    (destination shard, bucket slot) -> source row map. Returns
    ``(q, scales)``: q (S*cap, D) in ``wire_dtype`` with
    ``q[s*cap + r] = quantize(x[idx[s, r]])`` and f32 scales
    (S*cap, 1), one per BUCKETED row (scales ship in send layout —
    they cross the wire with their rows). Zero-padded feature columns
    cannot perturb the amax."""
    R, D = x.shape
    S, cap = idx.shape
    src = _one_row(lambda s, r, idx: idx[s, r])
    dst = _one_row(lambda s, r, idx: s * cap + r)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, cap),
        in_specs=[row_spec(D, src)],
        out_specs=[row_spec(D, dst), row_spec(1, dst)],
    )
    round_to_int = jnp.issubdtype(jnp.dtype(wire_dtype), jnp.integer)
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, float(qmax), round_to_int),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S * cap, 1, D), wire_dtype),
                   jax.ShapeDtypeStruct((S * cap, 1, 1), jnp.float32)],
        interpret=interpret,
        name="sfpl_quant_bucket_permute",
    )(idx.astype(jnp.int32), x.reshape(R, 1, D))
    return q.reshape(S * cap, D), scales.reshape(S * cap, 1)


def _dequant_kernel(idx_ref, s_ref, x_ref, o_ref):
    del idx_ref
    o_ref[...] = (x_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(o_ref.dtype)


def dequant_unbucket_permute_2d(q, scales, idx, out_dtype, *,
                                interpret=False):
    """Fused receive-side unbucket gather + dequantize.

    q: (R, D) flat received wire-dtype block (plus the zero pad row on
    slack-buffered plans — its packed scale is 0.0, so it dequantizes to
    exact zeros); scales: (R, 1) f32 per-row scales in the same flat
    order; idx: (B,) int32 output row -> flat slot. Returns (B, D) in
    ``out_dtype`` with ``out[i] = q[idx[i]] * scales[idx[i]]`` — the
    shuffled compute-dtype slab in one pass, scale selection riding the
    same prefetched index map as the row gather."""
    R, D = q.shape
    (B,) = idx.shape
    src = _one_row(lambda i, idx: idx[i])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[row_spec(1, src), row_spec(D, src)],
        out_specs=row_spec(D, _one_row(lambda i, idx: i)),
    )
    out = pl.pallas_call(
        _dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, D), out_dtype),
        interpret=interpret,
        name="sfpl_dequant_unbucket_permute",
    )(idx.astype(jnp.int32), scales.reshape(R, 1, 1), q.reshape(R, 1, D))
    return out.reshape(B, D)

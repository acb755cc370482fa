"""Pallas-TPU fused RMSNorm.

Memory-bound op: one HBM read of x, one write of y (vs 3+ round trips when
unfused). Rows are tiled (block_rows, d) into VMEM; the mean-square reduction
and the scale multiply happen in registers in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps, scale_offset, d_real):
    x = x_ref[...].astype(jnp.float32)            # (br, dp)
    if d_real != x.shape[-1]:                     # feature-dim padding mask
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < d_real, x, 0.0)
    ms = jnp.sum(x * x, axis=-1, keepdims=True) / d_real
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (s_ref[...].astype(jnp.float32) + scale_offset)
                  ).astype(o_ref.dtype)


def rmsnorm_2d(x, scale, *, eps=1e-6, scale_offset=0.0, block_rows=256,
               d_real=None, interpret=False):
    """x: (R, Dp) with R % block_rows == 0; scale: (Dp,)."""
    R, Dp = x.shape
    assert R % block_rows == 0
    d_real = Dp if d_real is None else d_real
    kernel = functools.partial(_rmsnorm_kernel, eps=eps,
                               scale_offset=scale_offset, d_real=d_real)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
            pl.BlockSpec((1, Dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Dp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfpl_rmsnorm",
    )(x, scale.reshape(1, Dp))

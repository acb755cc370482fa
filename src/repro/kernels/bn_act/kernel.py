"""Pallas-TPU fused BatchNorm affine + ReLU epilogue.

Memory-bound elementwise tail of every conv in the split ResNet: one HBM
read of the conv output, one write of the activated tensor — vs the 3+
round trips of unfused normalize / scale-shift / relu. The per-channel
affine ``(a, b)`` is precomputed in f32 from the BN statistics (batch or
running, per the CMSD/RMSD policy), broadcast from one VMEM-resident
``(1, Cp)`` row; the multiply-add and the clamp happen in registers in
f32 and the result is cast to the compute dtype on the way out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _bn_act_kernel(x_ref, a_ref, b_ref, o_ref, *, relu):
    x = x_ref[...].astype(jnp.float32)            # (br, Cp)
    y = x * a_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def bn_act_2d(x, a, b, *, relu=True, block_rows=256, interpret=False):
    """x: (R, Cp) with R % block_rows == 0 and Cp a lane multiple;
    a, b: (Cp,) f32 folded BN affine. Returns ``relu?(x * a + b)`` in
    ``x.dtype``."""
    R, Cp = x.shape
    assert R % block_rows == 0, (R, block_rows)
    kernel = functools.partial(_bn_act_kernel, relu=relu)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Cp), lambda i: (i, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
            pl.BlockSpec((1, Cp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, Cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Cp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfpl_bn_act",
    )(x, a.reshape(1, Cp), b.reshape(1, Cp))

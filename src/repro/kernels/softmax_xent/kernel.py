"""Pallas-TPU fused softmax cross-entropy (forward + backward sweeps).

One VMEM pass per direction over the (rows, classes) logits block:

* forward — per-row max / exp / sum in f32 registers, the label logit
  selected by an iota==label mask (no f32 logits materialized in HBM, no
  ``take_along_axis`` gather round-trip); emits per-row ``nll`` and the
  ``lse`` residual.
* backward — ``(softmax(x) - onehot(label)) * scale`` per row, with the
  softmax rebuilt from the saved ``lse`` (no second reduction).

Padded class columns are masked to -inf (forward) / zeroed (backward);
padded rows are neutralized by a zero per-row ``scale``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _xent_fwd_kernel(x_ref, l_ref, loss_ref, lse_ref, *, v_real):
    x = x_ref[...].astype(jnp.float32)            # (br, Vp)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if v_real != x.shape[-1]:                     # class-dim padding mask
        x = jnp.where(col < v_real, x, -jnp.inf)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    ll = jnp.sum(jnp.where(col == l_ref[...], x, 0.0),
                 axis=-1, keepdims=True)
    loss_ref[...] = lse - ll
    lse_ref[...] = lse


def _xent_bwd_kernel(x_ref, l_ref, lse_ref, g_ref, dx_ref, *, v_real):
    x = x_ref[...].astype(jnp.float32)            # (br, Vp)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    p = jnp.exp(x - lse_ref[...])                 # softmax from saved lse
    onehot = (col == l_ref[...]).astype(jnp.float32)
    d = (p - onehot) * g_ref[...]                 # per-row scale (br, 1)
    if v_real != x.shape[-1]:
        d = jnp.where(col < v_real, d, 0.0)
    dx_ref[...] = d.astype(dx_ref.dtype)


def xent_fwd_2d(x, labels, *, v_real=None, block_rows=256, interpret=False):
    """x: (R, Vp), R % block_rows == 0; labels: (R, 1) int32 (pre-masked to
    valid class ids). Returns per-row ``(nll, lse)``, both (R, 1) f32."""
    R, Vp = x.shape
    assert R % block_rows == 0, (R, block_rows)
    kernel = functools.partial(_xent_fwd_kernel,
                               v_real=Vp if v_real is None else v_real)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Vp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfpl_xent_fwd",
    )(x, labels)


def xent_bwd_2d(x, labels, lse, g, *, v_real=None, block_rows=256,
                interpret=False):
    """Backward sweep: x (R, Vp), labels (R, 1) int32, lse/g (R, 1) f32.
    Returns dlogits (R, Vp) in ``x.dtype``."""
    R, Vp = x.shape
    assert R % block_rows == 0, (R, block_rows)
    kernel = functools.partial(_xent_bwd_kernel,
                               v_real=Vp if v_real is None else v_real)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Vp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, Vp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Vp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfpl_xent_bwd",
    )(x, labels, lse, g)

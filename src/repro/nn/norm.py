"""Normalization layers.

BatchNorm is central to the paper: its running statistics ("RMSD") vs
current-batch statistics ("CMSD") distinction at inference, and its exclusion
from FedAvg aggregation, are half of SFPL's contribution. Running statistics
live in a separate ``state`` tree so aggregation policies can treat
parameters and statistics independently.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.nn.init import ones_init, zeros_init

# --------------------------------------------------------------------------
# BatchNorm


def _batch_moments(x, axes, valid):
    """f32 (mean, var) over ``axes``; rows with ``valid==False`` weightless.

    ``valid=None`` is the dense path and stays bit-identical to
    ``jnp.mean``/``jnp.var``.  With a ``(batch,)`` bool mask, masked rows
    contribute exactly zero to both moments (multiplication by a 0/1 f32
    weight is exact), so the statistics equal those of the surviving rows
    alone — the property elastic participation's oracle parity rests on.
    """
    x32 = x.astype(jnp.float32)
    if valid is None:
        return jnp.mean(x32, axis=axes), jnp.var(x32, axis=axes)
    w = valid.astype(jnp.float32).reshape((-1,) + (1,) * (x32.ndim - 1))
    spatial = math.prod(x32.shape[i] for i in axes if i != 0)
    cnt = jnp.maximum(jnp.sum(w), 1.0) * float(spatial)
    mean = jnp.sum(x32 * w, axis=axes) / cnt
    var = jnp.sum(w * jnp.square(x32 - mean), axis=axes) / cnt
    return mean, var


def batchnorm_init(key, dim, *, dtype=jnp.float32):
    params = {"scale": ones_init(key, (dim,), dtype),
              "bias": zeros_init(key, (dim,), dtype)}
    state = {"mean": jnp.zeros((dim,), jnp.float32),
             "var": jnp.ones((dim,), jnp.float32),
             "count": jnp.zeros((), jnp.float32)}
    return params, state


def batchnorm_apply(params, state, x, *, training, momentum=0.9, eps=1e-5,
                    use_running_stats=None, valid=None):
    """Returns (y, new_state).

    ``use_running_stats`` controls the inference statistics source:
      * True  -> RMSD (aggregated running mean/var)        [paper Table VI/VII]
      * False -> CMSD (current test-batch mean/var)        [paper Table VIII]
    Default at inference is RMSD; during training current-batch stats are
    always used for normalization while the running stats are updated.

    ``valid`` (optional ``(batch,)`` bool) drops rows from the batch
    statistics — the elastic-participation path where absent clients'
    rows ride along in the pooled batch but must not perturb the moments.
    ``valid=None`` is bit-identical to the dense computation.
    """
    with jax.named_scope("bn"):
        axes = tuple(range(x.ndim - 1))
        if training:
            mean, var = _batch_moments(x, axes, valid)
            new_state = {
                "mean": momentum * state["mean"] + (1 - momentum) * mean,
                "var": momentum * state["var"] + (1 - momentum) * var,
                "count": state["count"] + 1.0,
            }
        else:
            rmsd = True if use_running_stats is None else use_running_stats
            if rmsd:
                mean, var = state["mean"], state["var"]
            else:  # CMSD: statistics of the batch under test
                mean, var = _batch_moments(x, axes, valid)
            new_state = state
        x32 = x.astype(jnp.float32)
        y = (x32 - mean) * (1.0 / jnp.sqrt(var + eps))
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype), new_state


def batchnorm_act_apply(params, state, x, *, training, relu=True,
                        momentum=0.9, eps=1e-5, use_running_stats=None,
                        use_kernel=False, interpret=False, valid=None):
    """BatchNorm + optional ReLU with the elementwise tail fused.

    Same statistics semantics as :func:`batchnorm_apply` (training batch
    stats + running update; RMSD/CMSD at inference), but the per-channel
    normalize/scale/shift is folded into one f32 affine
    ``a = scale / sqrt(var + eps)``, ``b = bias - mean * a`` applied — with
    the ReLU — in a single sweep over ``x``.  The fold stays differentiable
    through the batch statistics, so autodiff's stat-gradients match the
    unfused form; the moments themselves are always computed in f32.
    ``use_kernel`` routes the sweep through the Pallas ``bn_act`` kernel
    (``interpret`` for CPU CI); off-kernel the fused jnp path is used.

    NOTE: the folded affine rounds differently from ``batchnorm_apply``'s
    subtract-then-scale at f32 — callers pinning bit-exact f32 parity
    (``policy=None`` in the split model) must keep the unfused path.
    """
    with jax.named_scope("bn"):
        axes = tuple(range(x.ndim - 1))
        if training:
            mean, var = _batch_moments(x, axes, valid)
            new_state = {
                "mean": momentum * state["mean"] + (1 - momentum) * mean,
                "var": momentum * state["var"] + (1 - momentum) * var,
                "count": state["count"] + 1.0,
            }
        else:
            rmsd = True if use_running_stats is None else use_running_stats
            if rmsd:
                mean, var = state["mean"], state["var"]
            else:  # CMSD: statistics of the batch under test
                mean, var = _batch_moments(x, axes, valid)
            new_state = state
        a = params["scale"].astype(jnp.float32) / jnp.sqrt(var + eps)
        b = params["bias"].astype(jnp.float32) - mean * a
        if use_kernel:
            from repro.kernels.bn_act import ops as _ops
            y = _ops.bn_act(x, a, b, relu=relu, interpret=interpret)
        else:
            y32 = x.astype(jnp.float32) * a + b
            if relu:
                y32 = jnp.maximum(y32, 0.0)
            y = y32.astype(x.dtype)
        return y, new_state


# --------------------------------------------------------------------------
# LayerNorm / RMSNorm


def layernorm_init(key, dim, *, dtype=jnp.float32):
    return {"scale": ones_init(key, (dim,), dtype),
            "bias": zeros_init(key, (dim,), dtype)}


def layernorm_apply(params, x, *, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_init(key, dim, *, dtype=jnp.float32):
    return {"scale": ones_init(key, (dim,), dtype)}


def rmsnorm_apply(params, x, *, eps=1e-6, use_kernel=False, scale_offset=0.0):
    """RMSNorm. ``scale_offset=1.0`` gives the Gemma "(1+scale)" convention.

    ``use_kernel`` routes through the Pallas kernel (interpret on CPU).
    """
    if use_kernel:
        from repro.kernels.rmsnorm import ops as _ops
        return _ops.rmsnorm(x, params["scale"], eps=eps,
                            scale_offset=scale_offset)
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 / jnp.sqrt(ms + eps)
    y = y * (params["scale"].astype(jnp.float32) + scale_offset)
    return y.astype(x.dtype)

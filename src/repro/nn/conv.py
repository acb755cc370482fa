"""2-D convolution (NHWC, HWIO) for the ResNet family."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.init import he_normal, zeros_init


def conv2d_init(key, in_ch, out_ch, kernel, *, use_bias=False,
                dtype=jnp.float32):
    kh, kw_ = (kernel, kernel) if isinstance(kernel, int) else kernel
    kw, kb = jax.random.split(key)
    p = {"w": he_normal(kw, (kh, kw_, in_ch, out_ch), dtype=dtype,
                        in_axis=2, out_axis=3)}
    if use_bias:
        p["b"] = zeros_init(kb, (out_ch,), dtype=dtype)
    return p


def conv2d_apply(params, x, *, stride=1, padding="SAME", compute_dtype=None):
    with jax.named_scope("conv"):
        w = params["w"]
        if compute_dtype is not None:
            w = w.astype(compute_dtype)
            x = x.astype(compute_dtype)
        strides = (stride, stride) if isinstance(stride, int) else stride
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y

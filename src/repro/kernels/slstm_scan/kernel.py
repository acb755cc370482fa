"""Pallas-TPU fused sLSTM scan.

Motivation (EXPERIMENTS.md §Perf iter 14): the sLSTM hidden-to-hidden
recurrence is sequential over time; any sharded-pjit formulation pays a
per-timestep collective or gather. This kernel keeps the (c, n, m, h) state
resident in VMEM scratch and runs the time loop ON-CHIP:

  grid = (B_blocks, S_chunks)  — S_chunks is the sequential dimension; the
  state scratch carries across chunks. Each grid cell loads a
  (bb, ts, H*Dh) tile of the four gate preactivations, loops ``ts`` steps
  with the per-head block-diagonal recurrent matmuls (Dh x Dh — MXU-aligned
  for Dh in {128..512}), and writes the h tile.

Head-local layout: R matrices are replicated per device (heads < TP degree),
so the kernel involves no cross-chip traffic at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _slstm_kernel(xi_ref, xf_ref, xz_ref, xo_ref, r_ref, o_ref,
                  c_ref, n_ref, m_ref, h_ref, *, ts, H, Dh):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.full_like(n_ref, 1e-6)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        h_ref[...] = jnp.zeros_like(h_ref)

    R = r_ref[...].astype(jnp.float32)            # (4, H, Dh, Dh)

    def step(t, _):
        h = h_ref[...].reshape(-1, H, Dh)         # (bb, H, Dh)
        rec = jnp.einsum("bhd,ghde->gbhe", h, R,
                         preferred_element_type=jnp.float32)
        rec = rec.reshape(4, -1, H * Dh)
        xi = xi_ref[:, t].astype(jnp.float32)     # (bb, HD)
        xf = xf_ref[:, t].astype(jnp.float32)
        xz = xz_ref[:, t].astype(jnp.float32)
        xo = xo_ref[:, t].astype(jnp.float32)
        i_pre = xi + rec[0]
        f_pre = xf + rec[1]
        z = jnp.tanh(xz + rec[2])
        o = jax.nn.sigmoid(xo + rec[3])
        logf = jax.nn.log_sigmoid(f_pre)
        m = m_ref[...]
        m_new = jnp.maximum(logf + m, i_pre)
        i_s = jnp.exp(i_pre - m_new)
        f_s = jnp.exp(logf + m - m_new)
        c_new = f_s * c_ref[...] + i_s * z
        n_new = jnp.maximum(f_s * n_ref[...] + i_s, 1e-6)
        h_new = o * (c_new / n_new)
        c_ref[...] = c_new
        n_ref[...] = n_new
        m_ref[...] = m_new
        h_ref[...] = h_new
        o_ref[:, t] = h_new.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, ts, step, 0)


def slstm_scan_pallas(pre_i, pre_f, pre_z, pre_o, R, *, block_b=8,
                      time_chunk=64, interpret=False):
    """pre_*: (B, S, HD) fp32; R: (4, H, Dh, Dh). Returns h (B, S, HD).

    B % block_b == 0 and S % time_chunk == 0 (the ops wrapper pads).
    """
    B, S, HD = pre_i.shape
    _, H, Dh, _ = R.shape
    assert H * Dh == HD
    assert B % block_b == 0 and S % time_chunk == 0
    grid = (B // block_b, S // time_chunk)

    kernel = functools.partial(_slstm_kernel, ts=time_chunk, H=H, Dh=Dh)
    x_spec = pl.BlockSpec((block_b, time_chunk, HD),
                          lambda b, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, x_spec, x_spec,
                  pl.BlockSpec((4, H, Dh, Dh), lambda b, j: (0, 0, 0, 0))],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, HD), pre_i.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, HD), jnp.float32)
                        for _ in range(4)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sfpl_slstm_scan",
    )(pre_i, pre_f, pre_z, pre_o, R)

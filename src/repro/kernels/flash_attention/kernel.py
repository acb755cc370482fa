"""Pallas-TPU flash attention (GQA, causal, sliding-window).

TPU-native adaptation: online-softmax over a 4-D grid
``(batch, q_head, q_block, kv_block)`` where the last dimension is the
sequential reduction axis ("arbitrary" dimension semantics). Running max /
denominator / accumulator live in VMEM scratch in fp32; block shapes are
MXU-aligned (multiples of 128 on the sequence dims, head_dim padded to 128
lanes by the caller). GQA loads each KV head once per q-head group via the
BlockSpec index map — no KV duplication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, bq, bk, kv_len, num_kv_blocks):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk

    # Block-level skip: fully-masked (causal / window / padding) blocks do no
    # compute. They still occupy a grid step, but the MXU work is gated off.
    relevant = k_start < kv_len
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, (q_start) - (k_start + bk - 1) < window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        iq = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ik = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = ik < kv_len                    # kv padding
        if causal:
            mask = jnp.logical_and(mask, iq - ik >= 0)
        if window is not None:
            mask = jnp.logical_and(mask, iq - ik < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        # rows with no valid kv (shouldn't happen for causal q<kv_len) get l=0
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, scale, causal=True, window=None,
                         kv_len=None, bq=128, bk=128, interpret=False):
    """q: (B, H, Sq, D); k/v: (B, K, Skv, D), Sq/Skv multiples of bq/bk.

    ``kv_len``: number of real (unpadded) kv positions (<= Skv).
    """
    B, H, Sq, D = q.shape
    K, Skv = k.shape[1], k.shape[2]
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    G = H // K
    kv_len = Skv if kv_len is None else kv_len
    nq, nk = Sq // bq, Skv // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, kv_len=kv_len, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="sfpl_flash_attention",
    )(q, k, v)

"""Blockwise online-softmax attention (FlashAttention) — Pallas TPU kernel.

TPU-native adaptation: instead of warp-level tiling, the kernel streams KV
blocks HBM->VMEM over the innermost grid dimension while the (block_q, d)
query tile, the fp32 accumulator and the running (m, l) softmax statistics
stay VMEM-resident.  GQA is handled in the BlockSpec index maps (q heads
share the KV block of their group — no KV repeat is ever materialized).
Causal masking skips fully-masked KV blocks via ``pl.when``.

Grid: (batch, q_heads, nq, nkv), kv innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  nkv: int, block_q: int, block_kv: int, causal: bool,
                  sm_scale: float, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + q_offset      # absolute query positions
    kv_start = ki * block_kv

    def body():
        q = q_ref[0, 0, ...]                  # [bq, d]
        k = k_ref[0, 0, ...]                  # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bkv]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_kv), 0)
            cols = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[...]                # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)             # [bq, bkv]
        alpha = jnp.exp(m_prev - m_new)    # [bq, 1]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, ...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip KV blocks entirely above the diagonal
        pl.when(kv_start <= q_start + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == nkv - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)    # fully-masked rows -> zeros
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool, block_q: int,
                           block_kv: int, q_offset: int = 0,
                           interpret: bool = False):
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Skv, D] (pre-padded to blocks).
    Returns [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert sq % block_q == 0 and skv % block_kv == 0
    nq, nkv = sq // block_q, skv // block_kv
    grp = hq // hkv
    sm_scale = 1.0 / np.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_kernel, nkv=nkv, block_q=block_q,
                          block_kv=block_kv, causal=causal,
                          sm_scale=sm_scale, q_offset=q_offset),
        grid=(b, hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki: (bi, hi // grp, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki: (bi, hi // grp, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)

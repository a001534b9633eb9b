"""Chunked gated linear-attention scan — Pallas TPU kernel.

Grid: (B*H, n_chunks); the chunk axis is innermost so the [Dk, Dv] carry
state lives in VMEM scratch across chunk steps (sequential join), while all
intra-chunk work is dense MXU matmuls on the [C, Dk/Dv] tiles (parallel
fork).  Cumulative log-decays are computed as a lower-triangular matmul
(MXU-friendly) rather than a sequential cumsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                 C: int, rwkv: bool):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0].astype(jnp.float32)          # [C, Dk]
    k = k_ref[0].astype(jnp.float32)          # [C, Dk]
    v = v_ref[0].astype(jnp.float32)          # [C, Dv]
    w = w_ref[0].astype(jnp.float32)          # [C, Dk]

    lw = jnp.log(w)
    # inclusive prefix sums via tril matmul (MXU) instead of cumsum
    tri_inc = jnp.tril(jnp.ones((C, C), jnp.float32))
    lb = jax.lax.dot_general(tri_inc, lw, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    lbq = lb - lw if rwkv else lb

    mid = lb[C // 2][None, :]                 # [1, Dk] normalizer
    # Clamped factor exponents: exact for C <= 21 at the RWKV6 decay clip
    # (see ops.SAFE_CHUNK); prevents inf*0 NaNs from masked-region overflow.
    qt = q * jnp.exp(jnp.minimum(lbq - mid, 80.0))
    kt = k * jnp.exp(jnp.minimum(mid - lb, 80.0))
    A = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [C, C]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    keep = (cols < rows) if rwkv else (cols <= rows)
    A = jnp.where(keep, A, 0.0)
    intra = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    if rwkv:
        u = u_ref[0].astype(jnp.float32)      # [1, Dk]
        bonus = jnp.sum(q * u * k, axis=-1, keepdims=True)
        intra = intra + bonus * v

    # inter-chunk: read carry, emit contribution, update carry
    S0 = s_ref[...]                           # [Dk, Dv] fp32
    qs = q * jnp.exp(lbq)
    inter = jax.lax.dot_general(qs, S0, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    dC = jnp.exp(lb[C - 1])                   # [Dk]
    kE = k * jnp.exp(lb[C - 1][None, :] - lb)
    s_ref[...] = dC[:, None] * S0 + jax.lax.dot_general(
        kE, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    o_ref[0] = (intra + inter).astype(o_ref.dtype)


def linear_scan_kernel(q, k, v, w, u, *, chunk: int, rwkv: bool,
                       interpret: bool = False):
    """q/k/w: [BH, S, Dk], v: [BH, S, Dv], u: [BH, 1, Dk]; S % chunk == 0.
    ``u`` carries a unit middle dim so its block's last two dims equal the
    array's, as the TPU's (8, 128) tiling rule requires."""
    BH, S, Dk = q.shape
    Dv = v.shape[-1]
    assert S % chunk == 0
    N = S // chunk

    return pl.pallas_call(
        functools.partial(_scan_kernel, C=chunk, rwkv=rwkv),
        grid=(BH, N),
        in_specs=[
            pl.BlockSpec((1, chunk, Dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, chunk, Dv), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, 1, Dk), lambda i, n: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Dv), lambda i, n: (i, n, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
        name="linear_scan",
    )(q, k, v, w, u)

"""Blocked GEMM with a fused, *open* epilogue — Pallas TPU kernel.

The TPU adaptation of TapirXLA's exposed Eigen routines: the GEMM's tiling
is explicit (BlockSpec over an (m, n, k) grid, fp32 VMEM accumulator) and the
epilogue slot executes the calling context's elementwise tail on the output
tile while it is still resident in VMEM — one HBM round-trip instead of one
per fused op.

Grid: (nm, nn, nk), k innermost so the accumulator scratch carries across k
steps for a fixed (m, n) tile.  Tiles are MXU-aligned by `core.schedule`
(multiples of 128 whenever shapes allow).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import _EW

# epilogue spec entry: (fn_name, operand_kind, head_pos, dtype)
#   operand_kind: "none" (unary), "row" (operand shape [n]),
#                 "full" (operand shape [m, n])
#   dtype: compute dtype of the un-fused consumer op (None = accumulator);
#          the tile is cast before the stage so fusing is bitwise-invisible


def _gemm_kernel(*refs, nk: int, epi_spec, out_dtype):
    """One (bm, bn) output tile; k is the innermost grid dim."""
    x_ref, w_ref = refs[0], refs[1]
    out_ref, acc_ref = refs[-2], refs[-1]
    epi_refs = refs[2:-2]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        y = acc_ref[...]
        oi = 0
        for fn, kind, head_pos, edt in epi_spec:
            if edt is not None:
                y = y.astype(edt)
            f = _EW[fn]
            if kind == "none":
                y = f(y)
            else:
                v = epi_refs[oi][...].astype(y.dtype)
                oi += 1
                if kind == "row":          # [1, bn] broadcast over rows
                    v = v.reshape(1, -1)
                y = f(y, v) if head_pos == 0 else f(v, y)
        out_ref[...] = y.astype(out_dtype)


def fused_matmul_kernel(x, w, epi_operands, epi_spec, *, bm, bn, bk,
                        out_dtype, interpret=False):
    """x: [m, k] (pre-padded to tile multiples), w: [k, n],
    epi_operands: arrays ([n] rows or [m, n] full) in epi_spec order,
    epi_spec: static tuple of (fn, kind, head_pos, dtype)."""
    m, k = x.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nm, nn, nk = m // bm, n // bn, k // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
        pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
    ]
    for (fn, kind, hp, edt) in epi_spec:
        if kind == "row":   # operands arrive as [1, n]
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)))
        elif kind == "full":
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)))

    return pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk, epi_spec=tuple(epi_spec),
                          out_dtype=out_dtype),
        grid=(nm, nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="fused_matmul",
    )(x, w, *epi_operands)

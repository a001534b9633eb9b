"""One-position SSM state update — Pallas TPU kernel, in place.

Grid: (slot, block of packed head rows).  Each step reads one block of a
slot's state ``[hb, N, L]`` (f32, ``L = g * P`` lanes, see ``ref``),
applies ``h <- a h + u B`` and writes it back over the same buffer
(``input_output_aliases``), then reduces ``y = sum_n C[n] h[n]`` over
sublanes.  The decay ``a`` and ``u = dt * x`` arrive as lane rows per
packed head row, ``B`` and ``C`` pre-broadcast along lanes, so the body
is broadcasts and one sublane reduction: the update streams the state
through VMEM once, read and written.  Rows of the state past the grid's
slots (parked requests) are never touched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _step_kernel(h_ref, au_ref, bc_ref, y_ref, ho_ref):
    h = h_ref[0]                                  # [hb, N, L]
    a = au_ref[0, :, 0:1, :]                      # [hb, 1, L]
    u = au_ref[0, :, 1:2, :]                      # [hb, 1, L]
    bm = bc_ref[0, 0]                             # [N, L]
    cm = bc_ref[0, 1]                             # [N, L]
    hn = a * h + u * bm[None]
    ho_ref[0] = hn
    y_ref[0] = jnp.sum(hn * cm[None], axis=1)


def ssm_state_step_kernel(h, au, bc, *, slots: int, rows_per_block: int,
                          interpret: bool = False):
    """h: f32[R, G, N, L] packed state rows (updated in place for rows
    < ``slots``); au: f32[slots, G, 2, L] (decay row, u row); bc:
    f32[slots, 2, N, L] (B and C along lanes).  Returns (y f32[slots, G,
    L], h)."""
    _, G, N, L = h.shape
    hb = rows_per_block
    return pl.pallas_call(
        _step_kernel,
        grid=(slots, G // hb),
        in_specs=[pl.BlockSpec((1, hb, N, L), lambda s, j: (s, j, 0, 0)),
                  pl.BlockSpec((1, hb, 2, L), lambda s, j: (s, j, 0, 0)),
                  pl.BlockSpec((1, 2, N, L), lambda s, j: (s, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, hb, L), lambda s, j: (s, j, 0)),
                   pl.BlockSpec((1, hb, N, L), lambda s, j: (s, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((slots, G, L), jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_state_step",
    )(h, au, bc)

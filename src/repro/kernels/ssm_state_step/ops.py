"""Public wrapper of the one-position SSM state-update kernel, the shapes
it takes, and the roofline terms the impl registry (``core.schedule``)
costs both lowerings with."""
from __future__ import annotations

import jax.numpy as jnp

from . import kernel

LANES = 128
#: bytes of state one grid step may carry (in and out are double-buffered)
BLOCK_BYTES = 1024 * 1024


def heads_per_row(h: int, p: int) -> int:
    """Heads packed side by side on one row of lanes: the most that fit
    the lane width and divide ``h`` (1 for a head of 128 lanes or more)."""
    g = max(1, LANES // p)
    while h % g:
        g -= 1
    return g


def pick_rows_per_block(g_rows: int, n: int, lanes: int) -> int:
    """Packed head rows per grid step: the largest divisor of ``g_rows``
    within ``BLOCK_BYTES`` that keeps the 8-row tiling (or all rows)."""
    best = g_rows
    for hb in range(g_rows, 0, -1):
        if g_rows % hb or (hb % 8 and hb != g_rows):
            continue
        best = hb
        if hb * n * lanes * 4 <= BLOCK_BYTES:
            return hb
    return best


def kernel_unsupported(s: int, h: int, p: int, n: int) -> str:
    """Why the kernel cannot take this shape on the TPU ('' when it can)."""
    g = heads_per_row(h, p)
    lanes = g * p
    if lanes % LANES and h // g != 1:
        return f"a packed row of {lanes} lanes is off the {LANES}-lane tiling"
    if n % 8:
        return f"state size {n} is off the 8-row tiling"
    hb = pick_rows_per_block(h // g, n, lanes)
    if hb * n * lanes * 4 > 4 * BLOCK_BYTES:
        return "one block of state exceeds the VMEM budget"
    return ""


def ssm_state_step(h, a, u, bm, cm, interpret: bool = False):
    """The kernel on ``ref.ssm_state_step_ref``'s arguments: h f32[R, G,
    N, g*P] packed rows; a f32[S, H]; u f32[S, H, P]; bm, cm f32[S, N].
    Returns (y f32[S, H, P], h updated in place for rows < S)."""
    S, H, P = u.shape
    _, G, N, L = h.shape
    a_row = jnp.broadcast_to(a[:, :, None], (S, H, P)).reshape(S, G, L)
    au = jnp.stack([a_row, u.reshape(S, G, L)], axis=2)
    bc = jnp.broadcast_to(jnp.stack([bm, cm], axis=1)[..., None],
                          (S, 2, N, L))
    y, h = kernel.ssm_state_step_kernel(
        h, au.astype(jnp.float32), bc.astype(jnp.float32), slots=S,
        rows_per_block=pick_rows_per_block(G, N, L), interpret=interpret)
    return y.reshape(S, H, P), h


def ssm_state_step_cost(s: int, h: int, p: int, n: int, impl: str) -> dict:
    """Roofline terms of one lowering: ``dict(flops, io_bytes)``.

    * ``kernel``    — reads and writes each slot's state once, in place;
                      the lane rows and broadcast B/C it reads beside it;
    * ``composite`` — XLA reads the rows, writes the unpacked update,
                      reads it again for ``y`` and writes the packed rows
                      back: four passes over the state.
    """
    state = 4.0 * s * h * p * n
    small = 4.0 * s * (3 * h * p + 2 * n)          # a and u rows, y, B, C
    out = dict(flops=5.0 * s * h * p * n, io_bytes=small)
    if impl == "kernel":
        g = heads_per_row(h, p)
        out["io_bytes"] += 2 * state + 4.0 * s * 2 * n * g * p
    elif impl == "composite":
        out["io_bytes"] += 4 * state
    else:
        raise ValueError(f"unknown ssm_state_step impl {impl!r}")
    return out

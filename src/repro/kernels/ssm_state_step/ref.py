"""Pure-jnp one-position SSM state update: the ``composite`` lowering (CPU
and mesh), and the oracle the kernel is tested against.

State rows are kept *packed*: ``[R, H / g, N, g * P]``, ``g`` heads side by
side on one row of lanes (``ops.heads_per_row``), so a head size of 64
fills the 128-lane tiling instead of padding it.  Row ``r`` of ``[N, g*P]``
holds state element ``(n, p)`` of head ``j * g + p // P`` at lane
``(p // P) * P + p % P``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_state(s, g: int):
    """[B, H, N, P] (``linear_scan``'s carried layout) -> packed
    [B, H / g, N, g * P]."""
    B, H, N, P = s.shape
    s = s.reshape(B, H // g, g, N, P).transpose(0, 1, 3, 2, 4)
    return s.reshape(B, H // g, N, g * P)


def unpack_state(s, p: int):
    """Packed [B, G, N, g * P] -> [B, G * g, N, P]."""
    B, G, N, L = s.shape
    g = L // p
    s = s.reshape(B, G, N, g, p).transpose(0, 1, 3, 2, 4)
    return s.reshape(B, G * g, N, p)


def ssm_state_step_ref(h, a, u, bm, cm):
    """h: f32[R, G, N, g*P] packed state rows (rows >= S untouched);
    a: f32[S, H] decay; u: f32[S, H, P] (dt * x); bm, cm: f32[S, N].
    For rows s < S: ``h <- a h + u (x) B``, ``y = C . h``.  Returns
    (y f32[S, H, P], the updated rows)."""
    S, H, P = u.shape
    hs = unpack_state(h[:S], P)                         # [S, H, N, P]
    hn = (a[:, :, None, None] * hs
          + bm[:, None, :, None] * u[:, :, None, :])
    y = jnp.einsum("shnp,sn->shp", hn, cm,
                   precision=jax.lax.Precision.HIGHEST)
    g = h.shape[-1] // P
    return y, jax.lax.dynamic_update_slice(h, pack_state(hn, g), (0, 0, 0, 0))

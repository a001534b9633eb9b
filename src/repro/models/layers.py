"""Shared layer primitives (norms, RoPE, shifts) — pure jnp; the GEMM-heavy
paths live behind ``repro.core.tapir`` ops.

Inside an open ``tapir`` region the norm/RoPE entry points dispatch through
``tapir.lift``: the very same jnp function becomes ONE opaque node of the
region graph (identical numerics), so a whole attention+MLP block captures
as a single TaskGraph instead of breaking at every norm."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tapir


def _rmsnorm_impl(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


_rmsnorm_jit = jax.jit(_rmsnorm_impl, static_argnames=("eps",))


def rmsnorm(x, scale, eps: float = 1e-6):
    # the eager path compiles the composite as ONE XLA computation — same
    # dispatch cost as a library call, and bitwise-identical to the node a
    # region traces (op-by-op eager dispatch would diverge in the last ulp
    # where jit fuses multiply-add chains into FMAs)
    if tapir.is_traced(x) or tapir.is_traced(scale):
        return tapir.lift(_rmsnorm_impl, x, scale, eps=eps)
    return _rmsnorm_jit(x, scale, eps=eps)


def _layernorm_impl(x, scale, bias=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


_layernorm_jit = jax.jit(_layernorm_impl, static_argnames=("eps",))


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    if tapir.is_traced(x) or tapir.is_traced(scale):
        if bias is None:
            return tapir.lift(_layernorm_impl, x, scale, eps=eps)
        return tapir.lift(_layernorm_impl, x, scale, bias, eps=eps)
    return _layernorm_jit(x, scale, bias, eps=eps)


def _groupnorm_heads_impl(x, scale, eps: float = 64e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


_groupnorm_heads_jit = jax.jit(_groupnorm_heads_impl, static_argnames=("eps",))


def groupnorm_heads(x, scale, eps: float = 64e-5):
    """Per-head groupnorm (RWKV6 wkv output norm).  x: [B,S,H,D]."""
    if tapir.is_traced(x) or tapir.is_traced(scale):
        return tapir.lift(_groupnorm_heads_impl, x, scale, eps=eps)
    return _groupnorm_heads_jit(x, scale, eps=eps)


def rope_table(positions, head_dim: int, base: float = 10000.0,
               fraction: float = 1.0):
    """cos/sin tables for the rotated ``fraction`` of head dims.
    positions: [S] (or [B,S]).  Returns cos,sin of [..., S, rot/2]."""
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float32) / rot)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def bucket_pow2(n: int, lo: int = 8) -> int:
    """Round ``n`` up to the next power of two (floor ``lo``) — the shape
    bucketing used by serving: region programs key on leaf shapes, so
    bucketed lengths replay from the program cache instead of re-tracing
    at every length."""
    m = lo
    while m < n:
        m *= 2
    return m


#: bucketed full RoPE tables, keyed by (bucket_len, head_dim, base,
#: fraction).  The arrays are cached so their *identities* are stable
#: across decode steps — a region that takes the table as an input binds
#: the same leaves every call and replays from the program cache.
_FULL_ROPE: dict = {}


def full_rope_table(max_len: int, head_dim: int, base: float = 10000.0,
                    fraction: float = 1.0):
    """cos/sin for ALL positions ``[0, bucket_pow2(max_len))``.

    Serving gathers per-slot rows from this table (``tapir.gather`` with
    the traced position vector) instead of recomputing cos/sin per step.
    The table length is rounded up to a power-of-two bucket: its shape —
    part of the region program-cache key — only changes when capacity
    crosses a bucket boundary, so a decode step whose ``pos`` (or
    configured ``max_len``) grows replays instead of re-tracing."""
    Lb = bucket_pow2(int(max_len))
    key = (Lb, int(head_dim), float(base), float(fraction))
    tab = _FULL_ROPE.get(key)
    if tab is None:
        cos, sin = rope_table(jnp.arange(Lb), head_dim, base, fraction)
        # uncommitted copies: computed under one engine's mesh, the table
        # must still be a valid input under the next engine's mesh
        tab = (jnp.asarray(np.asarray(cos)), jnp.asarray(np.asarray(sin)))
        _FULL_ROPE[key] = tab
    return tab


def arange_rope_table(seq_len: int, head_dim: int, base: float = 10000.0,
                      fraction: float = 1.0):
    """cos/sin for positions ``arange(seq_len)`` exactly (no bucketing),
    memoized so the array *identities* are stable across calls.

    The training-step capture takes the tables as region inputs; the
    replay cache requires every region input to arrive as a stable
    argument leaf — a table recomputed per call would force a re-trace
    every step.  Values are bitwise-identical to ``rope_table(arange(S))``
    (it IS that call, computed once)."""
    key = (int(seq_len), int(head_dim), float(base), float(fraction))
    tab = _ARANGE_ROPE.get(key)
    if tab is None:
        tab = rope_table(jnp.arange(seq_len), head_dim, base, fraction)
        _ARANGE_ROPE[key] = tab
    return tab


_ARANGE_ROPE: dict = {}


def apply_rope(x, cos, sin, fraction: float = 1.0):
    """x: [B,S,H,D].  chatglm-style '2d/half' rope passes fraction=0.5:
    only the first half of head dims rotates, the rest pass through."""
    if tapir.is_traced(x) or tapir.is_traced(cos):
        return tapir.lift(_apply_rope_impl, x, cos, sin, fraction=fraction)
    return _apply_rope_jit(x, cos, sin, fraction=fraction)


def _apply_rope_impl(x, cos, sin, fraction: float = 1.0):
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    if cos.ndim == 2:   # [S, rot/2] -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    elif cos.ndim == 3:  # [B, S, rot/2]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(*x1.shape[:-1], rot)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


_apply_rope_jit = jax.jit(_apply_rope_impl, static_argnames=("fraction",))


def _token_shift_shifted(x, state):
    return jnp.concatenate([state, x[:, :-1]], axis=1)


def _token_shift_zero(x):
    # zero initial state synthesized INSIDE the lifted fn: a fresh
    # jnp.zeros region input would disable the program-replay cache
    # (its id can't be rebound to an argument leaf)
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def token_shift(x, state=None):
    """RWKV token shift: x_{t-1} (zeros or ``state`` [B,1,D] at t=0).
    Returns (shifted, new_state [B,1,D])."""
    if tapir.is_traced(x) or tapir.is_traced(state):
        if state is None:
            shifted = tapir.lift(_token_shift_zero, x)
        else:
            shifted = tapir.lift(_token_shift_shifted, x, state)
        return shifted, x[:, -1:]
    if state is None:
        state = jnp.zeros_like(x[:, :1])
    shifted = jnp.concatenate([state, x[:, :-1]], axis=1)
    return shifted, x[:, -1:]


def _causal_conv_y(x, state, w):
    K = w.shape[0]
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return y.astype(x.dtype)


def _causal_conv_state(x, state):
    xp = jnp.concatenate([state, x], axis=1)
    return xp[:, x.shape[1]:] if state.shape[1] else state


def _causal_conv_y_zero(x, w):
    # zero state synthesized inside the lift (keeps program replay alive)
    K = w.shape[0]
    zero = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    return _causal_conv_y(x, zero, w)


def _causal_conv_state_zero(x, w):
    K = w.shape[0]
    zero = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    return _causal_conv_state(x, zero)


def causal_conv1d(x, w, state=None):
    """Depthwise causal conv.  x: [B,S,D], w: [K,D].  ``state``: [B,K-1,D]
    carry for decode.  Returns (y, new_state)."""
    K = w.shape[0]
    if tapir.is_traced(x) or tapir.is_traced(state) or tapir.is_traced(w):
        if state is None:
            y = tapir.lift(_causal_conv_y_zero, x, w)
            new_state = tapir.lift(_causal_conv_state_zero, x, w) \
                if K > 1 else jnp.zeros((x.shape[0], 0, x.shape[-1]),
                                        jnp.dtype(x.dtype))
            return y, new_state
        y = tapir.lift(_causal_conv_y, x, state, w)
        new_state = tapir.lift(_causal_conv_state, x, state) if K > 1 else state
        return y, new_state
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return y.astype(x.dtype), xp[:, -(K - 1):] if K > 1 else state

"""Chunked gated linear-attention scan — jit'd wrappers.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t^T v_t  is fork-join parallel in
chunked form: *intra-chunk* contributions are independent per chunk (the
fork: one dense [C,C] score block per chunk, MXU-friendly), and only the
[Dk,Dv] carry state crosses chunks (the join).  This is the TPU adaptation
of the paper's point that library recurrences (LSTMs there, SSMs here)
should be expressed so the compiler sees their parallel structure rather
than a sequential opaque call.

Derivation (b_t = prod_{s<=t} w_s inside a chunk, lb = log b):
  o_t = (q_t . b_t) S_0 + sum_{j<=t} ((q_t b_t / b_j) . k_j) v_j      (GLA)
RWKV6 uses S_{t-1} (strict triangle) plus the diag(u) bonus on the diagonal.
Intra-chunk scores are computed with a mid-chunk normalizer so the
exp(+/-lb) factors stay in fp32 range for chunk sizes <= 128.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


#: Largest numerically-exact chunk for the mid-normalized factored score
#: matmul given the model-side decay clip (log-decay per step >= -e^2):
#: need chunk * e^2 / 2 < 80  =>  chunk <= 21; we use the MXU-friendlier 16.
SAFE_CHUNK = 16


def linear_scan_chunked(q, k, v, w, u=None, chunk: int = SAFE_CHUNK,
                        init_state=None, return_state: bool = False):
    """Chunk-parallel jnp implementation (the tapir-mode CPU lowering)."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    C = max(1, min(chunk, S))
    Sp = _round_up(S, C)
    N = Sp // C
    pad = Sp - S

    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    wf = w.astype(jnp.float32)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        wf = jnp.pad(wf, ((0, 0), (0, pad), (0, 0), (0, 0)),
                     constant_values=1.0)

    def rs(t, d):
        return t.reshape(B, N, C, H, d)

    qc, kc, vc, wc = rs(qf, Dk), rs(kf, Dk), rs(vf, Dv), rs(wf, Dk)
    tri = jnp.tril(jnp.ones((C, C), bool), k=-1 if u is not None else 0)
    uf = u.astype(jnp.float32) if u is not None else None

    def step(S0, inp):  # S0: [B,H,Dk,Dv]; everything below per-chunk
        q_n, k_n, v_n, w_n = inp                      # [B,C,H,*]
        lw = jnp.log(w_n)
        lb = jnp.cumsum(lw, axis=1)                   # inclusive [B,C,H,Dk]
        lbq = lb - lw if u is not None else lb        # RWKV6 reads S_{t-1}
        mid = lb[:, C // 2][:, None]                  # normalizer [B,1,H,Dk]
        # Clamp the factor exponents: with per-step log-decay >= -L the valid
        # (lower-triangle) products have exponent <= 0, and each factor is
        # bounded by exp(C*L/2) — safe in fp32 for C*L/2 < 80 (C <= 21 at the
        # RWKV6 clip L = e^2).  Masked-region entries may still saturate; the
        # where() below drops them before they can poison the output.
        qt = q_n * jnp.exp(jnp.minimum(lbq - mid, 80.0))
        kt = k_n * jnp.exp(jnp.minimum(mid - lb, 80.0))
        A = jnp.einsum("bchd,bjhd->bhcj", qt, kt)     # [B,H,C,C]
        A = jnp.where(tri, A, 0.0)
        o = jnp.einsum("bhcj,bjhe->bche", A, v_n)     # intra
        if u is not None:
            bonus = jnp.einsum("bchd,hd,bchd->bch", q_n, uf, k_n)
            o = o + bonus[..., None] * v_n
        o = o + jnp.einsum("bchd,bhde->bche",         # inter (carry read)
                           q_n * jnp.exp(lbq), S0)
        dC = jnp.exp(lb[:, -1])                       # [B,H,Dk] chunk decay
        kE = k_n * jnp.exp(lb[:, -1][:, None] - lb)   # decay to chunk end
        S1 = dC[..., None] * S0 + jnp.einsum("bchd,bche->bhde", kE, v_n)
        return S1, o

    init = (jnp.zeros((B, H, Dk, Dv), jnp.float32) if init_state is None
            else init_state.astype(jnp.float32))
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (qc, kc, vc, wc))
    S_fin, o = jax.lax.scan(step, init, xs)
    o = jnp.moveaxis(o, 0, 1).reshape(B, Sp, H, Dv)[:, :S]
    o = o.astype(v.dtype)
    return (o, S_fin) if return_state else o


def linear_scan(q, k, v, w, u=None, chunk: int = 64,
                interpret: bool = False):
    """Pallas-kernel path (TPU target; ``interpret=True`` for tests)."""
    from . import kernel as _k
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    C = max(1, min(chunk, S))
    Sp = _round_up(S, C)
    pad = Sp - S

    def flat(t, d):
        t = jnp.moveaxis(t, 2, 1)                     # [B,H,S,d]
        return t.reshape(B * H, S, d)

    qf, kf, vf, wf = flat(q, Dk), flat(k, Dk), flat(v, Dv), flat(w, Dk)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
        wf = jnp.pad(wf, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    if u is None:
        ub = jnp.zeros((B * H, 1, Dk), jnp.float32)
        rwkv = False
    else:
        ub = jnp.broadcast_to(u.astype(jnp.float32)[None], (B, H, Dk)
                              ).reshape(B * H, 1, Dk)
        rwkv = True

    o = _k.linear_scan_kernel(qf, kf, vf, wf, ub, chunk=C, rwkv=rwkv,
                              interpret=interpret)
    o = o[:, :S].reshape(B, H, S, Dv)
    return jnp.moveaxis(o, 1, 2).astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def linear_scan_vjp(q, k, v, w, u, chunk=64, interpret=False):
    return linear_scan(q, k, v, w, u=u, chunk=chunk, interpret=interpret)


def _fwd(q, k, v, w, u, chunk, interpret):
    return linear_scan_vjp(q, k, v, w, u, chunk, interpret), (q, k, v, w, u)


def _bwd(chunk, interpret, res, do):
    q, k, v, w, u = res
    _, vjp = jax.vjp(lambda *a: ref.linear_scan_ref(*a), q, k, v, w, u)
    return vjp(do)


linear_scan_vjp.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Roofline cost descriptors (read by core.schedule's linear_scan registry)
# ---------------------------------------------------------------------------


def scan_cost(b, seq, h, d_k, d_v, eb, impl, chunk=SAFE_CHUNK):
    """Roofline terms for one candidate implementation of a linear_scan
    node: ``dict(flops, io_bytes, steps)``.

    ``steps`` is the serial trip count — the whole point of the chunked
    form: ``ref`` carries the state across every timestep (seq steps),
    ``chunked`` only across chunks (seq/chunk steps, each an MXU-friendly
    [C,C] score block), and the Pallas ``kernel`` runs the chunk loop on
    the TPU grid.  ``flops`` includes the factored intra-chunk score
    matmul that the chunked forms add over the plain recurrence."""
    flops = 8.0 * b * seq * h * d_v
    io = eb * b * seq * h * (2.0 * d_k + 2.0 * d_v)
    if impl == "ref":
        return dict(flops=flops, io_bytes=io, steps=int(seq))
    c = max(1, min(chunk, max(seq, 1)))
    flops += 2.0 * b * h * (-(-seq // c)) * c * c * (d_k + d_v)
    if impl == "chunked":
        return dict(flops=flops, io_bytes=io, steps=int(-(-seq // c)))
    if impl == "kernel":
        return dict(flops=flops, io_bytes=io, steps=0)
    raise ValueError(f"unknown linear_scan impl {impl!r}")

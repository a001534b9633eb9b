"""jit'd public wrapper for the fused GEMM kernel.

Handles: leading-batch flattening, padding to tile multiples, epilogue
spec/operand splitting, and a custom VJP (the backward GEMMs route through
plain XLA dots; the epilogue tail is differentiated by re-tracing the
reference composite).  ``interpret`` is the caller's choice: the TPU
program runs the Mosaic kernel, tests ask for the interpreter."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel, ref


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_block(bm: int, m: int) -> int:
    """The kernel's row tile for a scheduled ``bm`` over ``m`` rows: blocks
    obey the TPU's (8, 128) tiling whatever the schedule asked for.  Every
    row block of the grid streams the whole weight once."""
    return _round_up(min(bm, m), 8)


def _classify(epilogue, out_shape):
    """Split dynamic operands from the static spec the kernel needs."""
    spec, operands = [], []
    m, n = out_shape
    for fn, vals, at in epilogue or []:
        hp = at.get("head_pos", 0)
        edt = at.get("dtype")
        if not vals:
            spec.append((fn, "none", hp, edt))
            continue
        (v,) = vals  # one operand per epilogue stage
        if v.ndim <= 1 or (v.ndim == 2 and v.shape[0] == 1):
            spec.append((fn, "row", hp, edt))
            operands.append(
                jnp.broadcast_to(jnp.asarray(v).reshape(1, -1), (1, n)))
        else:
            spec.append((fn, "full", hp, edt))
            operands.append(jnp.broadcast_to(v.reshape(-1, v.shape[-1]), (m, n)))
    return tuple(spec), operands


def fused_matmul(x, w, epilogue=None, tile=None, out_dtype=None,
                 interpret: bool = False):
    """y = epilogue(x @ w);  x: [..., k], w: [k, n]."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(m, k)

    tile = tile or {}
    bm = row_block(tile.get("bm", 128), m)
    bn = _round_up(min(tile.get("bn", 128), n), 128)
    bk = _round_up(min(tile.get("bk", 512), k), 128)

    spec, operands = _classify(epilogue, (m, n))

    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    operands = [jnp.pad(o, ((0, 0), (0, np_ - n))) if o.shape[0] == 1
                else jnp.pad(o, ((0, mp - m), (0, np_ - n))) for o in operands]

    y = kernel.fused_matmul_kernel(x2, wp, operands, spec, bm=bm, bn=bn,
                                   bk=bk, out_dtype=out_dtype,
                                   interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


# -- differentiable wrapper ---------------------------------------------------


def _split_epilogue(epi_stages, epi_vals):
    """Rebuild ``[(fn, [operands], attrs)]`` from the static stage spec
    ``((fn, n_operands, attrs_items), ...)`` and the flat operand tuple."""
    out, i = [], 0
    for fn, n, at in epi_stages:
        out.append((fn, list(epi_vals[i:i + n]), dict(at)))
        i += n
    return out


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_matmul_vjp(x, w, epi_vals, epi_stages, out_dtype, tile=(),
                     interpret=False):
    """Differentiable ``fused_matmul``.  ``epi_vals``: flat tuple of the
    epilogue operands; ``epi_stages``: ``((fn, n_operands, attrs_items),
    ...)``; ``tile``: ``tuple(tile.items())`` — all static and hashable."""
    return fused_matmul(x, w, epilogue=_split_epilogue(epi_stages, epi_vals),
                        tile=dict(tile), out_dtype=out_dtype,
                        interpret=interpret)


def _fwd(x, w, epi_vals, epi_stages, out_dtype, tile, interpret):
    y = fused_matmul_vjp(x, w, epi_vals, epi_stages, out_dtype, tile,
                         interpret)
    return y, (x, w, epi_vals)


def _bwd(epi_stages, out_dtype, tile, interpret, res, dy):
    x, w, epi_vals = res

    def f(x_, w_, vals_):
        return ref.fused_matmul_ref(
            x_, w_, epilogue=_split_epilogue(epi_stages, vals_),
            out_dtype=out_dtype)

    _, vjp = jax.vjp(f, x, w, epi_vals)
    return vjp(dy)


fused_matmul_vjp.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Roofline cost descriptor (read by core.schedule's matmul impl registry)
# ---------------------------------------------------------------------------


def matmul_cost(batch, m, n, k, eb, impl, n_epilogue=0):
    """Roofline terms for one candidate implementation of a matmul node:
    ``dict(flops, io_bytes, steps)``.

    The fused ``kernel`` runs the epilogue on the fp32 accumulator tile in
    VMEM — extra operands stream once and the output writes once no matter
    how long the fused tail is.  The plain ``einsum`` pays one extra
    read+write of the output per epilogue stage (the traffic the
    epilogue-fusion pass exists to delete)."""
    flops = 2.0 * batch * m * n * k
    io = eb * batch * (m * k + k * n + m * n)
    if impl == "kernel":
        return dict(flops=flops, io_bytes=io, steps=0)
    if impl in ("einsum", "opaque"):
        return dict(flops=flops,
                    io_bytes=io + 2.0 * n_epilogue * eb * batch * m * n,
                    steps=0)
    raise ValueError(f"unknown matmul impl {impl!r}")

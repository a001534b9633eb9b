"""jit'd public wrapper for flash attention.

Layout plumbing ([B,S,H,D] <-> [B,H,S,D]), block-size clamping + padding,
custom VJP (backward is the standard recompute-based flash gradient,
expressed with the jnp oracle so it is correct on every backend; a
dedicated backward kernel is a TPU-side optimization).  ``interpret`` is
the caller's choice; shapes the kernel cannot run raise (the impl registry
rules them out first, see ``kernel_unsupported``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel, ref


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def kernel_unsupported(skv: int, causal: bool, has_bias: bool,
                       block_kv: int) -> str:
    """Why the kernel cannot run this attention ('' when it can): it has
    no bias operand, and without the causal mask padded keys would take
    softmax weight."""
    if has_bias:
        return "kernel has no bias operand"
    if not causal and skv % min(block_kv, _round_up(skv, 128)):
        return "non-causal with padded KV blocks"
    return ""


def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_kv: int = 128, interpret: bool = False):
    """q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D].  Returns [B, Sq, Hq, D]."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    why = kernel_unsupported(skv, causal, False, block_kv)
    if why:
        raise ValueError(f"flash kernel: {why}")

    block_q = min(block_q, _round_up(sq, 128))
    block_kv = min(block_kv, _round_up(skv, 128))
    sqp, skvp = _round_up(sq, block_q), _round_up(skv, block_kv)

    qt = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    # pad KV with -inf-free zeros; masked out because padded keys produce
    # scores at NEG_INF only under causal; for non-causal we mask via length
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))

    # align query positions to the END of the kv sequence (decode windows)
    q_offset = skv - sq if causal else 0

    o = kernel.flash_attention_kernel(
        qt, kt, vt, causal=causal, block_q=block_q, block_kv=block_kv,
        q_offset=q_offset, interpret=interpret)
    o = jnp.moveaxis(o, 1, 2)[:, :sq]
    return o


def flash_attention_jnp(q, k, v, causal: bool = False, block_kv: int = 1024):
    """Blockwise online-softmax attention in pure jnp (lax.scan over KV
    blocks).  Functionally identical to the Pallas kernel; this is the
    lowering used on non-TPU backends when the score matrix would not fit
    (e.g. 32k-sequence prefill) and the shape the multi-pod dry-run
    compiles — so the roofline sees flash memory behaviour, not a
    materialized [Sq, Skv] matrix."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    grp = hq // hkv
    bkv = min(block_kv, skv)
    nkv = -(-skv // bkv)
    skvp = nkv * bkv
    kp = jnp.pad(k, ((0, 0), (0, skvp - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skvp - skv), (0, 0), (0, 0)))
    qg = q.reshape(b, sq, hkv, grp, d).astype(jnp.float32)
    scale = 1.0 / np.sqrt(d)
    q_off = skv - sq  # causal: queries aligned to the end of kv

    def step(carry, i):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(kp, i * bkv, bkv, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, i * bkv, bkv, 1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(jnp.float32)) * scale
        kpos = i * bkv + jnp.arange(bkv)
        valid = kpos < skv
        if causal:
            qpos = q_off + jnp.arange(sq)
            valid = valid[None, :] & (kpos[None, :] <= qpos[:, None])
            valid = valid[None, None, None]
        else:
            valid = valid[None, None, None, None, :]
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, grp, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, grp, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, grp, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nkv))
    o = acc / jnp.where(l == 0, 1.0, l)[..., None]
    o = jnp.moveaxis(o, 3, 1).reshape(b, sq, hq, d)
    return o.astype(q.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_vjp(q, k, v, causal=False, block_q=128, block_kv=128,
                        interpret=False):
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_kv=block_kv, interpret=interpret)


def _fwd(q, k, v, causal, block_q, block_kv, interpret):
    return flash_attention_vjp(q, k, v, causal, block_q, block_kv,
                               interpret), (q, k, v)


def _bwd(causal, block_q, block_kv, interpret, res, do):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.attention_ref(q_, k_, v_,
                                                          causal=causal),
                     q, k, v)
    return vjp(do)


flash_attention_vjp.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Roofline cost descriptors (read by core.schedule's attention impl registry)
# ---------------------------------------------------------------------------


def attention_cost(b, sq, skv, h, hkv, d, eb, impl, block_kv=1024):
    """Roofline terms for one candidate implementation of an attention node.

    Returns ``dict(flops, io_bytes, score_bytes, copy_bytes, steps)``:

    * ``flops``       — arithmetic work, identical across impls (the score
                        and PV contractions; online-softmax rescales are
                        second-order and folded in for blockwise);
    * ``io_bytes``    — the unavoidable q/k/v/o streaming;
    * ``score_bytes`` — ONE pass over the fp32 [B,H,Sq,Skv] score matrix.
                        Impls that materialize it round-trip these bytes
                        several times (the multiplier is a CostModel knob:
                        a fused composite keeps score tiles VMEM-resident
                        on the TPU target but still walks them through the
                        cache hierarchy on a CPU); the flash kernel and the
                        blockwise scan never leave VMEM/registers -> 0;
    * ``copy_bytes``  — the GQA ``jnp.repeat`` K/V copy (repeat impl only);
    * ``steps``       — serial dispatch count (the lax.scan trip count of
                        the blockwise impl; the Cilk-style spawn-overhead
                        analogue that makes blockwise LOSE on tiny shapes).
    """
    grp = max(h // max(hkv, 1), 1)
    flops = 4.0 * b * h * sq * skv * d
    io = eb * (2.0 * b * sq * h * d + 2.0 * b * skv * hkv * d)
    score = 4.0 * b * h * sq * skv  # fp32 scores, one pass
    out = dict(flops=flops, io_bytes=io, score_bytes=0.0, copy_bytes=0.0,
               steps=0)
    if impl in ("materialized_grouped", "materialized_repeat", "ref",
                "opaque"):
        out["score_bytes"] = score
        if impl == "materialized_repeat" and grp > 1:
            out["copy_bytes"] = 2.0 * (grp - 1) * b * skv * hkv * d * eb
    elif impl == "blockwise":
        bkv = max(1, min(block_kv, skv))
        out["steps"] = -(-skv // bkv)
        out["flops"] += 2.0 * b * h * sq * d * out["steps"]  # rescale+accum
    elif impl != "flash_kernel":
        raise ValueError(f"unknown attention impl {impl!r}")
    return out

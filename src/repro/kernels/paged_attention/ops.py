"""Public wrapper of the paged decode-attention kernel, the shapes it can
take, its page-block size, and the roofline terms the impl registry
(``core.schedule``) costs both lowerings with."""
from __future__ import annotations

import jax.numpy as jnp

from . import kernel

#: bytes of K a page block should carry: a few hundred KiB per copy keeps
#: the per-block issue and wait cost small against the transfer
BLOCK_BYTES = 256 * 1024
#: VMEM the four page buffers (K and V, double-buffered) may take
VMEM_BUDGET = 16 * 1024 * 1024
#: page-table entries the scalar memory may hold
MAX_TABLE_ENTRIES = 32 * 1024
LANES = 128


def page_bytes(page_len: int, hkv: int, hd: int, eb: int) -> int:
    return page_len * hkv * hd * eb


def pick_pages_per_block(page_len: int, hkv: int, hd: int, eb: int,
                         pps: int) -> int:
    """The largest power of two of pages within ``BLOCK_BYTES`` (at least
    one), and no more than a slot holds."""
    pb = 1
    while pb * 2 <= pps and 2 * pb * page_bytes(page_len, hkv, hd, eb) \
            <= BLOCK_BYTES:
        pb *= 2
    return pb


def kernel_unsupported(b: int, s: int, h: int, hkv: int, hd: int,
                       page_len: int, pps: int, eb: int) -> str:
    """Why the kernel cannot take this shape on the TPU ('' when it can)."""
    if s != 1:
        return "kernel takes one query row per slot (decode)"
    if hkv < 1 or h % hkv:
        return "query heads not a multiple of KV heads"
    if hd % LANES:
        return f"head size {hd} not a multiple of the {LANES}-lane width"
    sublanes = 32 // eb                    # rows of one (sublane, lane) tile
    if (page_len * hkv) % sublanes:
        return (f"a page's {page_len * hkv} rows are off the {sublanes}-row"
                f" tiling")
    pb = pick_pages_per_block(page_len, hkv, hd, eb, pps)
    if 4 * pb * page_bytes(page_len, hkv, hd, eb) > VMEM_BUDGET:
        return "one page block exceeds the VMEM budget"
    if b * pps > MAX_TABLE_ENTRIES:
        return "page table exceeds the scalar memory"
    return ""


def paged_attention(q, ck, cv, ptab, lengths, pages_per_block=None,
                    interpret: bool = False):
    """q: [B, 1, H, hd]; ck/cv: [P, page_len, Hkv, hd] page pools; ptab:
    int32[B, pps]; lengths: int[B], the live positions of each slot (keys
    at positions >= length are masked, as in ``ref.paged_attention_
    gathered``).  Lengths are clamped to [1, pps * page_len].  Returns
    [B, 1, H, hd]."""
    b, s, h, hd = q.shape
    n_pages, page_len, hkv, _ = ck.shape
    pps = ptab.shape[1]
    if s != 1:
        raise ValueError(f"paged kernel: {s} query rows (decode takes 1)")
    if pages_per_block is None:
        pages_per_block = pick_pages_per_block(
            page_len, hkv, hd, jnp.dtype(ck.dtype).itemsize, pps)
    lens = jnp.clip(jnp.asarray(lengths).astype(jnp.int32), 1,
                    pps * page_len)
    o = kernel.paged_attention_kernel(
        lens, ptab.astype(jnp.int32), q.reshape(b, h, hd),
        ck.reshape(n_pages, page_len * hkv, hd),
        cv.reshape(n_pages, page_len * hkv, hd),
        page_len=page_len, pages_per_block=pages_per_block,
        interpret=interpret)
    return o.reshape(b, s, h, hd)


def paged_attention_cost(b, s, h, hkv, hd, page_len, pps, eb, impl):
    """Roofline terms of one lowering: ``dict(flops, io_bytes,
    score_bytes)``.  The live length is data, so both take the bound: a
    slot's whole ``pps * page_len`` view.

    * ``gathered``     — copies each slot's view of K and V out of the
                         pool (a read and a write) and the masked
                         attention reads the copy again; one pass over
                         the f32 score row of every position;
    * ``paged_kernel`` — reads at most the view once, in place; scores
                         stay in VMEM.
    """
    kv_len = pps * page_len
    view = float(eb) * b * kv_len * hkv * hd      # one of K or V
    out = dict(flops=4.0 * b * h * s * kv_len * hd,
               io_bytes=2.0 * eb * b * s * h * hd, score_bytes=0.0)
    if impl == "gathered":
        out["io_bytes"] += 2 * 3 * view
        out["score_bytes"] = 4.0 * b * h * s * kv_len
    elif impl == "paged_kernel":
        out["io_bytes"] += 2 * view
    else:
        raise ValueError(f"unknown paged_attention impl {impl!r}")
    return out

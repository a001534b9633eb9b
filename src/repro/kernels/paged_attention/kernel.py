"""Paged decode attention — Pallas TPU kernel that reads the KV page pool
in place.

The pool keeps its serving layout ``[P, page_len, Hkv, hd]``; the wrapper
views it as ``[P, page_len * Hkv, hd]`` (a bitcast of the same bytes), so
one page is one contiguous DMA that carries every KV head: row
``t * Hkv + h`` of a page is position ``t``'s key for head ``h``.

Grid: one step per slot, run in order.  Each step walks only that slot's
live pages (``ceil(len / page_len)``) in blocks of ``pages_per_block``
pages, copied HBM->VMEM by hand through the page table (scalar
prefetch), double-buffered: block ``i + 1``, or the next slot's first
block, is in flight while block ``i`` computes.  Pages past the length
are never fetched.  All query heads of the slot score against the block
at once ([H, hd] x [hd, rows]); a row counts for query head ``r`` only if
it belongs to head ``r // grp`` and its position is below the length.
The online softmax runs in f32 over blocks in logical order, so a slot's
result depends on its keys' positions, never on the physical pages that
hold them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)


def _paged_kernel(lens_ref, ptab_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sems, cur_ref, *, pages_per_block: int,
                  page_len: int, hkv: int, grp: int, pps: int,
                  sm_scale: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    pb = pages_per_block
    blk = pb * page_len                    # positions per block

    def page_copies(s, i, t):
        """(live, K copy, V copy) for each page of slot ``s``'s block
        ``i`` into buffer ``t``; copies of one buffer share a semaphore."""
        n_live = (lens_ref[s] + page_len - 1) // page_len
        out = []
        for j in range(pb):
            page = i * pb + j
            phys = ptab_ref[s * pps + jnp.minimum(page, pps - 1)]
            out.append((page < n_live,
                        pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[t, j],
                                              sems.at[0, t]),
                        pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[t, j],
                                              sems.at[1, t])))
        return out

    def start(s, i, t):
        for live, ck, cv in page_copies(s, i, t):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(s, i, t):
        for live, ck, cv in page_copies(s, i, t):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(b == 0)
    def _first():
        # pages a block does not fetch keep what the buffer held; zeros
        # at the start make that finite, so a zero weight stays zero
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        cur_ref[0] = 0
        start(0, 0, 0)

    length = lens_ref[b]
    n_blocks = (length + blk - 1) // blk
    q = q_ref[...]                         # [H, hd]
    h, d = q.shape
    rows = blk * hkv
    shape = (h, rows)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own_head = (col % hkv) == jax.lax.broadcasted_iota(jnp.int32, shape,
                                                       0) // grp
    col_pos = col // hkv

    def body(i, carry):
        m_prev, l_prev, acc = carry
        t = cur_ref[0]

        @pl.when(i + 1 < n_blocks)
        def _():
            start(b, i + 1, 1 - t)

        @pl.when((i + 1 == n_blocks) & (b + 1 < n_slots))
        def _():
            start(b + 1, 0, 1 - t)

        wait(b, i, t)
        k = kbuf[t].reshape(rows, d)
        v = vbuf[t].reshape(rows, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(own_head & (i * blk + col_pos < length), s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cur_ref[0] = 1 - t
        return m_new, l_new, acc

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    a0 = jnp.zeros((h, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_attention_kernel(lens, ptab, q, k_pages, v_pages, *,
                           page_len: int, pages_per_block: int,
                           interpret: bool = False):
    """lens: int32[B] in [1, pps * page_len]; ptab: int32[B, pps];
    q: [B, H, hd]; k_pages/v_pages: [P, page_len * Hkv, hd] (head-
    interleaved rows).  Returns [B, H, hd] in q's dtype."""
    b, h, d = q.shape
    rows = k_pages.shape[1]
    hkv = rows // page_len
    pps = ptab.shape[1]
    pb = pages_per_block
    return pl.pallas_call(
        functools.partial(_paged_kernel, pages_per_block=pb,
                          page_len=page_len, hkv=hkv, grp=h // hkv, pps=pps,
                          sm_scale=1.0 / np.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, h, d), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h, d), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pb, rows, d), k_pages.dtype),
                pltpu.VMEM((2, pb, rows, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # the next slot's first block is prefetched across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(lens, ptab.reshape(-1), q, k_pages, v_pages)

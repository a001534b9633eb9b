"""Pure-jnp paged attention: the ``gathered`` lowering, and the oracle the
kernel is tested against."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def masked_attention(q, ck, cv, valid_len):
    """Composite masked attention over a static-length KV cache.
    q: [B,S,H,hd], ck/cv: [B,maxlen,Hkv,hd]; positions >= valid_len masked.
    ``valid_len`` is a scalar (one shared length) or a [B] vector (the
    slot-paged cache: every slot has its own length — occupancy is data,
    not shape)."""
    B, S, H, hd = q.shape
    maxlen, Hkv = ck.shape[1], ck.shape[2]
    grp = H // Hkv
    qg = q.reshape(B, S, Hkv, grp, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    kpos = jnp.arange(maxlen)
    vl = jnp.asarray(valid_len)
    qpos = vl[..., None] - S + jnp.arange(S)       # [S] or [B,S]
    mask = kpos <= qpos[..., None]                 # causal within cache
    if mask.ndim == 2:
        mask = mask[None]                          # shared length -> [1,S,k]
    s = jnp.where(mask[:, None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def paged_attention_gathered(q, ck, cv, ptab, valid_len):
    """Masked attention over a per-slot *view* of the page pool.
    q: [B,S,H,hd]; ck/cv: [P,page_len,Hkv,hd] pools; ptab: [B,pps] page
    table.  Gathering ``pool[ptab]`` materialises each slot's logical
    [max_len] cache (shared prefix pages + private pages in one run) and
    the result is bitwise-identical to the unpaged layout: each query
    row's dot products, mask, and softmax depend only on its own keys,
    never on which pages back them."""
    B = q.shape[0]
    pl, Hkv, hd = ck.shape[1], ck.shape[2], ck.shape[3]
    pps = ptab.shape[-1]
    vk = ck[ptab].reshape(B, pps * pl, Hkv, hd)
    vv = cv[ptab].reshape(B, pps * pl, Hkv, hd)
    return masked_attention(q, vk, vv, valid_len)

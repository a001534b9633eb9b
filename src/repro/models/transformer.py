"""Dense GQA transformer LM (qwen1.5-110b, command-r-plus, qwen2.5-3b,
chatglm3 and the internvl2 backbone).  All GEMM-heavy paths route through
``repro.core.tapir``; layer stacking is a late-scheduled ``scan_layers``."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tapir
from repro.dist import shard_act
from repro.kernels.paged_attention.ref import masked_attention
from repro.spans import span

from . import layers as L
from .base import BaseModel, ModelConfig, ParamSpec, register_family


def _embed_lookup(embed, tokens, cdt):
    return jnp.take(embed, tokens, axis=0).astype(cdt)


def _transpose_2d(w):
    return w.T


def _block_specs(cfg: ModelConfig, n_layers: int) -> dict:
    d, hd = cfg.d_model, cfg.hd
    H, Hkv, ff = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    pdt = cfg.param_dtype
    Lx = (n_layers,)
    spec = {
        "ln1": ParamSpec(Lx + (d,), pdt, ("layers", "embed"), "ones"),
        "ln2": ParamSpec(Lx + (d,), pdt, ("layers", "embed"), "ones"),
        "wq": ParamSpec(Lx + (d, H * hd), pdt, ("layers", "embed", "heads")),
        "wk": ParamSpec(Lx + (d, Hkv * hd), pdt, ("layers", "embed", "kv")),
        "wv": ParamSpec(Lx + (d, Hkv * hd), pdt, ("layers", "embed", "kv")),
        "wo": ParamSpec(Lx + (H * hd, d), pdt, ("layers", "heads", "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec(Lx + (H * hd,), pdt, ("layers", "heads"), "zeros")
        spec["bk"] = ParamSpec(Lx + (Hkv * hd,), pdt, ("layers", "kv"), "zeros")
        spec["bv"] = ParamSpec(Lx + (Hkv * hd,), pdt, ("layers", "kv"), "zeros")
    if cfg.gated_mlp:
        spec["wg"] = ParamSpec(Lx + (d, ff), pdt, ("layers", "embed", "mlp"))
        spec["wu"] = ParamSpec(Lx + (d, ff), pdt, ("layers", "embed", "mlp"))
        spec["wd"] = ParamSpec(Lx + (ff, d), pdt, ("layers", "mlp", "embed"))
    else:
        spec["wu"] = ParamSpec(Lx + (d, ff), pdt, ("layers", "embed", "mlp"))
        spec["wd"] = ParamSpec(Lx + (ff, d), pdt, ("layers", "mlp", "embed"))
    return spec


@register_family("dense")
class DenseLM(BaseModel):

    def abstract_params(self) -> dict:
        cfg = self.cfg
        pdt = cfg.param_dtype
        p = {
            "embed": ParamSpec((cfg.vocab, cfg.d_model), pdt,
                               ("vocab", "embed"), scale=1.0),
            "blocks": _block_specs(cfg, cfg.n_layers),
            "ln_f": ParamSpec((cfg.d_model,), pdt, ("embed",), "ones"),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab), pdt,
                                     ("embed", "vocab"))
        return p

    # ------------------------------------------------------------------
    def _attn(self, p, x, cos, sin, causal=True, kv_cache=None, pos=None):
        cfg = self.cfg
        B, S, d = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        bs = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
        q, k, v = tapir.multi_linear(x, [p["wq"], p["wk"], p["wv"]], bs)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        frac = 0.5 if cfg.rope == "half" else 1.0
        q = L.apply_rope(q, cos, sin, frac)
        k = L.apply_rope(k, cos, sin, frac)
        q = shard_act(q, "batch", None, "heads", None)
        k = shard_act(k, "batch", None, "kv", None)
        v = shard_act(v, "batch", None, "kv", None)

        if kv_cache is None:
            o = tapir.attention(q, k, v, causal=causal)
        else:
            ck, cv, cpos, is_prefill = kv_cache
            # stateful capture: inside a region these become
            # dynamic_update_slice nodes that DONATE the cache buffers, so
            # the region jit writes the KV cache in place; outside they are
            # plain lax.dynamic_update_slice (identical numerics)
            ck = tapir.cache_write(ck, k, (0, cpos, 0, 0))
            cv = tapir.cache_write(cv, v, (0, cpos, 0, 0))
            if is_prefill:
                # flash path over the fresh K/V (cache only written)
                o = tapir.attention(q, k, v, causal=True)
            else:
                o = _decode_attention(q, ck, cv, cpos + S)
            kv_cache = (ck, cv)
        # heads-over-model on the attention node itself: per-head compute
        # is bitwise under this split, and the annotation is what lets
        # schedule.pick_gqa_impl cost the node per shard
        o = shard_act(o, "batch", None, "heads", None)
        o = o.reshape(B, S, H * hd)
        # gather the head-sharded attention output BEFORE the out-proj:
        # leaving it sharded makes GSPMD k-split the wo GEMM into per-rank
        # partial sums whose all-reduce reorders float adds — the
        # all-gather keeps mesh execution bitwise-equal to single device
        # (d_model bytes are tiny next to the score matrices)
        o = shard_act(o, "batch", None, None)
        out = tapir.linear(o, p["wo"])
        return (out, kv_cache) if kv_cache is not None else (out, None)

    def _mlp(self, p, x, gather_hidden: bool = False):
        """``gather_hidden``: the slot bodies keep ``wd`` replicated
        (``pin_slot_params``), so its contraction must see the whole hidden
        (see ``tapir.gated_mlp``)."""
        cfg = self.cfg
        if cfg.gated_mlp:
            return tapir.gated_mlp(x, p["wg"], p["wu"], p["wd"], cfg.act,
                                   gather_hidden)
        return tapir.linear(tapir.linear(x, p["wu"], activation=cfg.act),
                            p["wd"])

    def _residual(self, x, y):
        """A block's residual add (scaled in families that scale it)."""
        return x + y

    def _norm(self, x, scale):
        return L.rmsnorm(x, scale) if self.cfg.norm == "rmsnorm" \
            else L.layernorm(x, scale)

    def _attn_body(self, p, x, cos, sin):
        """Attention sub-block (norm + attn + residual) — region-wrapped on
        its own by families whose FFN can't trace (MoE routing)."""
        a, _ = self._attn(p, self._norm(x, p["ln1"]), cos, sin)
        return x + a

    def _block_body(self, p, x, cos, sin):
        x = self._attn_body(p, x, cos, sin)
        return x + self._mlp(p, self._norm(x, p["ln2"]))

    def _block(self, p, x, cos, sin):
        # Whole-region capture: the attention + gated-MLP block (norms,
        # QKV/O projections, residual adds) traces into ONE TaskGraph, so
        # the pass pipeline fuses across op-call boundaries — Q/K/V merge
        # into one wide GEMM and each residual add becomes a GEMM epilogue
        # — and the block executes as a single cached jax.jit call.  With
        # TapirConfig.regions=False this is byte-identical to the per-op
        # path (the region_vs_per_op benchmark control).
        blk = tapir.parallel_region(self._block_body, name="dense_block")
        x = blk(p, x, cos, sin)
        return shard_act(x, "batch", "seq", None)

    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        # lift keeps the lookup inside a region capture (a ``jnp.take`` on
        # a traced table would coerce and flush); outside a region it is a
        # direct call — same trace as the old inline form
        cdt = str(jnp.dtype(self.cfg.compute_dtype))
        return tapir.lift(_embed_lookup, params["embed"], tokens, cdt=cdt)

    def _head(self, params, x):
        x = self._norm(x, params["ln_f"])
        w = params.get("lm_head")
        if w is None:
            w = params["embed"]
            w = (tapir.lift(_transpose_2d, w) if tapir.is_traced(w)
                 else w.T)
        logits = tapir.linear(x, w.astype(x.dtype))
        return shard_act(logits, "batch", None, "vocab")

    def backbone(self, params, h, positions):
        frac = 0.5 if self.cfg.rope == "half" else 1.0
        if tapir.in_region():
            # identity-stable memoized tables: the training-step capture
            # binds them as region inputs, and program replay requires the
            # SAME leaves every call (values bitwise-equal to
            # ``rope_table(arange(S))`` — backbone only ever sees arange
            # positions, see ``forward``)
            cos, sin = L.arange_rope_table(int(positions.shape[0]),
                                           self.cfg.hd, fraction=frac)
        else:
            cos, sin = L.rope_table(positions, self.cfg.hd, fraction=frac)
        cdt = h.dtype

        def body(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            return self._block(p, x, cos, sin)

        return tapir.scan_layers(body, params["blocks"], h)

    def capture_aux(self, batch: dict) -> tuple:
        # the same memoized objects ``backbone`` fetches under capture
        return L.arange_rope_table(
            int(batch["tokens"].shape[1]), self.cfg.hd,
            fraction=0.5 if self.cfg.rope == "half" else 1.0)

    def forward(self, params, batch: dict):
        tokens = batch["tokens"]
        h = self._embed(params, tokens)
        h = shard_act(h, "batch", "seq", None)
        positions = jnp.arange(tokens.shape[1])
        h = self.backbone(params, h, positions)
        return self._head(params, h)

    # -- serving --------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = jnp.dtype(cfg.compute_dtype)
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, kv), "v": jnp.zeros(shape, kv),
                "pos": jnp.zeros((), jnp.int32)}

    def cache_specs(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = jnp.dtype(cfg.compute_dtype)
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jax.ShapeDtypeStruct(shape, kv),
                "v": jax.ShapeDtypeStruct(shape, kv),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}

    def cache_axes(self) -> dict:
        # "kvseq": the cache sequence dim shards over the model axis so
        # decode attention compiles to flash-decode partial softmax and
        # per-device cache bytes shrink by the TP degree.
        return {"k": ("layers", "batch", "kvseq", "kv", None),
                "v": ("layers", "batch", "kvseq", "kv", None),
                "pos": ()}

    def _cached_attn_body(self, p, x, cos, sin, ck, cv, pos0,
                          is_prefill: bool):
        """Attention sub-block against its KV-cache slab (stateful)."""
        a, (ck, cv) = self._attn(p, self._norm(x, p["ln1"]), cos, sin,
                                 kv_cache=(ck, cv, pos0, is_prefill))
        return x + a, ck, cv

    def _cached_block_body(self, p, x, cos, sin, ck, cv, pos0,
                           is_prefill: bool):
        """One transformer block against its KV-cache slab.  Under region
        capture (``tapir.parallel_region`` below) the whole step — norms,
        QKV, RoPE, the cache writes, masked decode attention, O-projection,
        residuals and the MLP — traces into ONE TaskGraph, executes as a
        single cached jit, and the cache writes donate their buffers."""
        x, ck, cv = self._cached_attn_body(p, x, cos, sin, ck, cv, pos0,
                                           is_prefill)
        x = x + self._mlp(p, self._norm(x, p["ln2"]))
        return x, ck, cv

    def _run_with_cache(self, params, tokens, cache, positions,
                        is_prefill: bool):
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        h = self._embed(params, tokens)
        cos, sin = L.rope_table(positions, cfg.hd,
                                fraction=0.5 if cfg.rope == "half" else 1.0)
        pos0 = cache["pos"]
        blk = tapir.parallel_region(self._cached_block_body,
                                    name="dense_cached_block")

        def body(carry, xs):
            x = carry
            p, ck, cv = xs
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            x, ck, cv = blk(p, x, cos, sin, ck, cv, pos0, is_prefill)
            return x, (ck, cv)

        h, (ck, cv) = jax.lax.scan(body, h,
                                   (params["blocks"], cache["k"], cache["v"]))
        cache = {"k": ck, "v": cv, "pos": pos0 + tokens.shape[1]}
        if is_prefill:
            h = h[:, -1:]   # only the last position's logits are served
        return self._head(params, h), cache

    def prefill(self, params, tokens, cache):
        positions = jnp.arange(tokens.shape[1])
        logits, cache = self._run_with_cache(params, tokens, cache,
                                             positions, is_prefill=True)
        return logits[:, -1], cache  # [B, vocab]

    def decode_step(self, params, tokens, cache):
        positions = cache["pos"] + jnp.arange(tokens.shape[1])
        logits, cache = self._run_with_cache(params, tokens, cache,
                                             positions, is_prefill=False)
        return logits[:, -1], cache

    # -- slot-paged serving (continuous batching) -----------------------
    #
    # The cache is a fixed [slots, max_len] page per layer plus a PER-SLOT
    # position vector: occupancy is data, not shape.  A decode step runs
    # every slot — each block is ONE region program (per-slot RoPE rows
    # gathered from the bucketed table, per-slot K/V scattered at
    # (slot, pos[slot]), per-slot masked attention) replayed from
    # ``_PROGRAMS`` regardless of which slots hold live requests.  New
    # requests enter a free slot MID-DECODE via ``prefill_into_slot``
    # (a dynamic-slot-start cache write), and finished slots free
    # immediately — no wave barrier anywhere.

    def supports_slots(self) -> bool:
        return True

    def init_slot_cache(self, slots: int, max_len: int,
                        page_len: int = None,
                        shared_pages: int = None) -> dict:
        """Per-layer physical page pools ``[P, page_len, Hkv, hd]``
        (python list — a layer's pool donates independently) plus the
        per-slot page table ``ptab [slots, pps]`` and length vector.

        ``P = 1 (trash) + slots*pps + shared_pages``: page 0 swallows
        out-of-capacity writes, each slot owns a fixed private page run,
        and the tail is the ref-counted shared-prefix region managed by
        ``repro.serve.pages.PagePool``.  The page indirection is DATA —
        a slot's KV view is ``pool[ptab[s]]`` — so binding shared pages
        never changes a program shape."""
        from repro.serve.pages import identity_row, page_geometry
        cfg = self.cfg
        kv = jnp.dtype(cfg.compute_dtype)
        pl, pps = page_geometry(max_len, page_len)
        if shared_pages is None:
            shared_pages = slots * pps
        P = 1 + slots * pps + shared_pages
        shape = (P, pl, cfg.n_kv_heads, cfg.hd)
        ptab = np.stack([identity_row(s, pps) for s in range(slots)])
        n = self._kv_layers()
        return {"k": [jnp.zeros(shape, kv) for _ in range(n)],
                "v": [jnp.zeros(shape, kv) for _ in range(n)],
                "ptab": jnp.asarray(ptab),
                "pos": jnp.zeros((slots,), jnp.int32)}

    def slot_cache_specs(self, slots: int, max_len: int,
                         page_len: int = None,
                         shared_pages: int = None) -> dict:
        return jax.eval_shape(lambda: self.init_slot_cache(
            slots, max_len, page_len, shared_pages))

    def slot_cache_axes(self) -> dict:
        """Logical axes of the page pools [P, page_len, Hkv, hd]: heads
        shard over ``model`` when divisible.  The page dims stay
        UNSHARDED — physical page ids are data-dependent (page-table
        indirection), so splitting them would turn every decode write
        into a collective."""
        a = (None, None, "kv", None)
        L = self._kv_layers()
        return {"k": [a] * L, "v": [a] * L, "ptab": (), "pos": ()}

    def _kv_layers(self) -> int:
        """Layers that keep KV pages."""
        return self.cfg.n_layers

    def slot_params(self, params) -> dict:
        """Per-layer param dicts + head params with STABLE array ids:
        slicing/casting is hoisted out of the decode loop so every region
        input rebinds to the same leaves and the program cache replays."""
        cdt = jnp.dtype(self.cfg.compute_dtype)
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        head = {"ln_f": params["ln_f"], "w": jnp.asarray(w).astype(cdt)}
        return {"layers": self._slot_layer_params(params, cdt),
                "head": head, "embed": params["embed"]}

    def _slot_layer_params(self, params, cdt) -> list:
        per_leaf = {k: _unstack(v, str(cdt))
                    for k, v in params["blocks"].items()}
        return [("dense", {k: vs[i] for k, vs in per_leaf.items()})
                for i in range(self.cfg.n_layers)]

    def slot_param_axes(self) -> dict:
        blocks = {k: tuple(s.axes[1:])
                  for k, s in _block_specs(self.cfg, self.cfg.n_layers).items()}
        return {"layers": [("dense", dict(blocks))
                           for _ in range(self.cfg.n_layers)],
                "head": {"ln_f": ("embed",), "w": ("embed", "vocab")},
                "embed": ("vocab", "embed")}

    def _rope_frac(self) -> float:
        return 0.5 if self.cfg.rope == "half" else 1.0

    def _slot_rope(self, q, k, rope_cos, rope_sin, pos, per_slot: bool):
        """Positions on the slot path's q and k: RoPE rows gathered at
        ``pos`` (one per slot in decode, one per prompt row in prefill)."""
        shape = (q.shape[0], 1, rope_cos.shape[-1]) if per_slot else None
        cos = tapir.gather(rope_cos, (pos,))
        cos = cos.reshape(shape) if per_slot else cos
        sin = tapir.gather(rope_sin, (pos,))
        sin = sin.reshape(shape) if per_slot else sin
        frac = self._rope_frac()
        return (L.apply_rope(q, cos, sin, frac),
                L.apply_rope(k, cos, sin, frac))

    def _slot_attn_body(self, p, x, rope_cos, rope_sin, ck, cv, pos, ptab):
        """Attention sub-block over the paged pool.  All data-dependent
        pieces are graph values: RoPE rows gather at ``pos``, the write
        target resolves through the page table
        (``phys = ptab[s, pos // page_len]``), K/V scatter at
        ``(phys, pos % page_len)``, and the ``paged_attention`` library op
        reads slot ``s``'s pages through ``ptab[s]`` with ``pos + 1``
        valid rows — page indirection is data, so one program serves
        every binding.
        On a mesh the ``shard_act`` constraints are captured as
        ``sharding`` annotations on the region nodes and replayed at
        lowering (heads over model; page dims unsharded so the donated
        writes stay in place per shard)."""
        cfg = self.cfg
        B = x.shape[0]
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        xn = self._norm(x, p["ln1"])
        bs = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
        q, k, v = tapir.multi_linear(xn, [p["wq"], p["wk"], p["wv"]], bs)
        q = q.reshape(B, 1, H, hd)
        k = k.reshape(B, 1, Hkv, hd)
        v = v.reshape(B, 1, Hkv, hd)
        q = shard_act(q, "batch", None, "heads", None)
        k = shard_act(k, "batch", None, "kv", None)
        v = shard_act(v, "batch", None, "kv", None)
        q, k = self._slot_rope(q, k, rope_cos, rope_sin, pos, per_slot=True)
        pidx, off = _page_coords_t(pos, page_len=int(ck.shape[1]))
        phys = tapir.gather(ptab, (np.arange(B), pidx))
        ck = tapir.scatter(ck, (phys, off), k.reshape(B, Hkv, hd))
        cv = tapir.scatter(cv, (phys, off), v.reshape(B, Hkv, hd))
        ck = shard_act(ck, None, None, "kv", None)
        cv = shard_act(cv, None, None, "kv", None)
        o = tapir.paged_attention(q, ck, cv, ptab, pos + 1)
        o = shard_act(o, "batch", None, "heads", None)
        # all-gather before wo so GSPMD never k-splits it (see _attn)
        o = shard_act(o.reshape(B, 1, H * hd), "batch", None, None)
        x = self._residual(x, tapir.linear(o, p["wo"]))
        return shard_act(x, "batch", None, None), ck, cv

    def _slot_block_body(self, p, x, rope_cos, rope_sin, ck, cv, pos, ptab):
        x, ck, cv = self._slot_attn_body(p, x, rope_cos, rope_sin, ck, cv,
                                         pos, ptab)
        x = x + self._mlp(p, self._norm(x, p["ln2"]), gather_hidden=True)
        return x, ck, cv

    def _slot_prefill_attn_body(self, p, x, rope_cos, rope_sin, ck, cv,
                                pos_vec, phys_vec, off_vec, prow, vlen):
        """Prefill one request's rows into its page run (B == 1).  The
        row targets are data: K/V land at ``(phys_vec[i], off_vec[i])``
        (out-of-range bucket padding targets the trash page), RoPE rows
        gather at absolute positions ``pos_vec``, and attention runs the
        masked kernel over the slot's gathered page view so a suffix
        prefill (start > 0, shared prefix pages already resident) is
        bitwise-identical per row to a full prefill of the same prompt."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        xn = self._norm(x, p["ln1"])
        bs = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
        q, k, v = tapir.multi_linear(xn, [p["wq"], p["wk"], p["wv"]], bs)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        q = shard_act(q, None, None, "heads", None)
        k = shard_act(k, None, None, "kv", None)
        v = shard_act(v, None, None, "kv", None)
        q, k = self._slot_rope(q, k, rope_cos, rope_sin, pos_vec,
                               per_slot=False)
        ck = tapir.scatter(ck, (phys_vec, off_vec), k.reshape(S, Hkv, hd))
        cv = tapir.scatter(cv, (phys_vec, off_vec), v.reshape(S, Hkv, hd))
        ck = shard_act(ck, None, None, "kv", None)
        cv = shard_act(cv, None, None, "kv", None)
        o = _paged_prefill_attn(q, ck, cv, prow, vlen)
        o = shard_act(o, None, None, "heads", None)
        # all-gather before wo so GSPMD never k-splits it (see _attn)
        o = shard_act(o.reshape(B, S, H * hd), None, None, None)
        x = self._residual(x, tapir.linear(o, p["wo"]))
        return x, ck, cv

    def _slot_prefill_block_body(self, p, x, rope_cos, rope_sin, ck, cv,
                                 pos_vec, phys_vec, off_vec, prow, vlen):
        x, ck, cv = self._slot_prefill_attn_body(
            p, x, rope_cos, rope_sin, ck, cv, pos_vec, phys_vec, off_vec,
            prow, vlen)
        x = x + self._mlp(p, self._norm(x, p["ln2"]), gather_hidden=True)
        return x, ck, cv

    def _prefill_targets(self, cache, slot: int, Sb: int, start: int):
        """Where rows ``[start, start + Sb)`` of a prompt in slot ``slot``
        land: (absolute positions, and as device arrays — rebindable region
        inputs, not baked-in consts — each row's physical page and in-page
        offset, the slot's page row, the valid length).  Rows past
        ``max_len`` go to the trash page."""
        pl = int(cache["k"][0].shape[1])
        row = np.asarray(cache["ptab"][slot])
        pps = row.shape[0]
        p_abs = start + np.arange(Sb)
        ok = p_abs < pps * pl
        pidx = np.minimum(p_abs // pl, pps - 1)
        phys = np.where(ok, row[pidx], 0).astype(np.int32)
        off = np.where(ok, p_abs % pl, 0).astype(np.int32)
        return (p_abs, jnp.asarray(phys), jnp.asarray(off), jnp.asarray(row),
                jnp.asarray(start + Sb, jnp.int32))

    def _slot_head_body(self, hp, x):
        x = self._norm(x, hp["ln_f"])
        logits = tapir.linear(x, hp["w"])[:, -1]
        return shard_act(logits, "batch", "vocab")

    def _slot_bodies(self) -> dict:
        return {"dense": self._slot_block_body}

    def _slot_prefill_bodies(self) -> dict:
        return {"dense": self._slot_prefill_block_body}

    def decode_step_slots(self, sp, tokens, cache):
        """One decode step for EVERY slot.  tokens: [slots, 1] (free slots
        carry don't-care tokens).  Returns (logits [slots, vocab], cache);
        per-slot positions advance by one, pool pages update in place
        (scatter donation).  The page table rides in the cache pytree as
        data, so rebinding pages (shared prefixes, COW, parking) never
        changes the program."""
        with span("model.decode", slots=tokens.shape[0]):
            cfg = self.cfg
            h = self._embed({"embed": sp["embed"]}, tokens)
            pl = cache["k"][0].shape[1]
            ptab = cache["ptab"]
            max_len = ptab.shape[1] * pl
            cos_t, sin_t = L.full_rope_table(max_len, cfg.hd,
                                             fraction=self._rope_frac())
            pos = cache["pos"]
            bodies = self._slot_bodies()
            blks = {kind: tapir.parallel_region(
                        fn, name=f"slot_{kind}_block")
                    for kind, fn in bodies.items()}
            for i, (kind, p) in enumerate(sp["layers"]):
                h, ck, cv = blks[kind](p, h, cos_t, sin_t, cache["k"][i],
                                       cache["v"][i], pos, ptab)
                cache["k"][i], cache["v"][i] = ck, cv
            head = tapir.parallel_region(self._slot_head_body,
                                         name="slot_head")
            logits = head(sp["head"], h)
            cache["pos"] = pos + 1
        return logits, cache

    def prefill_into_slot(self, sp, tokens, cache, slot: int, plen: int,
                          start: int = 0):
        """Insert one request into slot ``slot`` mid-decode.  tokens:
        [1, Sb] rows ``[start, start + Sb)`` of the prompt, right-padded
        to a power-of-two bucket.  ``start > 0`` is a *suffix* prefill:
        positions < start are already resident in the slot's page run
        (shared prefix pages) and only the divergent rows run.  Padding
        rows past ``plen`` write garbage into real offsets (decode masks
        them via pos[slot] = plen, exactly as before); padding rows past
        ``max_len`` are routed to the trash page so they can never
        corrupt live pages.  Returns (logits [1, vocab] at prompt row
        plen-1, cache)."""
        cfg = self.cfg
        Sb = tokens.shape[1]
        with span("model.prefill", tokens=Sb):
            p_abs, phys_vec, off_vec, prow, vlen = self._prefill_targets(
                cache, slot, Sb, start)
            max_len = cache["ptab"].shape[1] * int(cache["k"][0].shape[1])
            h = self._embed({"embed": sp["embed"]}, tokens)
            cos_t, sin_t = L.full_rope_table(max(max_len, Sb), cfg.hd,
                                             fraction=self._rope_frac())
            pos_vec = jnp.asarray(np.minimum(
                p_abs, cos_t.shape[0] - 1).astype(np.int32))
            bodies = self._slot_prefill_bodies()
            blks = {kind: tapir.parallel_region(
                        fn, name=f"slot_{kind}_prefill")
                    for kind, fn in bodies.items()}
            for i, (kind, p) in enumerate(sp["layers"]):
                h, ck, cv = blks[kind](p, h, cos_t, sin_t,
                                       cache["k"][i], cache["v"][i],
                                       pos_vec, phys_vec, off_vec, prow, vlen)
                cache["k"][i], cache["v"][i] = ck, cv
            hrow = jax.lax.dynamic_slice_in_dim(h, plen - 1 - start, 1,
                                                axis=1)
            head = tapir.parallel_region(self._slot_head_body,
                                         name="slot_head")
            logits = head(sp["head"], hrow)
            cache["pos"] = cache["pos"].at[slot].set(plen)
        return logits, cache


@partial(jax.jit, static_argnums=1)
def _unstack(v, dtype: str) -> list:
    """Per-layer slices of a stacked leaf, cast to ``dtype``: one program
    per leaf shape instead of one eager slice per layer."""
    return [v[i].astype(dtype) for i in range(v.shape[0])]


def _decode_attention(q, ck, cv, valid_len):
    """Traced-aware wrapper: inside a region the masked cache attention
    captures as one ``pyfunc`` node (ordered after the cache writes it
    reads); outside it runs as one jitted composite (same dispatch cost as
    a library call, bitwise-identical to the region's node)."""
    if any(tapir.is_traced(t) for t in (q, ck, cv, valid_len)):
        vl = valid_len if hasattr(valid_len, "shape") else jnp.asarray(
            valid_len, jnp.int32)
        return tapir.lift(masked_attention, q, ck, cv, vl)
    return _masked_attention_jit(q, ck, cv, valid_len)


_masked_attention_jit = jax.jit(masked_attention)


def _page_coords(pos, *, page_len):
    """Split absolute positions into (page index, in-page offset)."""
    pos = jnp.asarray(pos)
    return ((pos // page_len).astype(jnp.int32),
            (pos % page_len).astype(jnp.int32))


def _page_coords_t(pos, *, page_len):
    if tapir.is_traced(pos):
        return tapir.lift(_page_coords, pos, page_len=page_len)
    return _page_coords(pos, page_len=page_len)


def _paged_prefill_attention(q, ck, cv, prow, valid_len):
    """Prefill attention for one slot through its page row.  q:
    [1,S,H,hd]; prow: [pps] page ids.  Reuses the masked decode kernel so
    a suffix prefill (rows [start, start+S)) computes each kept row
    bitwise-identically to the full prefill of the same prompt: per-row
    causal masking only ever reads keys < row position, which are the
    same bytes whether they came from a shared prefix page or were just
    written."""
    pl, Hkv, hd = ck.shape[1], ck.shape[2], ck.shape[3]
    pps = prow.shape[-1]
    vk = ck[prow].reshape(1, pps * pl, Hkv, hd)
    vv = cv[prow].reshape(1, pps * pl, Hkv, hd)
    return masked_attention(q, vk, vv, valid_len)


def _paged_prefill_attn(q, ck, cv, prow, valid_len):
    if any(tapir.is_traced(t) for t in (q, ck, cv, prow, valid_len)):
        vl = valid_len if hasattr(valid_len, "shape") else jnp.asarray(
            valid_len, jnp.int32)
        return tapir.lift(_paged_prefill_attention, q, ck, cv, prow, vl)
    return _paged_prefill_attention_jit(q, ck, cv, prow, valid_len)


_paged_prefill_attention_jit = jax.jit(_paged_prefill_attention)

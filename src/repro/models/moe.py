"""Mixture-of-Experts LM (moonshot-v1-16b-a3b / moonlight, granite-moe).

Routing is capacity-based top-k with renormalized gates.  The expert FFN
GEMMs go through ``tapir.expert_mlp``: in opaque mode they lower to one
isolated library call per expert (stock XLA's structure); in tapir mode to
grouped batched GEMMs with fused epilogues — the MoE instance of the
paper's exposed-library claim."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import tapir
from repro.dist import shard_act

from .base import ModelConfig, ParamSpec, register_family
from .transformer import DenseLM, _block_specs


def _moe_block_specs(cfg: ModelConfig, n_layers: int) -> dict:
    spec = _block_specs(cfg, n_layers)
    for key in ("wg", "wu", "wd"):
        spec.pop(key, None)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Eh = experts_held(cfg)
    pdt = cfg.param_dtype
    Lx = (n_layers,)
    spec["router"] = ParamSpec(Lx + (d, E), pdt, ("layers", "embed", None))
    spec["ewg"] = ParamSpec(Lx + (Eh, d, ff), pdt,
                            ("layers", "expert", "embed", "mlp"))
    spec["ewu"] = ParamSpec(Lx + (Eh, d, ff), pdt,
                            ("layers", "expert", "embed", "mlp"))
    spec["ewd"] = ParamSpec(Lx + (Eh, ff, d), pdt,
                            ("layers", "expert", "mlp", "embed"))
    if cfg.shared_ff:
        sf = cfg.shared_ff
        spec["swg"] = ParamSpec(Lx + (d, sf), pdt, ("layers", "embed", "mlp"))
        spec["swu"] = ParamSpec(Lx + (d, sf), pdt, ("layers", "embed", "mlp"))
        spec["swd"] = ParamSpec(Lx + (sf, d), pdt, ("layers", "mlp", "embed"))
    return spec


def experts_held(cfg: ModelConfig) -> int:
    """Experts whose weights this chip holds (all of them without an
    ``expert_range``)."""
    if cfg.expert_range is None:
        return cfg.n_experts
    lo, hi = cfg.expert_range
    return hi - lo


def _held_routes(eidx, keep, *, lo: int, hi: int):
    """Routes to the experts held here, ``lo .. hi-1``: expert ids made
    local, every other route dropped from ``keep`` (its part of the
    result is the absent experts' chips' to add)."""
    mine = (eidx >= lo) & (eidx < hi)
    return jnp.where(mine, eidx - lo, 0), keep & mine


def _route_topk(xt, router, *, k: int, e: int, cap: int):
    """Top-k routing: (renormalized gates, expert ids, capacity positions,
    keep mask).  ONE pure-jnp composite shared by the per-op path (called
    eagerly) and the region path (captured as a ``pyfunc`` via
    ``tapir.lift``) — the router's data-dependent control stays a graph
    value feeding the gather/scatter dispatch nodes."""
    T = xt.shape[0]
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                           # [T, K]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    # capacity assignment: position of each (token, k) within its expert
    onehot = jax.nn.one_hot(eidx, e, dtype=jnp.int32)              # [T, K, E]
    flat = onehot.reshape(T * k, e)
    pos = jnp.cumsum(flat, axis=0) - flat                          # pre-count
    pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)               # [T, K]
    keep = pos < cap
    pos = jnp.where(keep, pos, cap - 1)
    return gate, eidx, pos, keep


def _dispatch_src(xt, keep, *, k: int, cdt: str):
    """Token rows replicated per routed copy, zeroed where dropped —
    the scatter-add update buffer [T*K, d]."""
    T, d = xt.shape
    src = jnp.where(keep[..., None],
                    jnp.broadcast_to(xt[:, None], (T, k, d)), 0)
    return src.reshape(T * k, d).astype(cdt)


def _combine_expert_out(fetched, keep, gate, *, k: int, cdt: str):
    """Weighted sum of the gathered expert outputs over the k routes."""
    T = keep.shape[0]
    d = fetched.shape[-1]
    f = fetched.reshape(T, k, d)
    f = jnp.where(keep[..., None], f, 0)
    return jnp.sum(f * gate[..., None].astype(cdt), axis=1)


@register_family("moe")
class MoELM(DenseLM):

    def abstract_params(self) -> dict:
        cfg = self.cfg
        p = super().abstract_params()
        F = cfg.first_dense_layers
        blocks = {}
        if F > 0:
            blocks["dense"] = _block_specs(cfg, F)
        blocks["moe"] = _moe_block_specs(cfg, cfg.n_layers - F)
        p["blocks"] = blocks
        return p

    # -- routing ----------------------------------------------------------
    def _moe_ffn(self, p, x):
        """Dispatch selector: on a mesh with a model axis that divides E,
        use the expert-parallel shard_map dispatch (local routing per data
        shard, experts resident per model shard, one psum to combine).
        Otherwise the global dense dispatch below.

        Why: the global scatter's capacity dim cannot be partitioned by
        GSPMD (data-dependent indices spanning the global batch), so every
        device materializes and multiplies the FULL [E, cap, d] buffer —
        data parallelism is lost exactly at the expert GEMM.  Baseline
        dry-run: moonshot train HLO flops ~20x model flops, 142s
        collective term.  The shard_map path keeps tokens sharded,
        restores the 1/dp factor, and replaces the scatter/gather
        collective storm with one [T_local, d] all-reduce per layer.
        """
        if tapir.is_traced(x):
            # open region: the whole dispatch (top-k routing, token
            # scatter, expert GEMMs, gather-back, combine) captures as
            # graph nodes, with the expert-dim sharding constraints
            # recorded on them (replayed at lowering under the mesh).
            # The EP shard_map path stays per-op only — shard_map's
            # per-shard python callable can't trace into the IR.
            return self._moe_ffn_traced(p, x)
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and "model" in mesh.axis_names:
            n_model = mesh.shape["model"]
            dp = [a for a in ("pod", "data") if a in mesh.axis_names]
            dp_size = 1
            for a in dp:
                dp_size *= mesh.shape[a]
            if (self.cfg.n_experts % n_model == 0
                    and x.shape[0] % max(dp_size, 1) == 0 and dp):
                return self._moe_ffn_ep(p, x, mesh, tuple(dp), n_model)
        return self._moe_ffn_global(p, x)

    def _moe_ffn_ep(self, p, x, mesh, dp: tuple, n_model: int):
        """Expert-parallel dispatch under shard_map (see _moe_ffn)."""
        from jax.sharding import PartitionSpec as P
        cfg = self.cfg
        B, S, d = x.shape
        E, K = cfg.n_experts, cfg.top_k
        El = E // n_model
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        T_loc = (B // dp_size) * S
        cap = max(1, int(math.ceil(T_loc * K / E * cfg.capacity_factor)))
        cap = min(cap, T_loc)
        if S == 1:
            cap = T_loc   # dropless decode (see _moe_ffn_global)
        batch_ax = dp[0] if len(dp) == 1 else tuple(dp)

        def ffn(x_loc, router, ewg, ewu, ewd):
            # x_loc: [B/dp, S, d]; ewg/ewu/ewd: [El, ...] (this shard's
            # experts); router replicated.
            Bl = x_loc.shape[0]
            xt = x_loc.reshape(T_loc, d)
            logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            gate, eidx = jax.lax.top_k(probs, K)              # [T,K]
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

            j = jax.lax.axis_index("model")
            lo = j * El
            eloc = eidx - lo
            mine = (eidx >= lo) & (eidx < lo + El)            # [T,K]
            onehot = jnp.where(mine[..., None],
                               jax.nn.one_hot(eloc, El, dtype=jnp.int32), 0)
            flat = onehot.reshape(T_loc * K, El)
            pos = jnp.cumsum(flat, axis=0) - flat
            pos = jnp.sum(pos * flat, axis=-1).reshape(T_loc, K)
            keep = mine & (pos < cap)
            pos_c = jnp.where(keep, pos, cap - 1)
            eloc_c = jnp.where(keep, eloc, 0)

            cdt = x_loc.dtype
            src = jnp.where(keep[..., None],
                            jnp.broadcast_to(xt[:, None], (T_loc, K, d)), 0)
            xe = jnp.zeros((El, cap, d), cdt)
            xe = xe.at[eloc_c.reshape(-1), pos_c.reshape(-1)].add(
                src.reshape(T_loc * K, d).astype(cdt), mode="drop")

            ye = tapir.expert_mlp(xe, ewg, ewu, ewd, cfg.act)

            fetched = ye[eloc_c.reshape(-1), pos_c.reshape(-1)
                         ].reshape(T_loc, K, d)
            fetched = jnp.where(keep[..., None], fetched, 0)
            out = jnp.sum(fetched * gate[..., None].astype(cdt), axis=1)
            out = jax.lax.psum(out, "model")   # combine across expert shards
            return out.reshape(Bl, S, d)

        sm_kwargs = dict(
            mesh=mesh,
            in_specs=(P(batch_ax, None, None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(batch_ax, None, None))
        f = jax.shard_map(ffn, check_vma=False, **sm_kwargs)
        # cast expert weights to compute dtype BEFORE the shard_map
        # boundary: the FSDP gather at entry and the gradient psum the VJP
        # inserts at exit both move bf16 instead of f32 (2x less DCN)
        return f(x, p["router"].astype(x.dtype), p["ewg"].astype(x.dtype),
                 p["ewu"].astype(x.dtype), p["ewd"].astype(x.dtype))

    def _moe_cap(self, T: int, S: int, dropless: bool) -> int:
        cfg = self.cfg
        cap = max(1, int(math.ceil(T * cfg.top_k / cfg.n_experts
                                   * cfg.capacity_factor)))
        cap = min(cap, T)
        if S == 1 or dropless:
            # decode (and slot-serving prefill): dropless — capacity
            # limits are a training construct; dropping tokens would
            # corrupt generation
            cap = T
        return cap

    def _moe_ffn_global(self, p, x):
        cfg = self.cfg
        B, S, d = x.shape
        T = B * S
        E, K = cfg.n_experts, cfg.top_k
        cap = self._moe_cap(T, S, dropless=False)

        xt = x.reshape(T, d)
        gate, eidx, pos, keep = _route_topk(xt, p["router"], k=K, e=E,
                                            cap=cap)
        # dispatch (scatter tokens into [E, cap, d])
        cdt = x.dtype
        xe = jnp.zeros((E, cap, d), cdt)
        src = _dispatch_src(xt, keep, k=K, cdt=str(cdt))
        xe = xe.at[eidx.reshape(-1), pos.reshape(-1)].add(src, mode="drop")
        xe = shard_act(xe, "expert", None, None)

        ye = tapir.expert_mlp(xe, p["ewg"], p["ewu"], p["ewd"], cfg.act)
        ye = shard_act(ye, "expert", None, None)

        # combine (gather back + weighted sum over k)
        fetched = ye[eidx.reshape(-1), pos.reshape(-1)]
        out = _combine_expert_out(fetched, keep, gate, k=K, cdt=str(cdt))
        return out.reshape(B, S, d)

    def _moe_ffn_traced(self, p, x, dropless: bool = False):
        """Region capture of the FULL dispatch — the piece that used to
        flush back to per-op execution.  The router runs as one lifted
        composite whose outputs (gate/eidx/pos/keep) are graph values; the
        token dispatch is a zero-init ``scatter`` node and the combine a
        ``gather`` node indexed BY those values — so a MoE decode step is
        ONE region program, router included."""
        cfg = self.cfg
        B, S, d = x.shape
        T = B * S
        E, K = cfg.n_experts, cfg.top_k
        cap = self._moe_cap(T, S, dropless)
        cdt = str(x.dtype)

        xt = x.reshape(T, d)
        gate, eidx, pos, keep = tapir.lift(_route_topk, xt, p["router"],
                                           k=K, e=E, cap=cap)
        held = None
        if cfg.expert_range is not None:
            # the router scores all E experts; only the held ones run here
            lo, hi = cfg.expert_range
            eidx, keep = tapir.lift(_held_routes, eidx, keep, lo=lo, hi=hi)
            held, E = (lo, hi, E), hi - lo
        src = tapir.lift(_dispatch_src, xt, keep, k=K, cdt=cdt)
        ef, pf = eidx.reshape(T * K), pos.reshape(T * K)
        xe = tapir.scatter_new((E, cap, d), cdt, (ef, pf), src, mode="add")
        # same constraints the per-op dispatch applies: on a mesh the
        # expert dim of the dispatch/combine buffers shards over "model"
        # (captured as node annotations, replayed at lowering)
        xe = shard_act(xe, "expert", None, None)
        ye = tapir.expert_mlp(xe, p["ewg"], p["ewu"], p["ewd"], cfg.act,
                              held=held)
        ye = shard_act(ye, "expert", None, None)
        fetched = tapir.gather(ye, (ef, pf))
        out = tapir.lift(_combine_expert_out, fetched, keep, gate,
                         k=K, cdt=cdt)
        return out.reshape(B, S, d)

    # -- forward ----------------------------------------------------------
    def backbone(self, params, h, positions):
        from . import layers as L
        cfg = self.cfg
        cos, sin = L.rope_table(positions, cfg.hd,
                                fraction=0.5 if cfg.rope == "half" else 1.0)
        cdt = h.dtype

        def dense_body(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            return self._block(p, x, cos, sin)

        attn_blk = tapir.parallel_region(self._attn_body, name="moe_attn")

        def moe_body(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            # attention sub-block traces as one region; the MoE dispatch
            # (data-dependent top-k routing + scatter) stays per-op
            x = attn_blk(p, x, cos, sin)
            x = x + self._moe_ffn(p, self._norm(x, p["ln2"]))
            return shard_act(x, "batch", "seq", None)

        blocks = params["blocks"]
        if "dense" in blocks:
            h = tapir.scan_layers(dense_body, blocks["dense"], h)
        return tapir.scan_layers(moe_body, blocks["moe"], h)

    def forward(self, params, batch: dict):
        tokens = batch["tokens"]
        h = self._embed(params, tokens)
        positions = jnp.arange(tokens.shape[1])
        h = self.backbone(params, h, positions)
        return self._head(params, h)

    # -- serving ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = jnp.dtype(cfg.compute_dtype)
        F = cfg.first_dense_layers
        mk = lambda L_: jnp.zeros((L_, batch, max_len, cfg.n_kv_heads, cfg.hd), kv)
        return {"k_dense": mk(F), "v_dense": mk(F),
                "k_moe": mk(cfg.n_layers - F), "v_moe": mk(cfg.n_layers - F),
                "pos": jnp.zeros((), jnp.int32)}

    def cache_specs(self, batch: int, max_len: int) -> dict:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def cache_axes(self) -> dict:
        a = ("layers", "batch", "kvseq", "kv", None)
        return {"k_dense": a, "v_dense": a, "k_moe": a, "v_moe": a, "pos": ()}

    def _cached_moe_block_body(self, p, x, cos, sin, ck, cv, pos0,
                               is_prefill: bool):
        """One MoE block against its KV-cache slab — attention, cache
        writes AND the routed expert FFN (top-k + scatter dispatch via
        gather/scatter nodes) in ONE region: the last per-op island in a
        decode step is gone."""
        x, ck, cv = self._cached_attn_body(p, x, cos, sin, ck, cv, pos0,
                                           is_prefill)
        x = x + self._moe_ffn(p, self._norm(x, p["ln2"]))
        return x, ck, cv

    def _run_with_cache(self, params, tokens, cache, positions, is_prefill):
        from repro.core.passes import mesh_has_model_axis

        from . import layers as L
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        h = self._embed(params, tokens)
        cos, sin = L.rope_table(positions, cfg.hd,
                                fraction=0.5 if cfg.rope == "half" else 1.0)
        pos0 = cache["pos"]

        dense_blk = tapir.parallel_region(self._cached_block_body,
                                          name="moe_dense_cached_block")
        moe_blk = tapir.parallel_region(self._cached_moe_block_body,
                                        name="moe_cached_block")
        attn_blk = tapir.parallel_region(self._cached_attn_body,
                                         name="moe_cached_attn")
        # under a model-axis mesh the expert FFN keeps its EP shard_map
        # dispatch (per-op, outside the region); otherwise the router +
        # dispatch capture INTO the block's region via gather/scatter
        one_region = not mesh_has_model_axis()

        def body_factory(is_moe):
            def body(carry, xs):
                x = carry
                p, ck, cv = xs
                p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
                if is_moe and one_region:
                    x, ck, cv = moe_blk(p, x, cos, sin, ck, cv, pos0,
                                        is_prefill)
                elif is_moe:
                    x, ck, cv = attn_blk(p, x, cos, sin, ck, cv, pos0,
                                         is_prefill)
                    x = x + self._moe_ffn(p, self._norm(x, p["ln2"]))
                else:
                    x, ck, cv = dense_blk(p, x, cos, sin, ck, cv, pos0,
                                          is_prefill)
                return x, (ck, cv)
            return body

        blocks = params["blocks"]
        new_cache = {"pos": pos0 + tokens.shape[1]}
        if "dense" in blocks and cfg.first_dense_layers > 0:
            h, (ck, cv) = jax.lax.scan(body_factory(False), h,
                                       (blocks["dense"], cache["k_dense"],
                                        cache["v_dense"]))
            new_cache["k_dense"], new_cache["v_dense"] = ck, cv
        else:
            new_cache["k_dense"] = cache["k_dense"]
            new_cache["v_dense"] = cache["v_dense"]
        h, (ck, cv) = jax.lax.scan(body_factory(True), h,
                                   (blocks["moe"], cache["k_moe"],
                                    cache["v_moe"]))
        new_cache["k_moe"], new_cache["v_moe"] = ck, cv
        if is_prefill:
            h = h[:, -1:]
        return self._head(params, h), new_cache

    # -- slot-paged serving ----------------------------------------------
    def _slot_layer_params(self, params, cdt) -> list:
        cfg = self.cfg
        blocks = params["blocks"]
        layers = []
        if "dense" in blocks:
            for i in range(cfg.first_dense_layers):
                layers.append(("dense", {k: v[i].astype(cdt)
                                         for k, v in blocks["dense"].items()}))
        for i in range(cfg.n_layers - cfg.first_dense_layers):
            layers.append(("moe", {k: v[i].astype(cdt)
                                   for k, v in blocks["moe"].items()}))
        return layers

    def slot_param_axes(self) -> dict:
        cfg = self.cfg
        base = super().slot_param_axes()
        dense = {k: tuple(s.axes[1:])
                 for k, s in _block_specs(cfg, 1).items()}
        moe = {k: tuple(s.axes[1:])
               for k, s in _moe_block_specs(cfg, 1).items()}
        layers = [("dense", dict(dense))
                  for _ in range(cfg.first_dense_layers)]
        layers += [("moe", dict(moe))
                   for _ in range(cfg.n_layers - cfg.first_dense_layers)]
        base["layers"] = layers
        return base

    def _slot_moe_block_body(self, p, x, rope_cos, rope_sin, ck, cv, pos,
                             ptab):
        """MoE decode block over the paged pool: attention, page-table
        cache scatter AND the routed expert FFN in ONE region."""
        x, ck, cv = self._slot_attn_body(p, x, rope_cos, rope_sin, ck, cv,
                                         pos, ptab)
        x = x + self._moe_ffn_traced(p, self._norm(x, p["ln2"]))
        return x, ck, cv

    def _slot_prefill_moe_block_body(self, p, x, rope_cos, rope_sin, ck, cv,
                                     pos_vec, phys_vec, off_vec, prow, vlen):
        # dropless: serving prefill pads prompts to a bucket; capacity
        # drops there would let padding evict real tokens
        x, ck, cv = self._slot_prefill_attn_body(
            p, x, rope_cos, rope_sin, ck, cv, pos_vec, phys_vec, off_vec,
            prow, vlen)
        x = x + self._moe_ffn_traced(p, self._norm(x, p["ln2"]),
                                     dropless=True)
        return x, ck, cv

    def _slot_bodies(self) -> dict:
        return {"dense": self._slot_block_body,
                "moe": self._slot_moe_block_body}

    def _slot_prefill_bodies(self) -> dict:
        return {"dense": self._slot_prefill_block_body,
                "moe": self._slot_prefill_moe_block_body}

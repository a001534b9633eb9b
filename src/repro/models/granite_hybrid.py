"""Granite 4.0 hybrid (``granitemoehybrid``): Mamba2 and attention layers,
every one followed by a routed mixture of experts plus a shared expert.

Each layer ``l`` of ``cfg.layer_types`` is

    x = x + r * mixer_l(norm(x))        mixer: Mamba2 SSD or GQA attention
    x = x + r * (moe(norm(x)) + shared(norm(x)))

with ``r = cfg.residual_scale``; the embedding is scaled by
``cfg.embed_scale`` and the logits divided by ``cfg.logit_scale``.  The
attention layers carry no position embedding (NoPE) and scale scores by
``cfg.attn_scale``: q is pre-scaled so the attention ops' fixed
``1/sqrt(hd)`` yields it.

Built from the pieces the other families use: the Mamba2 in/out
projections and SSD gates of ``mamba.py``, ``MoELM``'s routed dispatch
(with ``cfg.expert_range`` naming the experts held here) and the slot
attention bodies of ``DenseLM``.

Slot serving keeps, per slot, one row of SSM state and one of conv state
for every Mamba2 layer beside the attention layers' KV pages
(``slot_state_rows``).  A decode step advances the rows in place through
the ``ssm_state_step`` library op; a prefill starts from zero state and
writes the prompt's final state into the slot's rows.  Each layer runs as
two region programs, its mixer and its MoE, so a device trace tells the
Mamba, attention and MoE programs apart.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import tapir
from repro.kernels.linear_scan import ops as ls_ops
from repro.kernels.ssm_state_step.ops import heads_per_row
from repro.kernels.ssm_state_step.ref import pack_state
from repro.spans import span

from . import layers as L
from .base import ParamSpec, register_family
from .mamba import (CONV_K, _mamba_block_specs, _mamba_dims, _ssd_gates,
                    ssd_in_proj, ssd_out_proj)
from .moe import MoELM, _moe_block_specs
from .transformer import _unstack

#: parameters of a layer's FFN half (everything else is its mixer's)
FFN_KEYS = ("ln2", "router", "ewg", "ewu", "ewd", "swg", "swu", "swd")


def _embed_scaled(embed, tokens, cdt: str, scale: float):
    return (jnp.take(embed, tokens, axis=0) * scale).astype(cdt)


_embed_scaled_jit = jax.jit(_embed_scaled, static_argnames=("cdt", "scale"))


def _conv_tail(xBC, n_valid, *, k: int):
    """The conv state after the first ``n_valid`` rows from zero state:
    rows ``n_valid .. n_valid + k - 2`` of ``[zeros(k - 1), xBC]``."""
    B, _, C = xBC.shape
    xp = jnp.concatenate([jnp.zeros((B, k - 1, C), xBC.dtype), xBC], axis=1)
    return jax.lax.dynamic_slice_in_dim(xp, n_valid, k - 1, axis=1)


def _mask_dt(dt, n_valid):
    """Rows at or past ``n_valid`` (bucket padding) get dt = -inf: their
    softplus is 0, so the scan carries the state past them unchanged."""
    rows = jnp.arange(dt.shape[1])[None, :, None]
    return jnp.where(rows < n_valid, dt, -jnp.inf).astype(dt.dtype)


def _ssd_scan(q, k, v, w):
    """The SSD over whole rows from zero state: (y, final state
    f32[B, H, N, P])."""
    return ls_ops.linear_scan_chunked(q, k, v, w, chunk=ls_ops.SAFE_CHUNK,
                                      return_state=True)


def _ssm_step_gates(xBC, dt, dt_bias, A_log, *, din: int, N: int, H: int):
    """One position's SSD gates, f32, as ``ssm_state_step`` takes them:
    decay a [S, H], u = dt * x [S, H, P], B and C [S, N] (the formulas of
    ``mamba._ssd_gates``)."""
    S = xBC.shape[0]
    xb = xBC[:, 0].astype(jnp.float32)
    x = xb[:, :din].reshape(S, H, din // H)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) +
                          dt_bias.astype(jnp.float32))          # [S, H]
    a = jnp.exp(-jnp.exp(jnp.clip(A_log.astype(jnp.float32), -6.0, 4.0))
                * dtv)
    return a, dtv[..., None] * x, xb[:, din:din + N], xb[:, din + N:]


@register_family("granite_hybrid")
class GraniteHybrid(MoELM):

    # -- layout -------------------------------------------------------------
    def _order(self) -> list:
        """``(kind, index within its kind)`` for every layer, in order."""
        seen = {"mamba": 0, "attention": 0}
        out = []
        for t in self.cfg.layer_types:
            out.append((t, seen[t]))
            seen[t] += 1
        return out

    def _count(self, kind: str) -> int:
        return sum(1 for t in self.cfg.layer_types if t == kind)

    def _layer_specs(self, kind: str, n: int) -> dict:
        cfg = self.cfg
        moe = _moe_block_specs(cfg, n)
        if kind == "attention":
            return moe
        spec = _mamba_block_specs(cfg, n)
        din, _, _, N = _mamba_dims(cfg)
        spec["conv_b"] = ParamSpec((n, din + 2 * N), cfg.param_dtype,
                                   ("layers", None), "zeros")
        spec.update({k: v for k, v in moe.items() if k in FFN_KEYS})
        return spec

    def abstract_params(self) -> dict:
        cfg = self.cfg
        pdt = cfg.param_dtype
        return {
            "embed": ParamSpec((cfg.vocab, cfg.d_model), pdt,
                               ("vocab", "embed")),
            "blocks": {k: self._layer_specs(k, self._count(k))
                       for k in ("mamba", "attention") if self._count(k)},
            "ln_f": ParamSpec((cfg.d_model,), pdt, ("embed",), "ones"),
        }

    def _g(self) -> int:
        din, H, hd, _ = _mamba_dims(self.cfg)
        return heads_per_row(H, hd)

    # -- shared pieces --------------------------------------------------------
    def _norm(self, x, scale):
        return L.rmsnorm(x, scale, self.cfg.norm_eps)

    def _residual(self, x, y):
        return x + y * self.cfg.residual_scale

    def _embed(self, params, tokens):
        kw = dict(cdt=str(jnp.dtype(self.cfg.compute_dtype)),
                  scale=self.cfg.embed_scale)
        if tapir.is_traced(params["embed"]) or tapir.is_traced(tokens):
            return tapir.lift(_embed_scaled, params["embed"], tokens, **kw)
        return _embed_scaled_jit(params["embed"], tokens, **kw)

    def _slot_rope(self, q, k, rope_cos, rope_sin, pos, per_slot: bool):
        """NoPE: no rotation; q pre-scaled so the attention ops' fixed
        ``1/sqrt(hd)`` yields ``attn_scale``."""
        s = self.cfg.attn_scale
        if s is None:
            return q, k
        return q * (s * math.sqrt(self.cfg.hd)), k

    def _mamba_mix(self, p, x, n_valid):
        """One Mamba2 mixer over whole rows from zero state: (out, final
        SSM state f32[B, H, N, P], conv state [B, K-1, C]).  Rows at or
        past ``n_valid`` are padding and leave the state as it was."""
        cfg = self.cfg
        B, S, _ = x.shape
        din, H, hd, N = _mamba_dims(cfg)
        z, xBC, dt = ssd_in_proj(cfg, p, x)
        tail = tapir.lift(_conv_tail, xBC, n_valid, k=CONV_K)
        xBC, _ = L.causal_conv1d(xBC, p["conv_w"])
        xBC = tapir.elemwise(xBC + p["conv_b"], "silu")
        xc = xBC[..., :din].reshape(B, S, H, hd)
        dt = tapir.lift(_mask_dt, dt, n_valid)
        q, k, w = tapir.lift(_ssd_gates, xBC, dt, p["dt_bias"], p["A_log"],
                             din=din, N=N, H=H, dtype="float32")
        y, st = tapir.lift(_ssd_scan, q, k, xc, w)
        return ssd_out_proj(cfg, p, y, xc, z, x.dtype, cfg.norm_eps), st, tail

    def _moe_body(self, p, x):
        """The FFN half of a layer: routed experts (dropless) plus the
        shared expert, scaled onto the residual."""
        xn = self._norm(x, p["ln2"])
        y = self._moe_ffn_traced(p, xn, dropless=True)
        if self.cfg.shared_ff:
            y = y + tapir.gated_mlp(xn, p["swg"], p["swu"], p["swd"],
                                    self.cfg.act)
        return self._residual(x, y)

    # -- whole-sequence forward (no cache) --------------------------------------
    def _fwd_mamba_body(self, p, x):
        n = jnp.asarray(x.shape[1], jnp.int32)
        out, _, _ = self._mamba_mix(p, self._norm(x, p["ln"]), n)
        return self._residual(x, out)

    def _fwd_attention_body(self, p, x):
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q, k, v = tapir.multi_linear(self._norm(x, p["ln1"]),
                                     [p["wq"], p["wk"], p["wv"]])
        q, k = self._slot_rope(q.reshape(B, S, H, hd), k.reshape(B, S, Hkv, hd),
                               None, None, None, per_slot=False)
        o = tapir.attention(q, k, v.reshape(B, S, Hkv, hd), causal=True)
        return self._residual(x, tapir.linear(o.reshape(B, S, H * hd),
                                              p["wo"]))

    def _layer_params(self, params, kind: str, j: int, cdt) -> dict:
        return {k: v[j].astype(cdt) for k, v in params["blocks"][kind].items()}

    def forward(self, params, batch: dict):
        cdt = jnp.dtype(self.cfg.compute_dtype)
        h = self._embed(params, batch["tokens"])
        mix = {"mamba": tapir.parallel_region(self._fwd_mamba_body,
                                              name="granite_mamba"),
               "attention": tapir.parallel_region(self._fwd_attention_body,
                                                  name="granite_attention")}
        moe = tapir.parallel_region(self._moe_body, name="granite_moe")
        for kind, j in self._order():
            p = self._layer_params(params, kind, j, cdt)
            h = moe(p, mix[kind](p, h))
        return self._head(params, h) / self.cfg.logit_scale

    # -- slot-paged serving -------------------------------------------------------
    def slot_state_rows(self) -> dict:
        cfg = self.cfg
        din, H, hd, N = _mamba_dims(cfg)
        g = self._g()
        return {"ssm": (H // g, N, g * hd), "conv": (CONV_K - 1, din + 2 * N)}

    def _kv_layers(self) -> int:
        return self._count("attention")

    def init_slot_cache(self, slots: int, max_len: int, page_len: int = None,
                        shared_pages: int = None) -> dict:
        """The attention layers' page pools, page table and lengths (as
        ``DenseLM``'s), and ``state``: per Mamba2 layer, SSM rows
        f32[rows, H/g, N, g*hd] (packed, ``kernels.ssm_state_step.ref``)
        and conv rows [rows, K-1, C]; rows past ``slots`` park preempted
        requests (one per slot's worth of the shared region)."""
        from repro.serve.pages import default_shared_pages, page_geometry
        cfg = self.cfg
        pps = page_geometry(max_len, page_len)[1]
        if shared_pages is None:
            shared_pages = default_shared_pages(slots, pps, True)
        cache = super().init_slot_cache(slots, max_len, page_len,
                                        shared_pages)
        rows = slots + shared_pages // pps
        st = self.slot_state_rows()
        n_m = self._count("mamba")
        cdt = jnp.dtype(cfg.compute_dtype)
        cache["state"] = {
            "ssm": [jnp.zeros((rows,) + st["ssm"], jnp.float32)
                    for _ in range(n_m)],
            "conv": [jnp.zeros((rows,) + st["conv"], cdt)
                     for _ in range(n_m)]}
        return cache

    def slot_cache_axes(self) -> dict:
        n_m = self._count("mamba")
        return dict(super().slot_cache_axes(),
                    state={"ssm": [(None,) * 4] * n_m,
                           "conv": [(None,) * 3] * n_m})

    def _split(self, d: dict) -> dict:
        return {"mix": {k: v for k, v in d.items() if k not in FFN_KEYS},
                "ffn": {k: v for k, v in d.items() if k in FFN_KEYS}}

    def _slot_layer_params(self, params, cdt) -> list:
        per = {kind: {k: _unstack(v, str(cdt)) for k, v in blk.items()}
               for kind, blk in params["blocks"].items()}
        return [(kind, self._split({k: vs[j] for k, vs in per[kind].items()}))
                for kind, j in self._order()]

    def slot_param_axes(self) -> dict:
        axes = {kind: {k: tuple(s.axes[1:])
                       for k, s in self._layer_specs(kind, 1).items()}
                for kind in ("mamba", "attention")}
        return {"layers": [(kind, self._split(dict(axes[kind])))
                           for kind, _ in self._order()],
                "head": {"ln_f": ("embed",), "w": ("embed", "vocab")},
                "embed": ("vocab", "embed")}

    def _slot_head_body(self, hp, x):
        return super()._slot_head_body(hp, x) / self.cfg.logit_scale

    def _slot_mamba_body(self, p, x, ssm, conv):
        """One decode position of a Mamba2 mixer for every slot: the conv
        rows and the SSM rows advance in place."""
        cfg = self.cfg
        S = x.shape[0]
        din, H, hd, N = _mamba_dims(cfg)
        z, xBC, dt = ssd_in_proj(cfg, p, self._norm(x, p["ln"]))
        c0 = tapir.cache_read(conv, (0, 0, 0), (S,) + tuple(conv.shape[1:]))
        xBC, c1 = L.causal_conv1d(xBC, p["conv_w"], c0)
        conv = tapir.cache_write(conv, c1, (0, 0, 0))
        xBC = tapir.elemwise(xBC + p["conv_b"], "silu")
        a, u, bm, cm = tapir.lift(_ssm_step_gates, xBC, dt, p["dt_bias"],
                                  p["A_log"], din=din, N=N, H=H)
        y, ssm = tapir.ssm_state_step(ssm, a, u, bm, cm)
        xc = xBC[..., :din].reshape(S, 1, H, hd)
        out = ssd_out_proj(cfg, p, y.reshape(S, 1, H, hd), xc, z, x.dtype,
                           cfg.norm_eps)
        return self._residual(x, out), ssm, conv

    def _slot_attention_body(self, p, x, ck, cv, pos, ptab):
        return self._slot_attn_body(p, x, None, None, ck, cv, pos, ptab)

    def _slot_mamba_prefill_body(self, p, x, ssm, conv, slot, n_valid):
        """A prompt through a Mamba2 mixer from zero state; the final SSM
        and conv state land in slot ``slot``'s rows."""
        out, st, tail = self._mamba_mix(p, self._norm(x, p["ln"]), n_valid)
        ssm = tapir.cache_write(ssm, tapir.lift(pack_state, st, g=self._g()),
                                (slot, 0, 0, 0))
        conv = tapir.cache_write(conv, tail, (slot, 0, 0))
        return self._residual(x, out), ssm, conv

    def _slot_attention_prefill_body(self, p, x, ck, cv, phys_vec, off_vec,
                                     prow, vlen):
        return self._slot_prefill_attn_body(p, x, None, None, ck, cv, None,
                                            phys_vec, off_vec, prow, vlen)

    def _regions(self, prefill: bool) -> tuple:
        """(mixer region per kind, MoE region, head region) of a decode
        step or a prefill."""
        if prefill:
            bodies = {"mamba": self._slot_mamba_prefill_body,
                      "attention": self._slot_attention_prefill_body}
        else:
            bodies = {"mamba": self._slot_mamba_body,
                      "attention": self._slot_attention_body}
        phase = "prefill" if prefill else "block"
        mix = {kind: tapir.parallel_region(fn, name=f"slot_{kind}_{phase}")
               for kind, fn in bodies.items()}
        moe = tapir.parallel_region(self._moe_body, name=f"slot_moe_{phase}")
        head = tapir.parallel_region(self._slot_head_body, name="slot_head")
        return mix, moe, head

    def decode_step_slots(self, sp, tokens, cache):
        """One decode step for every slot: per layer its mixer program
        (Mamba2 rows or paged attention), then its MoE program; state rows
        and pages update in place."""
        with span("model.decode", slots=tokens.shape[0]):
            h = self._embed({"embed": sp["embed"]}, tokens)
            mix, moe, head = self._regions(prefill=False)
            st, pos, ptab = cache["state"], cache["pos"], cache["ptab"]
            for (kind, j), (_, p) in zip(self._order(), sp["layers"]):
                if kind == "mamba":
                    h, st["ssm"][j], st["conv"][j] = mix[kind](
                        p["mix"], h, st["ssm"][j], st["conv"][j])
                else:
                    h, cache["k"][j], cache["v"][j] = mix[kind](
                        p["mix"], h, cache["k"][j], cache["v"][j], pos, ptab)
                h = moe(p["ffn"], h)
            logits = head(sp["head"], h)
            cache["pos"] = pos + 1
        return logits, cache

    def prefill_into_slot(self, sp, tokens, cache, slot: int, plen: int,
                          start: int = 0):
        """Prefill a prompt into slot ``slot`` from zero state (tokens [1,
        Sb], right-padded to a bucket; ``start`` is 0: no prefix is shared
        with a model that keeps state).  Returns (logits [1, vocab] at row
        plen-1, cache)."""
        if start:
            raise ValueError("a model with state rows prefills whole prompts")
        Sb = tokens.shape[1]
        with span("model.prefill", tokens=Sb):
            _, phys_vec, off_vec, prow, vlen = self._prefill_targets(
                cache, slot, Sb, 0)
            slot_i, n_valid = (jnp.asarray(slot, jnp.int32),
                               jnp.asarray(plen, jnp.int32))
            h = self._embed({"embed": sp["embed"]}, tokens)
            mix, moe, head = self._regions(prefill=True)
            st = cache["state"]
            for (kind, j), (_, p) in zip(self._order(), sp["layers"]):
                if kind == "mamba":
                    h, st["ssm"][j], st["conv"][j] = mix[kind](
                        p["mix"], h, st["ssm"][j], st["conv"][j], slot_i,
                        n_valid)
                else:
                    h, cache["k"][j], cache["v"][j] = mix[kind](
                        p["mix"], h, cache["k"][j], cache["v"][j], phys_vec,
                        off_vec, prow, vlen)
                h = moe(p["ffn"], h)
            hrow = jax.lax.dynamic_slice_in_dim(h, plen - 1, 1, axis=1)
            logits = head(sp["head"], hrow)
            cache["pos"] = cache["pos"].at[slot].set(plen)
        return logits, cache


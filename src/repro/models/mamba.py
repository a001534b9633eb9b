"""Mamba2 (SSD) blocks and the Zamba2 hybrid (Mamba2 stack + one *shared*
attention+MLP block applied every ``shared_attn_every`` layers).

The SSD recurrence  h_t = a_t h_{t-1} + (dt_t B_t) x_t,  y_t = C_t h_t + D x_t
is the scalar-decay special case of the gated linear-attention scan, so it
lowers through the same exposed ``linear_scan`` library kernel as RWKV6
(q=C, k=dt*B, v=x-heads, w=a broadcast over the state dim).

Zamba2 simplifications (recorded in DESIGN.md): the shared block consumes
LN(x) directly (no concat-with-embedding projector, no per-application
LoRA); remainder layers after the last full group are plain Mamba2.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tapir
from repro.dist import shard_act
from repro.kernels.linear_scan import ops as ls_ops

from . import layers as L
from .base import BaseModel, ModelConfig, ParamSpec, register_family
from .transformer import DenseLM, _block_specs

CONV_K = 4


def _ssd_gates(xBC, dt, dt_bias, A_log, din, N, H, dtype):
    """SSD gate prep (dt softplus, decay, B/C broadcast to heads) — one
    liftable composite so the whole Mamba block stays a single region."""
    B_, S = dt.shape[0], dt.shape[1]
    Bm = xBC[..., din:din + N]
    Cm = xBC[..., din + N:]
    dtv = jax.nn.softplus(dt.astype(jnp.float32) +
                          dt_bias.astype(jnp.float32))          # [B,S,H]
    a = jnp.exp(-jnp.exp(jnp.clip(A_log.astype(jnp.float32),
                                  -6.0, 4.0)) * dtv)            # [B,S,H]
    w = jnp.broadcast_to(a[..., None], (B_, S, H, N))
    q = jnp.broadcast_to(Cm[:, :, None], (B_, S, H, N)).astype(dtype)
    k = (jnp.broadcast_to(Bm[:, :, None], (B_, S, H, N))
         * dtv[..., None]).astype(dtype)
    return q, k, w


def _ssm_step(q, k, xc, w, state):
    """Stateful SSD step (decode): chunked scan carrying the [B,H,N,hd]
    SSM state — the same stateful-capture problem as a KV-cache write."""
    return ls_ops.linear_scan_chunked(q, k, xc, w, chunk=64,
                                      init_state=state, return_state=True)


def ssd_in_proj(cfg: ModelConfig, p, x):
    """Mamba2 in-projection, split: ``(z, xBC, dt)``."""
    din, H, hd, N = _mamba_dims(cfg)
    zxbcdt = tapir.linear(x, p["w_in"])
    return (zxbcdt[..., :din], zxbcdt[..., din:2 * din + 2 * N],
            zxbcdt[..., 2 * din + 2 * N:])


def ssd_out_proj(cfg: ModelConfig, p, y, xc, z, dtype, eps: float = 1e-6):
    """The ``D`` skip, the gated RMSNorm (``norm(y * silu(z))``) and the
    out-projection.  y, xc: [B, S, H, hd]."""
    B, S = y.shape[0], y.shape[1]
    din = _mamba_dims(cfg)[0]
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * \
        xc.astype(jnp.float32)
    y = y.reshape(B, S, din).astype(dtype)
    y = L.rmsnorm(y * tapir.elemwise(z, "silu"), p["norm"], eps)
    return tapir.linear(y, p["w_out"])


def _mamba_dims(cfg: ModelConfig):
    din = cfg.ssm_expand * cfg.d_model
    hd = cfg.ssm_head_dim
    H = din // hd
    N = cfg.ssm_state
    return din, H, hd, N


def _mamba_block_specs(cfg: ModelConfig, n_layers: int) -> dict:
    d = cfg.d_model
    din, H, hd, N = _mamba_dims(cfg)
    pdt = cfg.param_dtype
    Lx = (n_layers,)
    width = 2 * din + 2 * N + H          # z, xc, B, C, dt
    return {
        "ln": ParamSpec(Lx + (d,), pdt, ("layers", "embed"), "ones"),
        "w_in": ParamSpec(Lx + (d, width), pdt, ("layers", "embed", "heads")),
        "conv_w": ParamSpec(Lx + (CONV_K, din + 2 * N), pdt,
                            ("layers", "conv", None), "small"),
        "A_log": ParamSpec(Lx + (H,), pdt, ("layers", "heads"), "zeros"),
        "D": ParamSpec(Lx + (H,), pdt, ("layers", "heads"), "ones"),
        "dt_bias": ParamSpec(Lx + (H,), pdt, ("layers", "heads"), "zeros"),
        "norm": ParamSpec(Lx + (din,), pdt, ("layers", "mlp"), "ones"),
        "w_out": ParamSpec(Lx + (din, d), pdt, ("layers", "heads", "embed")),
    }


@register_family("hybrid")
class Zamba2(BaseModel):
    """n_layers Mamba2 blocks; a single shared attention+MLP transformer
    block (one weight set) applied after every ``shared_attn_every`` Mamba
    layers.  ``shared_attn_every == 0`` makes this a pure Mamba2 LM."""

    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg)
        self._attn_helper = DenseLM(cfg)   # reuse attention machinery

    @property
    def n_groups(self) -> int:
        if self.cfg.shared_attn_every <= 0:
            return 0
        return self.cfg.n_layers // self.cfg.shared_attn_every

    def abstract_params(self) -> dict:
        cfg = self.cfg
        pdt = cfg.param_dtype
        p = {
            "embed": ParamSpec((cfg.vocab, cfg.d_model), pdt,
                               ("vocab", "embed")),
            "blocks": _mamba_block_specs(cfg, cfg.n_layers),
            "ln_f": ParamSpec((cfg.d_model,), pdt, ("embed",), "ones"),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab), pdt,
                                     ("embed", "vocab"))
        if self.n_groups > 0:
            shared = _block_specs(cfg, 1)
            p["shared"] = jax.tree_util.tree_map(
                lambda s: ParamSpec(s.shape[1:], s.dtype, s.axes[1:], s.init),
                shared, is_leaf=lambda x: isinstance(x, ParamSpec))
        return p

    # -- mamba2 block -----------------------------------------------------
    def _ssd(self, p, x, conv_state=None, ssm_state=None):
        cfg = self.cfg
        B, S, d = x.shape
        din, H, hd, N = _mamba_dims(cfg)
        z, xBC, dt = ssd_in_proj(cfg, p, x)
        xBC, new_conv = L.causal_conv1d(xBC, p["conv_w"], conv_state)
        xBC = tapir.elemwise(xBC, "silu")
        xc = xBC[..., :din].reshape(B, S, H, hd)
        dtype = str(jnp.dtype(x.dtype))
        if tapir.is_traced(xBC):
            q, k, w = tapir.lift(_ssd_gates, xBC, dt, p["dt_bias"],
                                 p["A_log"], din=din, N=N, H=H, dtype=dtype)
        else:
            q, k, w = _ssd_gates(xBC, dt, p["dt_bias"], p["A_log"],
                                 din=din, N=N, H=H, dtype=dtype)
        if ssm_state is None:
            y = tapir.wkv_scan(q, k, xc, w)
            new_ssm = None
        elif tapir.is_traced(xBC) or tapir.is_traced(ssm_state):
            y, new_ssm = tapir.lift(_ssm_step, q, k, xc, w, ssm_state)
        else:
            y, new_ssm = ls_ops.linear_scan_chunked(
                q, k, xc, w, chunk=64, init_state=ssm_state,
                return_state=True)
        return ssd_out_proj(cfg, p, y, xc, z, x.dtype), new_conv, new_ssm

    def _mamba_block_body(self, p, x):
        y, _, _ = self._ssd(p, L.rmsnorm(x, p["ln"]))
        return x + y

    def _mamba_step_body(self, p, x, conv, ssm):
        """One Mamba2 block threading (conv, ssm) state — stateful region."""
        y, conv, ssm = self._ssd(p, L.rmsnorm(x, p["ln"]),
                                 conv_state=conv, ssm_state=ssm)
        return x + y, conv, ssm

    def _mamba_body(self, cdt):
        # whole-region capture: in-proj, causal conv, SSD gates, the scan,
        # gated rmsnorm and out-proj trace into ONE TaskGraph per block
        blk = tapir.parallel_region(self._mamba_block_body,
                                    name="mamba_block")

        def body(p, x):
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            return shard_act(blk(p, x), "batch", "seq", None)
        return body

    def _shared_block(self, params, x, cos, sin, cdt, kv_cache=None):
        hp = self._attn_helper
        p = jax.tree_util.tree_map(lambda a: a.astype(cdt), params["shared"])
        if kv_cache is None:
            # forward: reuse the dense helper's region-wrapped block
            return hp._block(p, x, cos, sin), None
        ck, cv, pos0, is_prefill = kv_cache
        blk = tapir.parallel_region(hp._cached_block_body,
                                    name="zamba_shared_cached_block")
        x, ck, cv = blk(p, x, cos, sin, ck, cv, pos0, is_prefill)
        return shard_act(x, "batch", "seq", None), (ck, cv)

    # -- forward ----------------------------------------------------------
    def _stack(self, params, h, positions, cdt):
        cfg = self.cfg
        cos, sin = L.rope_table(positions, cfg.hd)
        body = self._mamba_body(cdt)
        per, G = cfg.shared_attn_every, self.n_groups
        blocks = params["blocks"]
        if G == 0:
            return tapir.scan_layers(body, blocks, h)
        for g in range(G):
            grp = jax.tree_util.tree_map(
                lambda a: a[g * per:(g + 1) * per], blocks)
            h = tapir.scan_layers(body, grp, h)
            h, _ = self._shared_block(params, h, cos, sin, cdt)
        rem = cfg.n_layers - G * per
        if rem:
            tail = jax.tree_util.tree_map(lambda a: a[G * per:], blocks)
            h = tapir.scan_layers(body, tail, h)
        return h

    def forward(self, params, batch: dict):
        cfg = self.cfg
        tokens = batch["tokens"]
        cdt = jnp.dtype(cfg.compute_dtype)
        h = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        h = self._stack(params, h, jnp.arange(tokens.shape[1]), cdt)
        h = L.rmsnorm(h, params["ln_f"])
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = tapir.linear(h, w.astype(h.dtype))
        return shard_act(logits, "batch", None, "vocab")

    # -- slot-paged serving layout (ROADMAP item 2 groundwork) -------------
    def slot_param_axes(self) -> dict:
        """Logical axes for the slot-serving param layout: one ``mamba``
        entry per SSD layer, with the shared attention+MLP block appearing
        as a ``shared_attn`` entry after each group (same single weight
        set each time — stable array ids, like the stacked-slice hoisting
        in the dense path).  Contraction-dim weights (``w_out``, and the
        shared block's ``wo``/``wd``) keep a non-model last axis and stay
        REPLICATED per the bitwise-serving carried constraint."""
        cfg = self.cfg
        mamba = {k: tuple(s.axes[1:])
                 for k, s in _mamba_block_specs(cfg, cfg.n_layers).items()}
        shared = {k: tuple(s.axes[1:])
                  for k, s in _block_specs(cfg, 1).items()}
        per, G = cfg.shared_attn_every, self.n_groups
        layers = []
        for i in range(cfg.n_layers):
            layers.append(("mamba", dict(mamba)))
            if G and (i + 1) % per == 0 and (i + 1) // per <= G:
                layers.append(("shared_attn", dict(shared)))
        return {"layers": layers,
                "head": {"ln_f": ("embed",), "w": ("embed", "vocab")},
                "embed": ("vocab", "embed")}

    # -- serving ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        din, H, hd, N = _mamba_dims(cfg)
        cdt = jnp.dtype(cfg.compute_dtype)
        Ln = cfg.n_layers
        c = {
            "conv": jnp.zeros((Ln, batch, CONV_K - 1, din + 2 * N), cdt),
            "ssm": jnp.zeros((Ln, batch, H, N, hd), jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
        if self.n_groups > 0:
            c["shared_k"] = jnp.zeros((self.n_groups, batch, max_len,
                                       cfg.n_kv_heads, cfg.hd), cdt)
            c["shared_v"] = jnp.zeros_like(c["shared_k"])
        return c

    def cache_specs(self, batch: int, max_len: int) -> dict:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def cache_axes(self) -> dict:
        c = {"conv": ("layers", "batch", None, None),
             "ssm": ("layers", "batch", "heads", None, None),
             "pos": ()}
        if self.n_groups > 0:
            c["shared_k"] = ("layers", "batch", "kvseq", "kv", None)
            c["shared_v"] = ("layers", "batch", "kvseq", "kv", None)
        return c

    def _run_with_cache(self, params, tokens, cache, is_prefill: bool):
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        h = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        pos0 = cache["pos"]
        positions = pos0 + jnp.arange(tokens.shape[1])
        cos, sin = L.rope_table(positions, cfg.hd)

        blk = tapir.parallel_region(self._mamba_step_body,
                                    name="mamba_stateful_block")

        def body(x, xs):
            p, conv, ssm = xs
            p = jax.tree_util.tree_map(lambda a: a.astype(cdt), p)
            x, conv, ssm = blk(p, x, conv, ssm)
            return x, (conv, ssm)

        per, G = cfg.shared_attn_every, self.n_groups
        blocks = params["blocks"]
        convs, ssms, sks, svs = [], [], [], []

        def run_group(h, lo, hi):
            grp = jax.tree_util.tree_map(lambda a: a[lo:hi], blocks)
            cg = (grp, cache["conv"][lo:hi], cache["ssm"][lo:hi])
            h, (conv, ssm) = jax.lax.scan(body, h, cg)
            convs.append(conv)
            ssms.append(ssm)
            return h

        if G == 0:
            h = run_group(h, 0, cfg.n_layers)
        else:
            for g in range(G):
                h = run_group(h, g * per, (g + 1) * per)
                kv = (cache["shared_k"][g], cache["shared_v"][g], pos0,
                      is_prefill)
                h, (sk, sv) = self._shared_block(params, h, cos, sin, cdt,
                                                 kv_cache=kv)
                sks.append(sk)
                svs.append(sv)
            if cfg.n_layers - G * per:
                h = run_group(h, G * per, cfg.n_layers)

        new_cache = {"conv": jnp.concatenate(convs, 0),
                     "ssm": jnp.concatenate(ssms, 0),
                     "pos": pos0 + tokens.shape[1]}
        if G > 0:
            new_cache["shared_k"] = jnp.stack(sks, 0)
            new_cache["shared_v"] = jnp.stack(svs, 0)
        h = L.rmsnorm(h[:, -1:], params["ln_f"])
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = tapir.linear(h, w.astype(h.dtype))
        return logits[:, -1], new_cache

    def prefill(self, params, tokens, cache):
        return self._run_with_cache(params, tokens, cache, is_prefill=True)

    def decode_step(self, params, tokens, cache):
        return self._run_with_cache(params, tokens, cache, is_prefill=False)

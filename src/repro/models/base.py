"""Model base: config, abstract parameters (shape+logical-axes, no
allocation), and the train/serve entry points every family implements.

Logical axes (bound to mesh axes by ``repro.dist.sharding``):
  "vocab"  — embedding rows / lm-head cols        -> model axis
  "embed"  — d_model                              -> unsharded (or fsdp)
  "heads"  — attention head count                 -> model axis
  "kv"     — kv head count                        -> model axis
  "mlp"    — FFN hidden                           -> model axis
  "expert" — MoE expert count                     -> model axis
  "layers" — stacked layer dim (scan)             -> unsharded
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _ce_loss(logits, labels, mask):
    """Masked mean CE — module-level so its identity is stable in region
    graph signatures (it lowers as one ``pyfunc`` node under capture)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _ce_loss_unmasked(logits, labels):
    # the all-ones mask is built INSIDE the lifted fn (under the jit), so
    # a region capture needs no concrete mask input — bitwise-identical
    # to the masked form with ones
    return _ce_loss(logits, labels, jnp.ones(labels.shape, jnp.float32))


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str = "dense"          # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope: str = "full"             # "full" | "half" (chatglm 2d rope)
    norm: str = "rmsnorm"          # "rmsnorm" | "layernorm"
    act: str = "silu"
    gated_mlp: bool = True
    tie_embeddings: bool = False
    max_seq: int = 8192
    # --- moe ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0
    #: ``(lo, hi)``: this chip holds experts ``lo .. hi-1`` of the
    #: ``n_experts`` the router scores (expert parallelism's share); None
    #: holds them all
    expert_range: Optional[tuple] = None
    shared_ff: int = 0             # shared SwiGLU expert width (0: none)
    # --- ssm / hybrid ---
    ssm_state: int = 64
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    shared_attn_every: int = 0     # zamba2: shared block period
    #: per-layer mixer, "mamba" | "attention" (granite hybrid); () = n/a
    layer_types: tuple = ()
    # --- residual / logit scaling (granite) ---
    norm_eps: float = 1e-6
    attn_scale: Optional[float] = None   # scores scale; None: 1/sqrt(hd)
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0             # logits are divided by this
    # --- enc-dec / vlm ---
    n_enc_layers: int = 0
    n_frames: int = 1500           # whisper stub frontend length
    n_img_tokens: int = 256        # vlm stub frontend length
    # --- numerics ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def n_params(self) -> float:
        """Approximate parameter count (for 6ND roofline accounting)."""
        d, L, ff, V = self.d_model, self.n_layers, self.d_ff, self.vocab
        hd = self.hd
        attn = d * hd * self.n_heads + 2 * d * hd * self.n_kv_heads \
            + hd * self.n_heads * d
        mlp = (3 if self.gated_mlp else 2) * d * ff
        if self.family == "moe":
            mlp_total = mlp * self.n_experts
        else:
            mlp_total = mlp
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            din = self.ssm_expand * d
            blk = d * (2 * din + 2 * self.n_heads * self.ssm_state) + din * d \
                + 2 * d * ff
            return L * blk + emb
        body = L * (attn + mlp_total)
        if self.family == "encdec":
            body += self.n_enc_layers * (attn + mlp) + L * (attn)  # cross attn
        if self.family == "hybrid":
            din = self.ssm_expand * d
            mamba = d * (2 * din + 2 * self.n_heads * self.ssm_state) + din * d
            n_shared = max(1, L // max(self.shared_attn_every, 1))
            body = L * mamba + (attn + mlp)  # one shared block
        return body + emb

    def n_active_params(self) -> float:
        if self.family != "moe":
            return self.n_params()
        dense_like = dataclasses.replace(
            self, family="dense",
            d_ff=self.d_ff * max(self.top_k, 1))
        return dense_like.n_params()


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dtype: str
    axes: tuple[Optional[str], ...]     # logical axis names per dim
    init: str = "normal"                # normal|zeros|ones|small
    scale: float = 1.0

    def sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def materialize(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.shape[0] if spec.shape else 1
    std = spec.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape) * std).astype(spec.dtype)


class BaseModel:
    """Family-independent plumbing; families implement ``_abstract_params``
    and ``forward`` (and the serve hooks if decodable)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- params ---------------------------------------------------------
    def abstract_params(self) -> dict:
        raise NotImplementedError

    def init_params(self, key) -> dict:
        specs = self.abstract_params()
        leaves, treedef = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        keys = jax.random.split(key, len(leaves))
        vals = [materialize(s, k) for s, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    def param_sds(self) -> dict:
        return jax.tree_util.tree_map(
            lambda s: s.sds(), self.abstract_params(),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    def param_axes(self) -> dict:
        return jax.tree_util.tree_map(
            lambda s: s.axes, self.abstract_params(),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    # -- compute ----------------------------------------------------------
    def forward(self, params, batch: dict) -> jax.Array:
        """Returns logits [B, S, vocab]."""
        raise NotImplementedError

    def loss(self, params, batch: dict) -> jax.Array:
        from repro.core import tapir
        logits = self.forward(params, batch)
        labels = batch["labels"]
        mask = batch.get("mask")
        # dispatched through ``lift`` so a region capture keeps the CE in
        # the same graph (one pyfunc node) instead of flushing; outside a
        # region ``lift`` is a direct call — identical trace either way
        if mask is None:
            return tapir.lift(_ce_loss_unmasked, logits, labels)
        return tapir.lift(_ce_loss, logits, labels, mask)

    def capture_aux(self, batch: dict) -> tuple:
        """Concrete auxiliary leaves the forward binds under region capture
        (identity-stable memoized tables).  The captured training step
        passes them as argument leaves so program replay can rebind every
        region input; families with none return ()."""
        return ()

    # -- serving ----------------------------------------------------------
    def supports_slots(self) -> bool:
        """True when the family implements the slot-paged serving API
        (``init_slot_cache`` / ``prefill_into_slot`` / ``decode_step_slots``)
        — the continuous-batching path of ``ServingEngine``."""
        return False

    def slot_state_rows(self) -> dict:
        """Recurrent state the slot cache keeps per slot beside its KV
        pages, ``{name: shape of one layer's row}``; empty for families
        whose only per-request state is KV.  A model with state rows
        shares no prefix pages (a prefix's KV without its state would be
        wrong)."""
        return {}

    def slot_param_axes(self) -> dict:
        """Logical sharding axes mirroring ``slot_params``' structure
        leaf-for-leaf (per-layer entries carry the stacked block axes with
        the leading "layers" axis dropped).  Used by ``ServingEngine`` to
        ``device_put``-pin the TP layout once instead of letting GSPMD
        re-shard per program."""
        raise NotImplementedError(
            f"{self.cfg.family} has no slot-paged serving path")

    def cache_len(self, seq_len: int, kind: str) -> int:
        """KV-cache capacity needed to serve ``seq_len`` tokens (vlm adds
        its image-token prefix)."""
        return seq_len

    def init_cache(self, batch: int, max_len: int) -> dict:
        raise NotImplementedError(f"{self.cfg.family} has no decode path")

    def prefill(self, params, tokens, cache) -> tuple[jax.Array, dict]:
        raise NotImplementedError

    def decode_step(self, params, tokens, cache) -> tuple[jax.Array, dict]:
        """tokens: [B, 1] new token; returns (logits [B, vocab], cache)."""
        raise NotImplementedError

    # -- dry-run input specs ----------------------------------------------
    def input_specs(self, seq_len: int, batch: int, kind: str) -> dict:
        """ShapeDtypeStruct stand-ins for every model input.  ``kind``:
        train | prefill | decode."""
        tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
        if kind == "train":
            return {"tokens": tok,
                    "labels": jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)}
        if kind == "prefill":
            return {"tokens": tok}
        if kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
        raise ValueError(kind)


_REGISTRY: dict[str, Callable[[ModelConfig], "BaseModel"]] = {}


def register_family(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def get_model(cfg: ModelConfig) -> BaseModel:
    from . import (granite_hybrid, mamba, moe, paper_nets, rwkv,  # noqa
                   transformer, vlm, whisper)
    return _REGISTRY[cfg.family](cfg)

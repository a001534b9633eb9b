"""Plain float32 Qwen2 (``Qwen2ForCausalLM``) in ``jax.numpy``.

Written from the published model description (Hugging Face
``modeling_qwen2``), independent of the program under test:

* pre-norm decoder blocks: RMSNorm -> GQA self-attention with q/k/v biases
  and no o-proj bias -> residual; RMSNorm -> SwiGLU MLP
  ``down(silu(gate(x)) * up(x))`` -> residual; final RMSNorm; LM head;
* rotary embedding on q and k in the half-split ("rotate_half") layout,
  ``inv_freq = theta ** -(2i / head_dim)``, at the configuration's
  ``rope_theta``;
* causal softmax attention scaled by ``head_dim ** -0.5``, each KV head
  shared by ``num_attention_heads / num_key_value_heads`` query heads;
* ``tie_word_embeddings``: the LM head is the embedding matrix.

Departures, all of representation: matrices are stored ``[in, out]``
(the transpose of ``nn.Linear.weight``); weights are random, drawn from
the seed by ``param_recipe`` + ``bench.harness.weights``.

Every product goes through ``einsum(spec, a, b, mode)``: mode ``"f32"``
is float32 at ``Precision.HIGHEST`` (a TPU otherwise rounds float32
operands to bfloat16).  Mode ``"fp8"`` is the control, one precision step
below the bfloat16 the configurations state: operands rounded to
float8_e4m3fn and, in the backward pass, the incoming gradient to
float8_e5m2, each tensor under its own scale, with float32 sums.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: published parameter tensors of one decoder layer, in a fixed order (the
#: order is part of how ``bench.harness.weights`` derives each key)
LAYER_KEYS = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "ln2", "wg", "wu", "wd")
GLOBAL_KEYS = ("embed", "ln_f")


def dims(conf: dict) -> dict:
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    return {"d": d, "L": int(conf["num_hidden_layers"]), "H": H,
            "Hkv": int(conf["num_key_value_heads"]),
            "hd": int(conf.get("head_dim") or d // H),
            "ff": int(conf["intermediate_size"]),
            "V": int(conf["vocab_size"]),
            "eps": float(conf["rms_norm_eps"]),
            "theta": float(conf["rope_theta"])}


def param_recipe(conf: dict, init: dict) -> dict:
    """``{name: (shape, kind, std)}`` for the globals and for one layer
    (``kind``: "normal" = N(0, std); "norm" = 1 + N(0, std)).  ``init``
    sets the standard deviations: ``std`` for every matrix and the
    embedding, ``std / sqrt(2 L)`` for the two residual outputs (o-proj,
    down-proj) when ``residual_scaled``, ``bias_std`` and ``norm_std``."""
    D = dims(conf)
    d, H, Hkv, hd, ff, V = D["d"], D["H"], D["Hkv"], D["hd"], D["ff"], D["V"]
    std = float(init["std"])
    res = std / math.sqrt(2 * D["L"]) if init.get("residual_scaled") else std
    b, n = float(init["bias_std"]), float(init["norm_std"])
    layer = {"ln1": ((d,), "norm", n),
             "wq": ((d, H * hd), "normal", std),
             "bq": ((H * hd,), "normal", b),
             "wk": ((d, Hkv * hd), "normal", std),
             "bk": ((Hkv * hd,), "normal", b),
             "wv": ((d, Hkv * hd), "normal", std),
             "bv": ((Hkv * hd,), "normal", b),
             "wo": ((H * hd, d), "normal", res),
             "ln2": ((d,), "norm", n),
             "wg": ((d, ff), "normal", std),
             "wu": ((d, ff), "normal", std),
             "wd": ((ff, d), "normal", res)}
    glob = {"embed": ((V, d), "normal", std), "ln_f": ((d,), "norm", n)}
    return {"layer": layer, "global": glob}


# -- arithmetic ------------------------------------------------------------

def _fp8(x, dtype):
    """``x`` rounded to an fp8 ``dtype`` under one scale that maps its
    largest magnitude to the format's largest finite value; float32."""
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_einsum(spec, a, b):
    """An fp8 product as fp8 training runs it: operands in float8_e4m3fn,
    the incoming gradient in float8_e5m2, each tensor under its own scale,
    sums in float32."""
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                      _fp8(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    qg = _fp8(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(qg)


fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, mode: str = "f32"):
    """Every product of the model: float32 at ``Precision.HIGHEST``, or
    the ``"fp8"`` control."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if mode == "fp8":
        return fp8_einsum(spec, a, b)
    raise ValueError(f"unknown precision mode {mode!r}")


def matmul(a, b, mode: str = "f32"):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    return einsum("...i,ij->...j", a, b, mode)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_tables(T: int, hd: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], axis=-1)          # [T, hd]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def apply_rope(x, cos, sin):
    """x: [T, heads, hd]; cos/sin: [T, hd]."""
    return x * cos[:, None] + _rotate_half(x) * sin[:, None]


def _attend(q, k, v, mode):
    """One sequence, causal.  q: [T, H, hd]; k/v: [T, Hkv, hd]."""
    T, H, hd = q.shape
    grp = H // k.shape[1]
    k = jnp.repeat(k, grp, axis=1)
    v = jnp.repeat(v, grp, axis=1)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))   # [H, T, hd]
    s = einsum("hqd,hkd->hqk", qh, kh, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("hqk,hkd->hqd", p, vh, mode)
    return jnp.swapaxes(o, 0, 1).reshape(T, H * hd)


def layer(conf: dict, w: dict, x, mode: str = "f32"):
    """One decoder layer over ``x`` [N, T, d] (each row its own sequence
    from position 0; rows are independent).  Attention runs one sequence
    at a time so its [H, T, T] scores stay small."""
    D = dims(conf)
    H, Hkv, hd, eps = D["H"], D["Hkv"], D["hd"], D["eps"]
    N, T, d = x.shape
    cos, sin = rope_tables(T, hd, D["theta"])

    def one(xs):
        h = rms_norm(xs, w["ln1"], eps)
        q = (matmul(h, w["wq"], mode) + w["bq"]).reshape(T, H, hd)
        k = (matmul(h, w["wk"], mode) + w["bk"]).reshape(T, Hkv, hd)
        v = (matmul(h, w["wv"], mode) + w["bv"]).reshape(T, Hkv, hd)
        o = _attend(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, mode)
        xs = xs + matmul(o, w["wo"], mode)
        h = rms_norm(xs, w["ln2"], eps)
        g = jax.nn.silu(matmul(h, w["wg"], mode))
        return xs + matmul(g * matmul(h, w["wu"], mode), w["wd"], mode)

    return jax.lax.map(one, x)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def logits(conf: dict, ln_f, head, x, mode: str = "f32"):
    """Final norm + LM head: x [..., d] -> [..., V]; ``head`` is [d, V]
    (the transposed embedding when tied)."""
    return matmul(rms_norm(x, ln_f, float(conf["rms_norm_eps"])), head, mode)


def forward(conf: dict, params: dict, tokens, mode: str = "f32"):
    """Whole-model logits [N, T, V] for small sizes (tests); the chip
    check runs ``layer`` one layer at a time instead."""
    x = embed(params["embed"], tokens)
    for w in params["layers"]:
        x = layer(conf, w, x, mode)
    head = params.get("head", params["embed"].T)
    return logits(conf, params["ln_f"], head, x, mode)


# -- training --------------------------------------------------------------

def loss(conf: dict, params: dict, tokens, labels, mode: str = "f32"):
    """Mean next-token cross-entropy over every position.  Each layer is
    rematerialized in the backward pass, which changes memory, not
    arithmetic."""
    x = embed(params["embed"], tokens)
    step = jax.checkpoint(lambda w, x: layer(conf, w, x, mode))
    for w in params["layers"]:
        x = step(w, x)
    head = params.get("head", params["embed"].T)
    z = logits(conf, params["ln_f"], head, x, mode)
    lse = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay to
    ``min_lr_frac * lr`` at ``total_steps`` (1-based step)."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def adamw_step(opt: dict, params, grads, m, v, step: int):
    """Global-norm clipping, then AdamW (Loshchilov & Hutter) with bias
    correction and decoupled weight decay on matrices only.  Returns
    ``(params, m, v, clipped_grads)``."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-30))
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = lr_at(opt, step)
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)

    def upd(p, mi, vi):
        u = (mi / (1 - b1 ** step)) / (jnp.sqrt(vi / (1 - b2 ** step)) + eps)
        if p.ndim >= 2:
            u = u + wd * p
        return p - lr * u

    return jax.tree_util.tree_map(upd, params, m, v), m, v, grads

"""Plain float32 Granite 4.0 hybrid (``GraniteMoeHybridForCausalLM``) in
``jax.numpy``.

Written from the published model description (Hugging Face
``modeling_granitemoehybrid``), independent of the program under test:

* ``inputs_embeds = embed(ids) * embedding_multiplier``;
* each layer, by ``layer_types``: RMSNorm -> mixer -> ``residual + out *
  residual_multiplier``; RMSNorm -> (routed experts + shared expert) ->
  ``residual + out * residual_multiplier``; final RMSNorm; the tied LM
  head; ``logits / logits_scaling``;
* Mamba2 mixer (one group): ``in_proj`` -> (gate z, xBC, dt); a depthwise
  causal conv of width ``mamba_d_conv`` with bias over xBC, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t``, run as a sequential scan over positions;
  gated RMSNorm ``norm(y * silu(z))``; ``out_proj``;
* attention: GQA, no position embedding (``position_embedding_type``
  "nope"), scores scaled by ``attention_multiplier``, causal softmax;
* MoE: router logits over all ``router_experts``, softmax over the top
  ``num_experts_per_tok`` (equal to the softmax over all, renormalised over
  the chosen), experts ``down(silu(gate(x)) * up(x))``; the shared expert
  the same form, added.

The chip's share (configuration ``experts_held``): only the routes to the
held experts add their part, as on the chip; what the absent experts
would add is left out on both sides.

Departures, all of representation: matrices are stored ``[in, out]``, the
conv as ``[width, channels]``, each expert's input projection as separate
gate and up matrices; weights are random, drawn from the seed by
``param_recipe``.  The embedding multiplier rides in layer 0's weights
(``in_scale``), since the harness's per-layer loop embeds without the
configuration.  Each layer's weights name their kind by their keys
(``A_log``: Mamba2; ``wq``: attention).  Products go through
``qwen2.einsum`` (float32 at ``Precision.HIGHEST``, or the ``fp8``
control); the scan is float32 throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.qwen2 import einsum, matmul, rms_norm

FFN_KEYS = ("ln2", "router", "ewg", "ewu", "ewd", "swg", "swu", "swd")
#: published parameter tensors of one layer of each kind, in a fixed order
#: (the order is part of how the weights are derived)
MAMBA_KEYS = ("ln", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "norm", "w_out") + FFN_KEYS
ATTENTION_KEYS = ("ln1", "wq", "wk", "wv", "wo") + FFN_KEYS
GLOBAL_KEYS = ("embed", "ln_f")


def dims(conf: dict) -> dict:
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    lo, hi = conf["experts_held"]
    return {"d": d, "L": int(conf["num_hidden_layers"]), "H": H,
            "Hkv": int(conf["num_key_value_heads"]),
            "hd": int(conf.get("head_dim") or d // H),
            "ff": int(conf["intermediate_size"]),
            "sff": int(conf["shared_intermediate_size"]),
            "V": int(conf["vocab_size"]),
            "eps": float(conf["rms_norm_eps"]),
            "E": int(conf["router_experts"]), "held": (int(lo), int(hi)),
            "K": int(conf["num_experts_per_tok"]),
            "mH": int(conf["mamba_n_heads"]), "mP": int(conf["mamba_d_head"]),
            "N": int(conf["mamba_d_state"]), "conv": int(conf["mamba_d_conv"]),
            "din": int(conf["mamba_expand"]) * d,
            "types": tuple(conf["layer_types"])}


def param_recipe(conf: dict, init: dict) -> dict:
    """``{"mamba" | "attention" | "global": {name: (shape, kind, std)}}``
    (``kind``: "normal" = N(0, std); "norm" = 1 + N(0, std)).  ``init``:
    ``std`` for matrices, ``res_std`` for the residual outputs (out-proj,
    o-proj, expert and shared down-proj), ``embed_std``, ``conv_std``,
    ``bias_std``, ``norm_std``, ``a_log_std`` (A_log = 1 + N(0, a_log_std)) and
    ``dt_bias_std`` (shifted by ``dt_bias_mean``, see ``dt_bias_mean``)."""
    D = dims(conf)
    d, H, Hkv, hd = D["d"], D["H"], D["Hkv"], D["hd"]
    din, N, mH = D["din"], D["N"], D["mH"]
    Eh = D["held"][1] - D["held"][0]
    std, res = float(init["std"]), float(init["res_std"])
    n = float(init["norm_std"])
    ffn = {"ln2": ((d,), "norm", n),
           "router": ((d, D["E"]), "normal", std),
           "ewg": ((Eh, d, D["ff"]), "normal", std),
           "ewu": ((Eh, d, D["ff"]), "normal", std),
           "ewd": ((Eh, D["ff"], d), "normal", res),
           "swg": ((d, D["sff"]), "normal", std),
           "swu": ((d, D["sff"]), "normal", std),
           "swd": ((D["sff"], d), "normal", res)}
    mamba = {"ln": ((d,), "norm", n),
             "w_in": ((d, 2 * din + 2 * N + mH), "normal", std),
             "conv_w": ((D["conv"], din + 2 * N), "normal",
                        float(init["conv_std"])),
             "conv_b": ((din + 2 * N,), "normal", float(init["bias_std"])),
             "dt_bias": ((mH,), "normal", float(init["dt_bias_std"])),
             "A_log": ((mH,), "norm", float(init["a_log_std"])),
             "D": ((mH,), "norm", n),
             "norm": ((din,), "norm", n),
             "w_out": ((din, d), "normal", res), **ffn}
    attention = {"ln1": ((d,), "norm", n),
                 "wq": ((d, H * hd), "normal", std),
                 "wk": ((d, Hkv * hd), "normal", std),
                 "wv": ((d, Hkv * hd), "normal", std),
                 "wo": ((H * hd, d), "normal", res), **ffn}
    glob = {"embed": ((D["V"], d), "normal", float(init["embed_std"])),
            "ln_f": ((d,), "norm", n)}
    return {"mamba": mamba, "attention": attention, "global": glob}


def dt_bias_mean(init: dict) -> float:
    """The shift added to the drawn ``dt_bias``: softplus of it is the
    step size a Mamba2 layer starts from."""
    return float(init["dt_bias_mean"])


# -- mixers ------------------------------------------------------------------

def _ssd_scan(x, dt, A, B, C):
    """x: [T, H, P]; dt: [T, H]; A: [H]; B, C: [T, N].  Sequential scan
    from zero state; returns y [T, H, P] (without the D skip)."""
    T, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * A)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, ct,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C))
    return y


def _mamba(conf, w, xs, mode):
    D = dims(conf)
    T = xs.shape[0]
    din, N, mH, K = D["din"], D["N"], D["mH"], D["conv"]
    h = rms_norm(xs, w["ln"], D["eps"])
    zxbcdt = matmul(h, w["w_in"], mode)
    z = zxbcdt[:, :din]
    xbc = zxbcdt[:, din:2 * din + 2 * N]
    dt = zxbcdt[:, 2 * din + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = sum(xp[i:i + T] * w["conv_w"][i] for i in range(K)) + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :din].reshape(T, mH, D["mP"])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = _ssd_scan(x, dt, -jnp.exp(w["A_log"]), xbc[:, din:din + N],
                  xbc[:, din + N:])
    y = (y + w["D"][:, None] * x).reshape(T, din)
    y = rms_norm(y * jax.nn.silu(z), w["norm"], D["eps"])
    return matmul(y, w["w_out"], mode)


def _attention(conf, w, xs, mode):
    D = dims(conf)
    T = xs.shape[0]
    H, Hkv, hd = D["H"], D["Hkv"], D["hd"]
    h = rms_norm(xs, w["ln1"], D["eps"])
    q = matmul(h, w["wq"], mode).reshape(T, H, hd)
    k = jnp.repeat(matmul(h, w["wk"], mode).reshape(T, Hkv, hd), H // Hkv,
                   axis=1)
    v = jnp.repeat(matmul(h, w["wv"], mode).reshape(T, Hkv, hd), H // Hkv,
                   axis=1)
    s = einsum("qhd,khd->hqk", q, k, mode) * float(
        conf["attention_multiplier"])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, mode)
    return matmul(o.reshape(T, H * hd), w["wo"], mode)


def _swiglu(h, wg, wu, wd, mode):
    return matmul(jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode),
                  wd, mode)


def ffn(conf, w, xs, mode="f32"):
    """The routed experts held here plus the shared expert, over one
    sequence's rows [T, d]."""
    D = dims(conf)
    lo, hi = D["held"]
    h = rms_norm(xs, w["ln2"], D["eps"])
    logits = matmul(h, w["router"], mode)                     # [T, E]
    top, idx = jax.lax.top_k(logits, D["K"])
    gate = jax.nn.softmax(top, axis=-1)                       # [T, K]
    # the weight each held expert gets per row (0 where not chosen)
    onehot = jax.nn.one_hot(idx - lo, hi - lo, dtype=jnp.float32)
    weight = jnp.einsum("tk,tke->te", gate, onehot)           # [T, Eh]
    g = jax.nn.silu(einsum("td,edf->tef", h, w["ewg"], mode))
    u = einsum("td,edf->tef", h, w["ewu"], mode)
    y = einsum("tef,efd->ted", g * u, w["ewd"], mode)
    routed = jnp.einsum("te,ted->td", weight, y,
                        precision=jax.lax.Precision.HIGHEST)
    return routed + _swiglu(h, w["swg"], w["swu"], w["swd"], mode)


def layer(conf: dict, w: dict, x, mode: str = "f32"):
    """One decoder layer over ``x`` [N, T, d] (each row its own sequence
    from position 0), of the kind its weights name."""
    if "in_scale" in w:
        x = x * w["in_scale"]
    r = float(conf["residual_multiplier"])
    mixer = _mamba if "A_log" in w else _attention

    def one(xs):
        xs = xs + mixer(conf, w, xs, mode) * r
        return xs + ffn(conf, w, xs, mode) * r

    return jax.lax.map(one, x)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def logits(conf: dict, ln_f, head, x, mode: str = "f32"):
    """Final norm, the LM head ([d, V], the transposed embedding) and the
    logit scaling: x [..., d] -> [..., V]."""
    z = matmul(rms_norm(x, ln_f, float(conf["rms_norm_eps"])), head, mode)
    return z / float(conf["logits_scaling"])


def forward(conf: dict, params: dict, tokens, mode: str = "f32"):
    """Whole-model logits [N, T, V] for small sizes (tests)."""
    x = embed(params["embed"], tokens)
    for w in params["layers"]:
        x = layer(conf, w, x, mode)
    return logits(conf, params["ln_f"], params["embed"].T, x, mode)

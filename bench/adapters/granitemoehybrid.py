"""How the program under test runs a ``granitemoehybrid`` configuration:
its ``ModelConfig`` and its parameter tree, drawn from the seed.

The program keeps each kind's layers stacked (``blocks.mamba [9, ...]``,
``blocks.attention [1, ...]``) under the reference's tensor names; the
SSM state it carries is packed (``kernels.ssm_state_step.ref``), which
changes no weight.  Layer ``l`` of a kind is drawn from layer index ``l``
of the whole stack, so the reference (one layer at a time) and the
program (a kind at a time) draw the same values.
"""
from __future__ import annotations

import json
from functools import lru_cache

import jax
import jax.numpy as jnp

from bench.harness import weights
from bench.reference import granitemoehybrid as ref

_KEYS = {"mamba": ref.MAMBA_KEYS, "attention": ref.ATTENTION_KEYS}


def model_config(conf: dict, name: str):
    from repro.models.base import ModelConfig
    D = ref.dims(conf)
    if D["din"] != D["mH"] * D["mP"] or int(conf["mamba_n_groups"]) != 1:
        raise ValueError("the program's Mamba2 takes one group and "
                         "d_inner = heads x head size")
    if D["conv"] != 4 or not conf["mamba_conv_bias"] \
            or conf["mamba_proj_bias"] or conf["attention_bias"]:
        raise ValueError("the program's Mamba2 has a width-4 conv with "
                         "bias and no projection biases")
    if conf["position_embedding_type"] != "nope" \
            or not conf["tie_word_embeddings"]:
        raise ValueError("this adapter runs NoPE attention and a tied head")
    return ModelConfig(
        name=name, family="granite_hybrid", n_layers=D["L"],
        layer_types=D["types"], d_model=D["d"], n_heads=D["H"],
        n_kv_heads=D["Hkv"], head_dim=D["hd"], d_ff=D["ff"],
        shared_ff=D["sff"], n_experts=D["E"], expert_range=D["held"],
        top_k=D["K"], vocab=D["V"], ssm_state=D["N"],
        ssm_head_dim=D["mP"], ssm_expand=int(conf["mamba_expand"]),
        norm_eps=D["eps"],
        attn_scale=float(conf["attention_multiplier"]),
        embed_scale=float(conf["embedding_multiplier"]),
        residual_scale=float(conf["residual_multiplier"]),
        logit_scale=float(conf["logits_scaling"]), tie_embeddings=True,
        max_seq=int(conf["max_position_embeddings"]),
        param_dtype=conf["program"]["param_dtype"],
        compute_dtype=conf["program"]["compute_dtype"])


def _served_dtype(conf: dict):
    return jnp.dtype(conf["program"]["param_dtype"])


def _key(conf: dict) -> str:
    return json.dumps(conf, sort_keys=True)


def _draw_layer(conf: dict, root, kind: str, layer, dtype) -> dict:
    """Layer ``layer``'s tensors (a layer of kind ``kind``), rounded to
    ``dtype``; ``dt_bias`` is shifted by the recipe's mean."""
    recipe = ref.param_recipe(conf, conf["init"])[kind]
    w = weights.draw_layer(root, recipe, _KEYS[kind], layer, dtype)
    if "dt_bias" in w:
        shift = ref.dt_bias_mean(conf["init"])
        w["dt_bias"] = (w["dt_bias"].astype(jnp.float32) + shift).astype(dtype)
    return w


@lru_cache(maxsize=None)
def _program_params_fn(conf_key: str):
    conf = json.loads(conf_key)
    D = ref.dims(conf)
    dtype = _served_dtype(conf)
    ids = {k: [l for l, t in enumerate(D["types"]) if t == k]
           for k in ("mamba", "attention")}
    recipe = ref.param_recipe(conf, conf["init"])

    def make(root):
        blocks = {k: jax.vmap(lambda l, k=k: _draw_layer(
                      conf, root, k, l, dtype))(jnp.asarray(v))
                  for k, v in ids.items() if v}
        glob = weights.draw_global(root, recipe["global"], ref.GLOBAL_KEYS,
                                   dtype)
        return {"embed": glob["embed"], "ln_f": glob["ln_f"],
                "blocks": blocks}

    return jax.jit(make)


def program_params(conf: dict, seed: int):
    """The program's parameter tree, on the device, in one jitted call."""
    return _program_params_fn(_key(conf))(weights.root_key(seed))


@lru_cache(maxsize=None)
def _reference_layer_fn(conf_key: str, kind: str):
    conf = json.loads(conf_key)
    dtype = _served_dtype(conf)

    def make(root, layer):
        w = _draw_layer(conf, root, kind, layer, dtype)
        return {k: v.astype(jnp.float32) for k, v in w.items()}

    return jax.jit(make)


def reference_layer(conf: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` as the reference reads it: drawn alone, rounded to
    the served dtype, upcast to float32; layer 0 carries the embedding
    multiplier as ``in_scale``."""
    kind = ref.dims(conf)["types"][layer]
    w = _reference_layer_fn(_key(conf), kind)(weights.root_key(seed),
                                              jnp.int32(layer))
    if layer == 0:
        w = dict(w, in_scale=jnp.float32(conf["embedding_multiplier"]))
    return w


def reference_globals(conf: dict, seed: int) -> dict:
    recipe = ref.param_recipe(conf, conf["init"])
    g = jax.jit(lambda root: weights.draw_global(
        root, recipe["global"], ref.GLOBAL_KEYS, _served_dtype(conf)))(
            weights.root_key(seed))
    return {k: v.astype(jnp.float32) for k, v in g.items()}


def reference_params(conf: dict, seed: int) -> dict:
    """Every tensor in the reference's layout, float32 (small sizes)."""
    g = reference_globals(conf, seed)
    return {"embed": g["embed"], "ln_f": g["ln_f"],
            "layers": [reference_layer(conf, seed, l)
                       for l in range(int(conf["num_hidden_layers"]))]}

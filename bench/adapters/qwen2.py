"""How the program under test runs a ``qwen2`` configuration: its
``ModelConfig`` and its parameter tree, drawn from the seed.

The program keeps every layer's tensors stacked ``[L, ...]`` and rotates
interleaved pairs of head dims (``x[2i], x[2i+1]``) where the published
model rotates the halves (``x[i], x[i + hd/2]``).  The two are the same
model under a fixed permutation of each head's q and k columns, so the
published q/k weights are permuted into the program's order here, as a
checkpoint converter would.  The reference side (``reference_layer``,
``reference_globals``) draws the same values in the published layout.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights
from bench.reference import qwen2 as ref

#: the program's own rotary tables use this theta and take no other
PROGRAM_ROPE_THETA = 10000.0


def model_config(conf: dict, name: str):
    from repro.models.base import ModelConfig
    D = ref.dims(conf)
    if D["theta"] != PROGRAM_ROPE_THETA:
        raise ValueError(f"the program's rotary tables are fixed at theta "
                         f"{PROGRAM_ROPE_THETA}; this configuration asks "
                         f"for {D['theta']}")
    if not conf["tie_word_embeddings"]:
        raise ValueError("this adapter draws tied embeddings only")
    return ModelConfig(
        name=name, family="dense", n_layers=D["L"], d_model=D["d"],
        n_heads=D["H"], n_kv_heads=D["Hkv"], d_ff=D["ff"], vocab=D["V"],
        head_dim=D["hd"], qkv_bias=True, tie_embeddings=True,
        max_seq=int(conf["max_position_embeddings"]),
        param_dtype=conf["program"]["param_dtype"],
        compute_dtype=conf["program"]["compute_dtype"])


def rope_permutation(n_heads: int, hd: int) -> np.ndarray:
    """Column order that turns half-split rotary pairs into interleaved
    ones: program column ``h*hd + 2i (+1)`` takes published column
    ``h*hd + i (+ hd/2)``."""
    half = hd // 2
    one = np.stack([np.arange(half), np.arange(half) + half], -1).reshape(-1)
    return (np.arange(n_heads)[:, None] * hd + one[None]).reshape(-1)


def _served_dtype(conf: dict):
    return jnp.dtype(conf["program"]["param_dtype"])


@lru_cache(maxsize=None)
def _program_params_fn(conf_key: str):
    import json
    conf = json.loads(conf_key)
    D = ref.dims(conf)
    recipe = ref.param_recipe(conf, conf["init"])
    dtype = _served_dtype(conf)
    pq = rope_permutation(D["H"], D["hd"])
    pk = rope_permutation(D["Hkv"], D["hd"])

    def make(root):
        blocks = weights.draw_stacked(root, recipe["layer"], ref.LAYER_KEYS,
                                      D["L"], dtype)
        for k, perm in (("wq", pq), ("bq", pq), ("wk", pk), ("bk", pk)):
            blocks[k] = jnp.take(blocks[k], perm, axis=-1)
        glob = weights.draw_global(root, recipe["global"], ref.GLOBAL_KEYS,
                                   dtype)
        return {"embed": glob["embed"], "ln_f": glob["ln_f"],
                "blocks": blocks}

    return jax.jit(make)


def _key(conf: dict) -> str:
    import json
    return json.dumps(conf, sort_keys=True)


def program_params(conf: dict, seed: int):
    """The program's parameter tree, on the device, in one jitted call."""
    return _program_params_fn(_key(conf))(weights.root_key(seed))


@lru_cache(maxsize=None)
def _reference_layer_fn(conf_key: str):
    import json
    conf = json.loads(conf_key)
    recipe = ref.param_recipe(conf, conf["init"])
    dtype = _served_dtype(conf)

    def make(root, layer):
        w = weights.draw_layer(root, recipe["layer"], ref.LAYER_KEYS, layer,
                               dtype)
        return {k: v.astype(jnp.float32) for k, v in w.items()}

    return jax.jit(make)


def reference_layer(conf: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` as the reference reads it: drawn alone, rounded to
    the served dtype, upcast to float32, published layout."""
    return _reference_layer_fn(_key(conf))(weights.root_key(seed),
                                           jnp.int32(layer))


def reference_globals(conf: dict, seed: int) -> dict:
    recipe = ref.param_recipe(conf, conf["init"])
    g = jax.jit(lambda root: weights.draw_global(
        root, recipe["global"], ref.GLOBAL_KEYS, _served_dtype(conf)))(
            weights.root_key(seed))
    return {k: v.astype(jnp.float32) for k, v in g.items()}


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _program_norms(tree):
    out = {k: _norm(tree[k]) for k in ("embed", "ln_f")}
    for k, v in tree["blocks"].items():
        axes = tuple(range(1, v.ndim))
        out[k] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axes))
    return out


def program_leaf_norms(tree) -> dict:
    """``{published tensor name: norm}`` of a tree laid out as the
    program's parameters (``embed``, ``ln_f``, ``layers.<l>.<name>``);
    column permutations leave a norm unchanged."""
    raw = jax.device_get(_program_norms(tree))
    out = {"embed": float(raw["embed"]), "ln_f": float(raw["ln_f"])}
    for k in ref.LAYER_KEYS:
        for l, v in enumerate(np.asarray(raw[k])):
            out[f"layers.{l}.{k}"] = float(v)
    return out


def reference_leaf_norms(tree) -> dict:
    """The same names for a tree in the reference's layout."""
    raw = jax.device_get(jax.jit(lambda t: jax.tree_util.tree_map(_norm, t))(
        tree))
    out = {"embed": float(raw["embed"]), "ln_f": float(raw["ln_f"])}
    for l, layer in enumerate(raw["layers"]):
        for k in ref.LAYER_KEYS:
            out[f"layers.{l}.{k}"] = float(layer[k])
    return out


def reference_params(conf: dict, seed: int) -> dict:
    """Every tensor in the reference's layout, float32 (training sizes)."""
    g = reference_globals(conf, seed)
    return {"embed": g["embed"], "ln_f": g["ln_f"],
            "layers": [reference_layer(conf, seed, l)
                       for l in range(int(conf["num_hidden_layers"]))]}

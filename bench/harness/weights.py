"""Random weights drawn from the seed, identical wherever they are drawn.

Every tensor comes from its own key, ``fold_in(fold_in(root, tensor),
layer)``, so a layer's weights can be drawn alone (the reference reads
one layer at a time) or all layers at once (the program's stacked
layout) and come out with the same values.  Values are drawn in float32
and rounded once to the dtype they are served in; the reference upcasts
that rounded value, so both sides compute with the same numbers.  The
root key is an argument, so one compiled program serves every seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """Key for any non-negative seed up to 2**64: the low and the high 32
    bits are folded in separately."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def draw(root, tensor_idx: int, layer, shape, kind: str, std: float, dtype):
    """One tensor: N(0, std) (``kind`` "normal") or 1 + N(0, std)
    ("norm"), rounded to ``dtype``.  ``layer`` may be traced."""
    key = jax.random.fold_in(jax.random.fold_in(root, tensor_idx), layer)
    z = jax.random.normal(key, shape, jnp.float32)
    x = z * std + (1.0 if kind == "norm" else 0.0)
    return x.astype(dtype)


def draw_layer(root, recipe: dict, keys: tuple, layer, dtype) -> dict:
    """Layer ``layer``'s tensors, ``{name: array}``."""
    return {name: draw(root, 1 + i, layer, *recipe[name], dtype=dtype)
            for i, name in enumerate(keys)}


def draw_stacked(root, recipe: dict, keys: tuple, n_layers: int,
                 dtype) -> dict:
    """Every layer at once: ``{name: [n_layers, ...]}``."""
    return jax.vmap(lambda l: draw_layer(root, recipe, keys, l, dtype))(
        jnp.arange(n_layers))


def draw_global(root, recipe: dict, keys: tuple, dtype) -> dict:
    """Tensors outside the layers (tensor ids clear of the layers')."""
    return {name: draw(root, 1000 + i, 0, *recipe[name], dtype=dtype)
            for i, name in enumerate(keys)}

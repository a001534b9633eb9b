"""The float32 reference against the program at smoke size, on the CPU:
the same seeded weights through the program's plain ``jax.jit`` path
(f32 activations, "highest" matmuls) and through the reference."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.adapters import qwen2 as ad
from bench.harness import weights
from bench.reference import qwen2 as ref
from bench.tests import tiny


def f32_conf():
    conf = copy.deepcopy(tiny.TRAIN_CONF)
    conf["program"] = {"param_dtype": "float32", "compute_dtype": "float32"}
    return conf


def program_model(conf):
    from repro.models.base import get_model
    return get_model(ad.model_config(conf, "tiny"))


def opaque():
    from repro.core.tapir import TapirConfig, use
    return use(TapirConfig(mode="opaque", regions=False))


def test_layers_drawn_alone_equal_layers_drawn_stacked():
    conf = tiny.CONF
    recipe = ref.param_recipe(conf, conf["init"])["layer"]
    root = weights.root_key(2**40 + 3)
    stacked = weights.draw_stacked(root, recipe, ref.LAYER_KEYS, 2,
                                   jnp.bfloat16)
    for l in range(2):
        one = weights.draw_layer(root, recipe, ref.LAYER_KEYS, l,
                                 jnp.bfloat16)
        for k in ref.LAYER_KEYS:
            np.testing.assert_array_equal(np.asarray(stacked[k][l]),
                                          np.asarray(one[k]))


def test_rope_permutation_interleaves_halves():
    assert list(ad.rope_permutation(1, 8)) == [0, 4, 1, 5, 2, 6, 3, 7]
    assert list(ad.rope_permutation(2, 4))[4:] == [4, 6, 5, 7]


def test_forward_matches_the_program():
    conf = f32_conf()
    seed = 12345
    model = program_model(conf)
    params = ad.program_params(conf, seed)
    toks = np.random.default_rng(0).integers(0, 512, size=(2, 24))
    with opaque(), jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(params, {"tokens": jnp.asarray(toks)}))
    want = np.asarray(ref.forward(conf, ad.reference_params(conf, seed),
                                  jnp.asarray(toks)))
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


def test_loss_and_gradients_match_the_program():
    conf = f32_conf()
    seed = 777
    model = program_model(conf)
    params = ad.program_params(conf, seed)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512,
                                                         size=(2, 17)))
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with opaque(), jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(params, batch)
    lr_, gr = jax.value_and_grad(
        lambda p: ref.loss(conf, p, batch["tokens"], batch["labels"]))(
            ad.reference_params(conf, seed))
    assert float(lp) == pytest.approx(float(lr_), rel=1e-6)
    np_, nr = ad.program_leaf_norms(gp), ad.reference_leaf_norms(gr)
    assert set(np_) == set(nr)
    for k in nr:
        assert np_[k] == pytest.approx(nr[k], rel=1e-4, abs=1e-9), k


def test_adamw_matches_the_program():
    from repro.optim import AdamWConfig
    from repro.optim.adamw import adamw_init, adamw_update
    opt = dict(tiny.JOB["optimizer"], warmup_steps=2, weight_decay=0.1)
    rng = np.random.default_rng(2)
    p = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    cfg = AdamWConfig(**opt)
    state = adamw_init(p, cfg)
    pp, m, v = p, jax.tree_util.tree_map(jnp.zeros_like, p), \
        jax.tree_util.tree_map(jnp.zeros_like, p)
    pr = p
    for s in range(1, 4):
        g = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(size=x.shape) * 3, jnp.float32),
            p)
        pp, state, _ = adamw_update(pp, g, state, cfg)
        pr, m, v, _ = ref.adamw_step(opt, pr, g, m, v, s)
    for k in p:
        np.testing.assert_allclose(np.asarray(pp[k]), np.asarray(pr[k]),
                                   rtol=1e-6, atol=1e-7)

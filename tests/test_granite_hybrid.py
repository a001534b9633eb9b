"""granite's hybrid family at smoke size on the slot path: what its decode
step compiles into, and what the other state-space families keep."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.core import tapir
from repro.core.tapir import TapirConfig, use
from repro.models.base import get_model


def _model():
    cfg = dataclasses.replace(C.get_smoke("granite_4_0_h_small"),
                              compute_dtype="float32")
    model = get_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_decode_step_is_a_mixer_and_a_moe_program_per_layer():
    """Each layer replays two region programs, its mixer's and its MoE's,
    named so a device trace tells Mamba, attention and MoE apart; one
    program per kind serves every layer of that kind."""
    model, params = _model()
    sp = model.slot_params(params)
    cache = model.init_slot_cache(3, 32)
    tapir.clear_cache()
    with use(TapirConfig(mode="tapir")):
        model.decode_step_slots(sp, jnp.zeros((3, 1), jnp.int32), cache)
        names = sorted(g.name for g in tapir.cached_graphs().values())
    tapir.clear_cache()
    assert names == ["slot_attention_block", "slot_head", "slot_mamba_block",
                     "slot_moe_block"]


def test_state_rows_advance_only_for_the_slots():
    """A decode step advances rows [0, slots) of every state array; the
    park rows past them keep what they hold."""
    model, params = _model()
    sp = model.slot_params(params)
    cache = model.init_slot_cache(2, 32)
    park = [np.asarray(a[2:]) + 1.0 for a in cache["state"]["ssm"]]
    cache["state"]["ssm"] = [a.at[2:].set(p) for a, p in
                             zip(cache["state"]["ssm"], park)]
    before = [np.asarray(a[:2]) for a in cache["state"]["ssm"]]
    with use(TapirConfig(mode="tapir")):
        _, cache = model.decode_step_slots(
            sp, jnp.asarray([[5], [7]], jnp.int32), cache)
    for a, p, b in zip(cache["state"]["ssm"], park, before):
        np.testing.assert_array_equal(np.asarray(a[2:]), p)
        assert not np.array_equal(np.asarray(a[:2]), b)


def test_zamba2_keeps_the_padded_waves():
    """Zamba2 (Mamba2 + one shared attention block) has no slot path yet:
    the engine serves it through the padded-wave loop."""
    from repro.serve import Request, ServeConfig, ServingEngine
    cfg = C.get_smoke("zamba2_7b")
    model = get_model(cfg)
    assert not model.supports_slots() and model.slot_state_rows() == {}
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        batch=2, max_len=32, cfg=ServeConfig(target="cpu"))
    assert not eng._slot_capable and not eng._state
    reqs = [Request(rid=i, prompt=np.arange(1, 6, dtype=np.int32) + i,
                    max_new=3) for i in range(2)]
    eng.run(reqs)
    assert all(len(r.out) == 3 for r in reqs)

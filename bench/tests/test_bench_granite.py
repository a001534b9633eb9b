"""granitemoehybrid against its float32 reference on the CPU, at a tiny
size with seeded random weights: the whole forward, prefill into a slot
and decode through the state rows, slots admitted apart and re-admitted,
park and resume, and the chip's share of the experts.

Every comparison here runs the program in float32 (activations, state
and "highest" matmuls), so the only differences from the reference are
the order of float32 sums (the program's chunked scan and fused GEMMs
against the reference's sequential scan): a tolerance of 1e-5 of the
largest logit holds them with two orders of magnitude to spare (largest
difference seen: 2e-7 of 1.5), while any missing term (a dropped state
update, a wrong conv window, a mis-scaled residual) moves logits by
percents."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.adapters import granitemoehybrid as ad
from bench.reference import granitemoehybrid as ref
from bench.tests import tiny_hybrid

SEED = 2**40 + 5
TOL = 1e-5


def _model(conf):
    from repro.models.base import get_model
    return get_model(ad.model_config(conf, "tiny"))


def _tapir():
    from repro.core.tapir import TapirConfig, use
    return use(TapirConfig(mode="tapir"))


@pytest.fixture(scope="module")
def setup():
    conf = tiny_hybrid.f32()
    model = _model(conf)
    params = ad.program_params(conf, SEED)
    rparams = ad.reference_params(conf, SEED)
    return conf, model, params, rparams


def _ref_logits(conf, rparams, seq):
    return np.asarray(ref.forward(conf, rparams, jnp.asarray(seq)[None]))[0]


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= TOL * np.max(np.abs(want)), \
        float(np.max(np.abs(got - want)))


def test_forward_matches_reference(setup):
    conf, model, params, rparams = setup
    toks = np.random.default_rng(0).integers(0, 512, size=(2, 40))
    with _tapir(), jax.default_matmul_precision("highest"):
        got = model.forward(params, {"tokens": jnp.asarray(toks)})
    _close(got, ref.forward(conf, rparams, jnp.asarray(toks)))


def test_prefill_then_decode_matches_reference(setup):
    """A 13-token prompt (padded to a 16-row bucket) prefilled into slot 2
    of 3, then 20 decode steps through the state rows and pages: each
    step's logits are the reference's at that position of the same
    sequence."""
    conf, model, params, rparams = setup
    seq = np.random.default_rng(1).integers(0, 512, size=33)
    plen = 13
    want = _ref_logits(conf, rparams, seq)
    sp = model.slot_params(params)
    cache = model.init_slot_cache(3, 64, page_len=16)
    with _tapir(), jax.default_matmul_precision("highest"):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :plen] = seq[:plen]
        logits, cache = model.prefill_into_slot(sp, jnp.asarray(toks), cache,
                                                2, plen)
        _close(logits[0], want[plen - 1])
        for t in range(plen, len(seq)):
            feed = np.zeros((3, 1), np.int32)
            feed[2, 0] = seq[t]
            logits, cache = model.decode_step_slots(sp, jnp.asarray(feed),
                                                    cache)
            _close(logits[2], want[t])
    assert int(cache["pos"][2]) == len(seq)


def _greedy_ref(conf, rparams, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_ref_logits(conf, rparams, seq)[-1])))
    return seq[len(prompt):]


def _engine(model, params, **kw):
    from repro.serve import ServeConfig, ServingEngine
    return ServingEngine(model, params, batch=2, max_len=64,
                         cfg=ServeConfig(target="cpu", **kw))


def test_slots_admitted_apart_and_readmitted(setup):
    """Two slots admitted at different ticks, and a third request
    admitted into the slot the first frees: its state rows are reset, so
    every request's greedy tokens are the reference's."""
    from repro.serve import Request
    conf, model, params, rparams = setup
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, size=n)
                    .astype(np.int32), max_new=m, arrival_step=a)
            for i, (n, m, a) in enumerate([(9, 5, 0), (14, 9, 3),
                                           (6, 7, 0)])]
    eng = _engine(model, params)
    assert eng._slot_capable and model.supports_slots()
    with jax.default_matmul_precision("highest"):
        eng.run(reqs)
    assert eng.last_stats["admitted"] == 3
    for r in reqs:
        assert r.done and r.out == _greedy_ref(conf, rparams, r.prompt,
                                               r.max_new), r.rid


def test_park_and_resume_mid_output(setup):
    """A higher-priority arrival parks a running request mid-output (its
    pages and state rows copied out) and the request resumes from them:
    its tokens are the reference's, as if never evicted."""
    from repro.serve import Request
    conf, model, params, rparams = setup
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, size=n)
                    .astype(np.int32), max_new=m, priority=p,
                    arrival_step=a)
            for i, (n, m, p, a) in enumerate([(7, 12, 0, 0), (11, 6, 0, 0),
                                              (5, 4, 5, 3)])]
    eng = _engine(model, params, preempt_mode="park")
    with jax.default_matmul_precision("highest"):
        eng.run(reqs)
    assert eng.last_stats["parked"] == 1
    for r in reqs:
        assert r.out == _greedy_ref(conf, rparams, r.prompt, r.max_new), \
            r.rid


def test_expert_shares_sum_to_the_uncut_layer():
    """72 routed experts, top-10, held 9 at a time by 8 shares: the parts
    the shares compute (the program's held-expert layer, and the
    reference's), with the shared expert counted once, add up to the
    layer holding all 72."""
    conf = tiny_hybrid.f32(dict(copy.deepcopy(tiny_hybrid.CONF),
                                router_experts=72, num_experts_per_tok=10,
                                num_local_experts=72, experts_held=[0, 72]))
    whole = ad.reference_layer(conf, SEED, 0)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)

    def held(lo, hi):
        return {k: (v[lo:hi] if k in ("ewg", "ewu", "ewd") else v)
                for k, v in whole.items()}

    want = ref.ffn(conf, whole, x)
    # the shared expert alone: a share holding no routed expert
    shared = ref.ffn(dict(conf, experts_held=[0, 0]), held(0, 0), x)
    parts, prog = [], []
    uncut = _model(conf)
    for i in range(8):
        lo, hi = 9 * i, 9 * (i + 1)
        cut = held(lo, hi)
        share = dict(conf, experts_held=[lo, hi])
        parts.append(ref.ffn(share, cut, x) - shared)
        m = _model(share)
        assert m.cfg.expert_range == (lo, hi)
        with _tapir(), jax.default_matmul_precision("highest"):
            prog.append(m._moe_ffn_traced(cut, x[None], dropless=True)[0])
    # every routed expert's part lands in exactly one share: the sum is
    # the whole layer to float32 rounding of 8 partial sums
    _close(sum(parts) + shared, want)
    with _tapir(), jax.default_matmul_precision("highest"):
        routed = uncut._moe_ffn_traced(whole, x[None], dropless=True)[0]
    _close(sum(prog), routed)


def test_decode_path_is_the_slot_path(setup):
    """The engine serves this family through its slot loop, with prefix
    sharing off (a prefix's pages without its state would be wrong) and
    a shared region sized to park one slot's worth per eight."""
    conf, model, params, _ = setup
    eng = _engine(model, params)
    assert eng._state and not eng._prefix_sharing
    assert eng._shared_pages == 1          # 2 slots: one slot's one page
    cache = model.init_slot_cache(2, 64, shared_pages=eng._shared_pages)
    assert cache["state"]["ssm"][0].shape[0] == 3      # 2 slots + 1 park row
    st = model.slot_state_rows()
    assert cache["state"]["ssm"][0].shape[1:] == st["ssm"]
    assert dataclasses.asdict(model.cfg)["expert_range"] == (0, 8)

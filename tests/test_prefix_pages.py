"""Ref-counted shared prefix pages + priority preemption (ISSUE 9).

* validation — ServeConfig / Request reject bad policy strings and
  out-of-range fields at construction, not mid-run;
* PagePool — geometry, token-exact longest-prefix lookup, refcounted
  bind/unbind, LRU eviction of unreferenced entries, park/resume page
  accounting, JSON meta round-trip;
* prefix sharing — requests extending a resident prefix prefill ONLY
  their suffix yet stay bitwise identical to the unshared engine, and
  COW on exact-cover prompts never perturbs peers bound to the same
  pages;
* preemption — a high-priority arrival evicts a lower-priority slot
  (park and replay arms both), and the victim's final output is bitwise
  identical to an uncontended run;
* program cache — `_PROGRAMS` hit-rate stays 1 across bindings (page
  indirection is data, not shape).
"""
import dataclasses

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.core.tapir import clear_cache
from repro.models.base import get_model
from repro.serve import Request, ServeConfig, ServingEngine
from repro.serve.pages import (PagePool, PreemptCost, page_geometry,
                               preempt_cost, private_page)


def setup_function(_):
    clear_cache()


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    {"admit_policy": "bogus"},
    {"preempt_mode": "drop"},
    {"shed_base": -1},
    {"shed_cap": -2},
    {"page_len": 0},
    {"page_len": -64},
    {"shared_pages": -1},
])
def test_serve_config_rejects_bad_fields(kw):
    with pytest.raises(ValueError):
        ServeConfig(target="cpu", **kw)


@pytest.mark.parametrize("kw", [
    {"priority": 10},
    {"priority": -1},
    {"arrival_step": -1},
])
def test_request_rejects_bad_fields(kw):
    with pytest.raises(ValueError):
        Request(rid=0, prompt=np.ones(4, np.int32), max_new=2, **kw)


def test_admit_policy_slo_accepted():
    assert ServeConfig(target="cpu", admit_policy="slo").admit_policy == \
        "slo"


# ---------------------------------------------------------------------------
# PagePool unit tests (host state only — no model)
# ---------------------------------------------------------------------------


def test_page_geometry_divides_or_raises():
    assert page_geometry(576) == (64, 9)
    assert page_geometry(48) == (48, 1)          # 64 does not divide 48
    assert page_geometry(128, page_len=32) == (32, 4)
    with pytest.raises(ValueError):
        page_geometry(128, page_len=48)


def test_lookup_is_token_exact_and_longest():
    pool = PagePool(slots=2, max_len=128, page_len=32)
    prompt = np.arange(1, 97, dtype=np.int32)        # 3 full pages
    # fake a published 2-page entry by driving the public API on a
    # host-only "cache" of plain numpy pools
    cache = {"k": [np.zeros((pool.shared_start + pool.n_shared, 32, 1, 1))],
             "v": [np.zeros((pool.shared_start + pool.n_shared, 32, 1, 1))]}
    assert pool.publish(cache, 0, prompt[:64]) == 2
    k, pages = pool.lookup(prompt)
    assert k == 2 and len(pages) == 2
    # token-exact: same-length different tokens must MISS
    other = prompt.copy()
    other[10] += 1
    assert pool.lookup(other) == (0, [])
    # shorter than one page: no match possible
    assert pool.lookup(prompt[:31]) == (0, [])


def test_bind_refcounts_and_lru_eviction():
    pool = PagePool(slots=2, max_len=64, page_len=32, shared_pages=2)
    cache = {"k": [np.zeros((pool.shared_start + 2, 32, 1, 1))],
             "v": [np.zeros((pool.shared_start + 2, 32, 1, 1))]}
    p1 = np.arange(1, 33, dtype=np.int32)
    assert pool.publish(cache, 0, p1) == 1
    h = pool.bind(0, p1, 1)
    assert pool.entries[h].refs == 1
    # referenced entries are not evictable: a 2-page publish cannot fit
    p2 = np.arange(100, 164, dtype=np.int32)
    assert pool.publish(cache, 1, p2) == 0
    pool.unbind(0)
    assert pool.entries[h].refs == 0
    # now LRU eviction frees the old entry and the publish lands
    assert pool.publish(cache, 1, p2) == 2
    assert h not in pool.entries


def test_park_resume_roundtrip_accounting():
    pool = PagePool(slots=1, max_len=64, page_len=32, shared_pages=2)
    P = pool.shared_start + 2
    cache = {"k": [np.arange(P * 32, dtype=np.float32).reshape(P, 32, 1, 1)],
             "v": [np.zeros((P, 32, 1, 1), np.float32)]}
    want = np.array(cache["k"][0][private_page(0, 0, pool.pps)])
    assert pool.park(cache, rid=7, slot=0, length=20)
    assert 7 in pool.parked and len(pool.free) == 1
    # clobber the private page, then resume must restore it bitwise
    # (park returned jax pools — clobber via a host copy)
    k0 = np.array(cache["k"][0])
    k0[private_page(0, 0, pool.pps)] = -1.0
    cache["k"][0] = k0
    rec = pool.resume(cache, rid=7, slot=0)
    assert rec["length"] == 20 and not pool.parked
    assert len(pool.free) == 2
    np.testing.assert_array_equal(
        np.asarray(cache["k"][0][private_page(0, 0, pool.pps)]), want)


def test_pool_meta_roundtrip():
    pool = PagePool(slots=2, max_len=128, page_len=32)
    cache = {"k": [np.zeros((pool.shared_start + pool.n_shared, 32, 1, 1))],
             "v": [np.zeros((pool.shared_start + pool.n_shared, 32, 1, 1))]}
    prompt = np.arange(1, 65, dtype=np.int32)
    pool.publish(cache, 0, prompt)
    pool.bind(0, prompt, 2)
    pool.park(cache, rid=3, slot=1, length=40)
    back = PagePool.from_meta(pool.to_meta(), slots=2, max_len=128,
                              page_len=32)
    assert back.free == pool.free
    assert back.slot_entry == pool.slot_entry
    assert back.slot_bound == pool.slot_bound
    assert set(back.entries) == set(pool.entries)
    for h in pool.entries:
        np.testing.assert_array_equal(back.entries[h].tokens,
                                      pool.entries[h].tokens)
        assert back.entries[h].refs == pool.entries[h].refs
    assert back.parked.keys() == pool.parked.keys()
    assert back.parked[3]["pages"] == pool.parked[3]["pages"]


def test_preempt_cost_arms():
    class CM:
        peak_flops, hbm_bw, spawn_s = 1e12, 1e11, 1e-6

    # tiny state, expensive replay -> park
    c = preempt_cost(CM(), length=512, prefix_len=0, n_out=400,
                     page_bytes=1 << 10, pps=8, page_len=64,
                     model_flops_per_tok=1e9, step_s=0.5)
    assert isinstance(c, PreemptCost) and c.arm == "park"
    # huge state, nearly-free replay -> replay
    c = preempt_cost(CM(), length=128, prefix_len=64, n_out=2,
                     page_bytes=1 << 30, pps=8, page_len=64,
                     model_flops_per_tok=1e3, step_s=1e-6)
    assert c.arm == "replay"


# ---------------------------------------------------------------------------
# engine-level: prefix sharing, COW, preemption (smoke model)
# ---------------------------------------------------------------------------


def _model():
    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                              compute_dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _engines(model, params, slots, max_len):
    shared = ServingEngine(model, params, batch=slots, max_len=max_len,
                           cfg=ServeConfig(target="cpu"))
    base = ServingEngine(model, params, batch=slots, max_len=max_len,
                         cfg=ServeConfig(target="cpu",
                                         prefix_sharing=False))
    return shared, base


def _shared_prefix_reqs(rng, prefix, n, suffix_len=4, max_new=4):
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(1, 100, size=suffix_len)
                         .astype(np.int32)]),
                    max_new=max_new)
            for i in range(n)]


def test_prefix_sharing_bitwise_and_counters():
    model, params = _model()
    shared, base = _engines(model, params, slots=2, max_len=128)
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, 100, size=64).astype(np.int32)

    mk = lambda: _shared_prefix_reqs(np.random.default_rng(4), prefix, 4)
    ref = base.run(mk())
    out = shared.run(mk())
    assert [r.out for r in out] == [r.out for r in ref]
    assert all(r.done for r in out)
    st = shared.last_stats
    # request 0 publishes the 64-token (one page) prefix; 1..3 bind it
    assert st["prefix_hits"] == 3
    assert st["prefix_tokens_saved"] == 3 * 64
    assert base.last_stats["prefix_hits"] == 0


def test_programs_hit_rate_one_across_bindings():
    model, params = _model()
    shared, _ = _engines(model, params, slots=2, max_len=128)
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 100, size=64).astype(np.int32)
    mk = lambda: _shared_prefix_reqs(np.random.default_rng(6), prefix, 4)
    shared.run(mk())                       # warmup: compiles everything
    shared.run(mk())
    assert shared.last_stats["compiled_programs"] == 0, \
        "page indirection leaked into program identity"


def test_cow_exact_cover_never_perturbs_peers():
    """A prompt that exactly covers a published prefix must COW the
    boundary page (its last token re-runs to produce logits).  Peers
    bound to the same shared pages — including one still mid-decode —
    must stay bitwise identical to the unshared engine."""
    model, params = _model()
    shared, base = _engines(model, params, slots=2, max_len=192)
    rng = np.random.default_rng(7)
    full = rng.integers(1, 100, size=128).astype(np.int32)   # 2 pages
    ext = np.concatenate([full,
                          rng.integers(1, 100, size=5).astype(np.int32)])

    def mk():
        return [
            Request(rid=0, prompt=full.copy(), max_new=6),
            # exact cover: prompt == published 2-page prefix -> COW
            Request(rid=1, prompt=full.copy(), max_new=6),
            # extension: binds both pages, prefills only the tail
            Request(rid=2, prompt=ext.copy(), max_new=6),
        ]

    ref = base.run(mk())
    out = shared.run(mk())
    assert [r.out for r in out] == [r.out for r in ref]
    assert shared.last_stats["prefix_hits"] == 2


def _preempt_workload(rng, long_new=12):
    low = Request(rid=0,
                  prompt=rng.integers(1, 100, size=6).astype(np.int32),
                  max_new=long_new, priority=0)
    high = Request(rid=1,
                   prompt=rng.integers(1, 100, size=5).astype(np.int32),
                   max_new=3, priority=5, arrival_step=3)
    return [low, high]


@pytest.mark.parametrize("mode", ["park", "replay", "auto"])
def test_priority_preemption_bitwise(mode):
    """With one slot, the priority-5 arrival evicts the running
    priority-0 request; the victim is later restored (park) or replayed
    (drop + re-prefill + recorded-token feed) and must finish with
    exactly the tokens of an uncontended run."""
    model, params = _model()
    eng = ServingEngine(model, params, batch=1, max_len=64,
                        cfg=ServeConfig(target="cpu", preempt_mode=mode))
    ref_eng = ServingEngine(model, params, batch=1, max_len=64,
                            cfg=ServeConfig(target="cpu"))

    rng = np.random.default_rng(11)
    reqs = _preempt_workload(rng)
    # reference: same prompts, no priorities -> plain FIFO, no eviction
    ref = ref_eng.run([Request(rid=r.rid, prompt=r.prompt.copy(),
                               max_new=r.max_new) for r in reqs])
    out = eng.run(reqs)
    assert [r.out for r in out] == [r.out for r in ref]
    assert all(r.done for r in out)
    st = eng.last_stats
    assert st["preemptions"] == 1
    if mode == "park":
        assert st["parked"] == 1 and st["replayed"] == 0
    elif mode == "replay":
        assert st["replayed"] == 1 and st["parked"] == 0
    else:
        assert st["parked"] + st["replayed"] == 1
    assert ref_eng.last_stats["preemptions"] == 0


def test_ttft_and_queue_wait_reported():
    model, params = _model()
    eng = ServingEngine(model, params, batch=1, max_len=32,
                        cfg=ServeConfig(target="cpu"))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 100, size=4).astype(np.int32)
               for _ in range(3)]

    def run(n):
        reqs = [Request(rid=i, prompt=p, max_new=2)
                for i, p in enumerate(prompts[:n])]
        eng.run(reqs)
        return reqs, eng.last_stats

    run(3)                              # compiles: time the warm runs
    reqs, st = run(3)
    for k in ("ttft_p50", "ttft_p95", "queue_wait_p50", "queue_wait_p95",
              "itl_p50", "itl_p95"):
        assert k in st and st[k] >= 0.0
    # 3 requests through 1 slot: the later ones actually waited, and
    # each waited until its admission began, not until its first token
    assert st["queue_wait_p95"] >= st["queue_wait_p50"] > 0.0
    assert st["queue_wait_p50"] < st["ttft_p50"]
    assert st["queue_wait_p95"] < st["ttft_p95"]
    # one token time per output token; the one decode gap is the ITL
    assert [len(r.token_times) for r in reqs] == [len(r.out) for r in reqs]
    gaps = [r.token_times[1] - r.token_times[0] for r in reqs]
    assert st["itl_p50"] == float(np.median(gaps))
    # the first request admits at once: its wait (the slot cache's set-up,
    # ~0.3 of it on the CPU) is a part of its time to first token, which
    # holds its prefill
    _, st = run(1)
    assert st["queue_wait_p50"] < 0.75 * st["ttft_p50"]

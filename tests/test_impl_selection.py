"""Cost-model-driven implementation selection (the ISSUE 7 tentpole).

* cross-impl equivalence: every available candidate of every library op
  matches the reference numerics across GQA / causal / bias / decode
  (S=1) shapes
* forcing an impl via ``TapirConfig.force_impl`` really changes the
  lowered path (and unavailable/unknown names raise)
* the roofline argmin picks blockwise on a long-KV decode and the
  materialized score matrix on a tiny prefill (the two bench-gate
  regimes), and its repeat-vs-grouped arm never disagrees with
  ``pick_gqa_impl``
* scan chunks / schedule metadata: SAFE_CHUNK cap, impl in
  ``signature()``, ``dump_schedule``/``tapir.explain`` observability.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tapir
from repro.core.ir import TaskGraph, TensorType
from repro.core.schedule import (CPU_COST_MODEL, CostModel, IMPL_REGISTRY,
                                 attention_candidates, cost_model_for,
                                 pick_gqa_impl, pick_scan_chunk)
from repro.core.tapir import TapirConfig, clear_cache, trace_graph, use
from repro.kernels.linear_scan.ops import SAFE_CHUNK

TPU_CM = CostModel()


def setup_function(_):
    clear_cache()


def _cfg(impl=None, op="attention", backend="cpu"):
    return TapirConfig(mode="tapir", backend=backend,
                       force_impl=None if impl is None else ((op, impl),))


def _attn_graph(b, sq, skv, h, hkv, d, bias=False, causal=False,
                backend="cpu", cm=CPU_COST_MODEL, force=None):
    """Trace one attention node through the real pipeline (no execution)."""
    q = jnp.zeros((b, sq, h, d), jnp.float32)
    k = jnp.zeros((b, skv, hkv, d), jnp.float32)
    v = jnp.zeros((b, skv, hkv, d), jnp.float32)
    bb = jnp.zeros((b, h, sq, skv), jnp.float32) if bias else None
    with use(TapirConfig(mode="tapir", backend=backend, cost_model=cm)):
        g = tapir.capture_region(
            lambda q, k, v: tapir.attention(q, k, v, causal=causal, bias=bb),
            q, k, v)
        from repro.core.passes import run_pipeline
        run_pipeline(g, "tapir", cm, backend, force_impl=force)
    return g


def _attn_node(g):
    return next(n for n in g.nodes.values() if n.op == "attention")


# ---------------------------------------------------------------------------
# cross-impl equivalence: every candidate == reference numerics
# ---------------------------------------------------------------------------

_EQ_SHAPES = [
    # (label, b, sq, skv, h, hkv, causal, bias)
    ("gqa_prefill", 2, 32, 32, 8, 2, False, False),
    ("causal", 2, 32, 32, 4, 4, True, False),
    ("bias", 2, 16, 16, 4, 4, False, True),
    ("decode_s1", 2, 1, 128, 8, 2, False, False),
]


@pytest.mark.parametrize("label,b,sq,skv,h,hkv,causal,bias", _EQ_SHAPES)
def test_attention_all_impls_match_reference(label, b, sq, skv, h, hkv,
                                             causal, bias):
    d = 32
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (b, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, skv, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, skv, hkv, d))
    bb = 0.1 * jax.random.normal(jax.random.fold_in(key, 3),
                                 (b, h, sq, skv)) if bias else None

    def run(impl):
        clear_cache()
        with use(_cfg(impl)):
            return np.asarray(tapir.attention(q, k, v, causal=causal,
                                              bias=bb))

    # availability from the registry itself: every float-costed candidate
    g = _attn_graph(b, sq, skv, h, hkv, d, bias=bias, causal=causal)
    costs = _attn_node(g).schedule.impl_costs
    avail = [i for i, c in costs.items() if isinstance(c, float)]
    assert "ref" in avail and "materialized_grouped" in avail
    if bias:
        assert "blockwise" not in avail   # no bias operand on that path
    ref = run("ref")
    for impl in avail:
        got = run(impl)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{label}: {impl} != ref")


def test_linear_scan_all_impls_match_reference():
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (2, 48, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 48, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 48, 2, 16))
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.fold_in(key, 3),
                                           (2, 48, 2, 16))))
    u = jax.random.normal(jax.random.fold_in(key, 4), (2, 16))

    def run(impl):
        clear_cache()
        with use(_cfg(impl, op="linear_scan")):
            return np.asarray(tapir.wkv_scan(q, k, v, w, u))

    ref = run("ref")
    np.testing.assert_allclose(run("chunked"), ref, rtol=2e-3, atol=2e-3)


def test_matmul_einsum_impl_matches_default():
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (8, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    b = jax.random.normal(jax.random.fold_in(key, 2), (16,))
    clear_cache()
    with use(_cfg()):
        ref = np.asarray(tapir.linear(x, w, b, "gelu"))
    clear_cache()
    with use(_cfg("einsum", op="matmul")):
        got = np.asarray(tapir.linear(x, w, b, "gelu"))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# forcing an impl changes the lowered path; bad names raise
# ---------------------------------------------------------------------------


def test_force_impl_changes_lowered_path():
    b, sq, skv, h, hkv, d = 2, 16, 16, 4, 4, 32
    g_def = _attn_graph(b, sq, skv, h, hkv, d)
    # tiny prefill: the argmin is the materialized einsum...
    assert _attn_node(g_def).schedule.impl == "materialized_grouped"
    # ...forcing blockwise rebinds impl AND the lowered jaxpr now carries
    # the online-softmax lax.scan the materialized path doesn't have
    g_blk = _attn_graph(b, sq, skv, h, hkv, d,
                        force=(("attention", "blockwise"),))
    assert _attn_node(g_blk).schedule.impl == "blockwise"
    from repro.core.lowering import emit

    def jaxpr_of(g):
        args = {n: jnp.zeros(tuple(g.nodes[nid].ttype.shape),
                             g.nodes[nid].ttype.dtype)
                for n, nid in g.inputs}
        return str(jax.make_jaxpr(lambda a: emit(g, "cpu")(a))(args))

    assert "scan" in jaxpr_of(g_blk)
    assert "scan" not in jaxpr_of(g_def)


def test_force_impl_unavailable_raises():
    with pytest.raises(ValueError, match="unavailable"):
        _attn_graph(2, 16, 16, 4, 4, 32,
                    force=(("attention", "flash_kernel"),))  # CPU target


def test_force_impl_unknown_raises():
    with pytest.raises(ValueError, match="unknown impl"):
        _attn_graph(2, 16, 16, 4, 4, 32,
                    force=(("attention", "nonsense"),))


# ---------------------------------------------------------------------------
# the argmin picks the measured-winner regimes (bench-gate shapes)
# ---------------------------------------------------------------------------


def test_long_kv_decode_picks_blockwise_on_cpu():
    g = _attn_graph(4, 1, 8192, 8, 2, 64)
    n = _attn_node(g)
    assert n.schedule.impl == "blockwise"
    costs = n.schedule.impl_costs
    assert costs["blockwise"] < costs["materialized_grouped"]


def test_tiny_prefill_picks_materialized_on_cpu():
    g = _attn_graph(2, 16, 16, 4, 4, 32, causal=True)
    n = _attn_node(g)
    assert n.schedule.impl == "materialized_grouped"
    assert n.schedule.impl_costs["materialized_grouped"] \
        < n.schedule.impl_costs["blockwise"]


def test_tpu_prefill_picks_flash_kernel():
    g = _attn_graph(2, 128, 128, 8, 8, 64, backend="tpu", cm=TPU_CM)
    assert _attn_node(g).schedule.impl == "flash_kernel"


def test_tpu_decode_and_bias_fall_back_from_kernel():
    g = _attn_graph(2, 1, 4096, 8, 2, 64, backend="tpu", cm=TPU_CM)
    n = _attn_node(g)
    assert n.schedule.impl != "flash_kernel"
    assert isinstance(n.schedule.impl_costs["flash_kernel"], str)  # n/a
    g2 = _attn_graph(2, 64, 64, 4, 4, 32, bias=True, backend="tpu",
                     cm=TPU_CM)
    assert _attn_node(g2).schedule.impl == "ref"


def test_registry_repeat_vs_grouped_agrees_with_pick_gqa_impl():
    # the two shapes the GQA tests lock: CPU prefill -> repeat, CPU
    # decode against a very long cache -> grouped
    for shape, want in (((8, 256, 256, 8, 2, 64), "repeat"),
                        ((8, 1, 32768, 8, 2, 64), "grouped")):
        b, sq, skv, h, hkv, d = shape
        g = _attn_graph(b, sq, skv, h, hkv, d)
        n = _attn_node(g)
        assert pick_gqa_impl(n, CPU_COST_MODEL, "cpu") == want
        c = n.schedule.impl_costs
        if want == "repeat":
            assert c["materialized_repeat"] <= c["materialized_grouped"]
        else:
            assert c["materialized_grouped"] < c["materialized_repeat"]


def test_every_library_op_gets_an_impl_and_cost_table():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (8, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    with use(_cfg()):
        g = tapir.capture_region(lambda x: tapir.linear(x, w), x)
        from repro.core.passes import run_pipeline
        run_pipeline(g, "tapir", CPU_COST_MODEL, "cpu")
    mm = next(n for n in g.nodes.values() if n.op == "matmul")
    assert mm.schedule.impl == "einsum"   # no pallas GEMM off-TPU
    assert isinstance(mm.schedule.impl_costs["einsum"], float)
    assert set(IMPL_REGISTRY) == {"matmul", "attention", "linear_scan",
                                  "conv2d"}


# ---------------------------------------------------------------------------
# scan chunk derivation + schedule metadata
# ---------------------------------------------------------------------------


def test_scan_chunk_capped_at_safe_chunk_on_both_targets():
    for cm in (CPU_COST_MODEL, TPU_CM):
        assert pick_scan_chunk(128, 16, 16, "float32", cm) == SAFE_CHUNK
    # a starved VMEM budget shrinks the chunk below the numeric cap
    tiny = CostModel(name="tiny", vmem_bytes=1 << 12)
    assert pick_scan_chunk(128, 64, 64, "float32", tiny) < SAFE_CHUNK
    assert pick_scan_chunk(3, 16, 16, "float32", CPU_COST_MODEL) == 3


def test_impl_participates_in_graph_signature():
    g_a = _attn_graph(2, 16, 16, 4, 4, 32)
    g_b = _attn_graph(2, 16, 16, 4, 4, 32,
                      force=(("attention", "blockwise"),))
    assert g_a.signature() != g_b.signature()


def test_dump_schedule_and_explain():
    g = _attn_graph(4, 1, 8192, 8, 2, 64)
    txt = g.dump_schedule()
    assert "impl=blockwise" in txt and "costs:" in txt and "note:" in txt
    assert "n/a" in txt            # unavailable candidates stay visible
    assert tapir.explain(g) == txt
    clear_cache()
    assert "no compiled graphs" in tapir.explain()
    q = jnp.ones((2, 4, 4, 8)); k = jnp.ones((2, 4, 4, 8))
    with use(_cfg()):
        tapir.attention(q, k, k)
    assert "impl=" in tapir.explain()


def test_cost_model_follows_the_device_kind():
    assert cost_model_for("cpu") is CPU_COST_MODEL
    assert cost_model_for() is CPU_COST_MODEL     # tests compute on the CPU
    v5e = cost_model_for("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no cost model"):
        cost_model_for("TPU v99")       # its peaks would otherwise be assumed

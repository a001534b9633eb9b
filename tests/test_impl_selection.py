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
* paged decode attention: the Pallas kernel binds on the TPU target with
  no mesh; off it, under a mesh, and for shapes it cannot take, the
  gathered composite binds and the kernel shows ``n/a`` with its reason.
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
    assert set(IMPL_REGISTRY) == {"matmul", "attention", "paged_attention",
                                  "linear_scan", "ssm_state_step", "conv2d"}


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


# ---------------------------------------------------------------------------
# fused GEMM row tile: picked from the rows the kernel sees
# ---------------------------------------------------------------------------

_V5E = cost_model_for("TPU v5 lite")
_ROW_TILE_CASES = [
    # (label, activation, weight, expected tile; None: one row block)
    ("granite_in_proj_decode", (96, 1, 4096), (4096, 16768), None),
    ("qwen_head_decode", (16, 1, 2048), (2048, 151936), None),
    ("prefill", (1, 4096, 2048), (2048, 2048),
     {"bm": 512, "bn": 512, "bk": 2048}),
    ("train", (2, 2048, 2048), (2048, 2048),
     {"bm": 512, "bn": 512, "bk": 2048}),
]


def _scheduled_matmul(xs, ws, site):
    """Capture one bf16 linear for the TPU target (shapes only, nothing
    runs) and bind its schedule at ``site``: the compile pipeline's
    ``assign_schedules`` or the derivation-time ``core.autodiff`` one."""
    from repro.core.autodiff import _resolve_library_schedule
    from repro.core.passes import optimize_graph, run_pipeline
    x = jax.ShapeDtypeStruct(xs, jnp.bfloat16)
    w = jax.ShapeDtypeStruct(ws, jnp.bfloat16)
    with use(TapirConfig(mode="tapir", backend="tpu", cost_model=_V5E)):
        g = tapir.capture_region(lambda x, w: tapir.linear(x, w), x, w)
    if site == "assign_schedules":
        run_pipeline(g, "tapir", _V5E, "tpu")
    else:
        optimize_graph(g, _V5E)
    node = next(n for n in g.nodes.values() if n.op == "matmul")
    if site == "autodiff":
        _resolve_library_schedule(g, node, _V5E, "tpu", {}, {})
    return g, node


@pytest.mark.parametrize("site", ["assign_schedules", "autodiff"])
@pytest.mark.parametrize("label,xs,ws,tile", _ROW_TILE_CASES,
                         ids=[c[0] for c in _ROW_TILE_CASES])
def test_fused_gemm_row_tile_covers_kernel_rows(site, label, xs, ws, tile):
    """A [slots, 1, d] decode GEMM is one row block of the fused kernel
    (its weight streams from HBM once a call); prefill and training keep
    the tiles they had, since their first row tile was already the cap."""
    g, node = _scheduled_matmul(xs, ws, site)
    assert node.schedule.impl == "fused_kernel"
    rows = int(np.prod(xs[:-1]))
    if tile is None:
        assert node.schedule.tile["bm"] == rows
        note = f"weight passes: 1 (rows {rows}, bm {rows})"
    else:
        assert node.schedule.tile == tile
        note = f"weight passes: {rows // 512} (rows {rows}, bm 512)"
    assert note in node.schedule.notes
    if site == "assign_schedules":
        assert note in tapir.explain(g)
    else:   # both sites take the same argmin
        _, bound = _scheduled_matmul(xs, ws, "assign_schedules")
        assert node.schedule.tile == bound.schedule.tile


# ---------------------------------------------------------------------------
# paged decode attention: the Pallas kernel reading pages in place vs the
# gathered-view composite
# ---------------------------------------------------------------------------


def _paged_graph(backend="cpu", cm=CPU_COST_MODEL, force=None, mesh_axes=None,
                 hd=128, slots=4, pps=4, page_len=64):
    """Trace one paged_attention node (the serving decode shape: 16/2
    heads) and schedule it for ``backend``, optionally under a mesh."""
    from repro.core.passes import optimize_graph
    from repro.core.schedule import assign_schedules
    q = jnp.zeros((slots, 1, 16, hd), jnp.bfloat16)
    pool = jnp.zeros((slots * pps + 1, page_len, 2, hd), jnp.bfloat16)
    ptab = jnp.zeros((slots, pps), jnp.int32)
    lens = jnp.ones((slots,), jnp.int32)
    with use(TapirConfig(mode="tapir", backend=backend, cost_model=cm)):
        g = tapir.capture_region(tapir.paged_attention, q, pool, pool, ptab,
                                 lens)
    optimize_graph(g, cm)
    assign_schedules(g, cm, backend=backend, mesh_axes=mesh_axes,
                     force_impl=force)
    return g


def _paged_node(g):
    return next(n for n in g.nodes.values() if n.op == "paged_attention")


def test_paged_kernel_bound_on_tpu_without_mesh():
    n = _paged_node(_paged_graph("tpu", TPU_CM))
    assert n.schedule.impl == "paged_kernel"
    costs = n.schedule.impl_costs
    assert costs["paged_kernel"] < costs["gathered"]
    # the view's read against its read, write and re-read
    assert costs["gathered"] > 2.5 * costs["paged_kernel"]
    assert n.schedule.tile == {"pages_per_block": 4}


@pytest.mark.parametrize("backend,mesh_axes,hd,why", [
    ("cpu", None, 128, "pallas kernel needs the TPU target"),
    ("tpu", {"data": 1, "model": 4}, 128, "GSPMD cannot partition"),
    ("tpu", None, 64, "head size 64"),
])
def test_paged_kernel_na_binds_gathered(backend, mesh_axes, hd, why):
    cm = TPU_CM if backend == "tpu" else CPU_COST_MODEL
    n = _paged_node(_paged_graph(backend, cm, mesh_axes=mesh_axes, hd=hd))
    assert n.schedule.impl == "gathered"
    na = n.schedule.impl_costs["paged_kernel"]
    assert isinstance(na, str) and na.startswith("n/a") and why in na
    assert isinstance(n.schedule.impl_costs["gathered"], float)


def test_paged_force_impl_changes_lowered_jaxpr_and_signature():
    from repro.core.lowering import emit

    def jaxpr_of(g):
        args = {n: jnp.zeros(tuple(g.nodes[nid].ttype.shape),
                             g.nodes[nid].ttype.dtype)
                for n, nid in g.inputs}
        return str(jax.make_jaxpr(lambda a: emit(g, "tpu")(a))(args))

    g_k = _paged_graph("tpu", TPU_CM,
                       force=(("paged_attention", "paged_kernel"),))
    g_g = _paged_graph("tpu", TPU_CM,
                       force=(("paged_attention", "gathered"),))
    assert _paged_node(g_g).schedule.impl == "gathered"
    j_k, j_g = jaxpr_of(g_k), jaxpr_of(g_g)
    assert "pallas_call" in j_k and "gather" not in j_k
    assert "pallas_call" not in j_g and "gather" in j_g
    # the op and its bound impl enter the program's identity
    assert g_k.signature() != g_g.signature()
    assert "paged_attention" in str(g_k.signature())
    with pytest.raises(ValueError, match="unavailable"):
        _paged_graph("cpu", force=(("paged_attention", "paged_kernel"),))


def test_paged_node_in_explain():
    txt = tapir.explain(_paged_graph("tpu", TPU_CM))
    assert "paged_attention" in txt and "impl=paged_kernel" in txt
    assert "paged_kernel=" in txt and "gathered=" in txt
    assert "pages_per_block" in txt


def test_slot_decode_region_binds_paged_op():
    """The model's slot decode block captures decode attention as one
    ``paged_attention`` library node, which the CPU target binds to the
    gathered composite."""
    import dataclasses

    import repro.configs as C
    from repro.models.base import get_model
    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                              compute_dtype="float32")
    model = get_model(cfg)
    sp = model.slot_params(model.init_params(jax.random.PRNGKey(0)))
    cache = model.init_slot_cache(2, 128)
    with use(_cfg()):
        logits, _ = model.decode_step_slots(
            sp, jnp.ones((2, 1), jnp.int32), cache)
    graphs = [g for g in tapir.cached_graphs().values()
              if g.name.startswith("slot_dense_block")]
    assert graphs
    for g in graphs:
        ops = [n.op for n in g.nodes.values()]
        assert ops.count("paged_attention") == 1
        assert _paged_node(g).schedule.impl == "gathered"
    assert np.isfinite(np.asarray(logits)).all()

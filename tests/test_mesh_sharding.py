"""Sharding-aware region IR: constraints captured by the tracer must ride
through the pass pipeline, replay at lowering, and compose with the
slot-paged serving engine on a TP mesh.

* CSE — nodes with conflicting ``sharding`` annotations never unify;
* ``_cfg_key`` — the FULL mesh fingerprint keys compiled programs: two
  meshes that both "have a model axis" must not replay each other
  (regression for the stale-program hazard);
* region capture under a 2x4 ``(data, model)`` mesh — forward and slot
  decode bitwise-match their single-device counterparts, for the dense
  AND MoE families;
* the constraints are OBSERVABLE in the lowered computation (annotation
  on the optimized graph + a ``sharding_constraint`` in the emitted
  jaxpr);
* ``_PROGRAMS`` — misses (recompiles) when the mesh changes, hit rate 1
  across occupancy changes on a fixed mesh.
"""
from conftest import run_mesh_subprocess

from repro.core.ir import TaskGraph, TensorType
from repro.core.passes.cse import cse


# ---------------------------------------------------------------------------
# IR-level (no devices needed)
# ---------------------------------------------------------------------------


def _twin_ew_graph(spec_a, spec_b):
    g = TaskGraph("shard_cse")
    t = TensorType((4, 8), "float32")
    x = g.add_input("x", t)
    a = g.add("ew", (x,), t, pdims=(0, 1), fn="tanh", sharding=spec_a)
    b = g.add("ew", (x,), t, pdims=(0, 1), fn="tanh", sharding=spec_b)
    g.set_outputs([a, b])
    return g


def test_cse_refuses_conflicting_shardings():
    g = _twin_ew_graph(("data", None), ("model", None))
    assert cse(g) == 0, "conflicting shardings must not unify"
    assert len([n for n in g.nodes.values() if n.op == "ew"]) == 2

    g = _twin_ew_graph(("data", None), None)
    assert cse(g) == 0, "constrained vs unconstrained must not unify"

    g = _twin_ew_graph(("data", None), ("data", None))
    assert cse(g) == 1, "equal shardings are compatible — must unify"

    g = _twin_ew_graph(None, None)
    assert cse(g) == 1


def test_gqa_choice_is_per_shard_aware():
    """Sharded cost model: per-device compute divides by the full shard
    factor, but the K/V repeat-copy only shrinks along dims where K/V
    itself partitions — q-heads-over-model with replicated KV (Hkv
    indivisible) must flip repeat -> grouped, while batch sharding
    (copy and compute shrink together) must not change the choice."""
    import dataclasses

    from repro.core.ir import Node
    from repro.core.schedule import CPU_COST_MODEL, pick_gqa_impl

    n = Node(0, "attention", (), TensorType((8, 16, 8, 64), "float32"),
             {"q_shape": (8, 16, 8, 64), "kv_len": 256, "kv_heads": 2})
    assert pick_gqa_impl(n, CPU_COST_MODEL, "cpu") == "repeat"
    heads = dataclasses.replace(n, sharding=(None, None, "model", None))
    assert pick_gqa_impl(heads, CPU_COST_MODEL, "cpu",
                         mesh_axes={"model": 4}) == "grouped"
    batch = dataclasses.replace(n, sharding=("data", None, None, None))
    assert pick_gqa_impl(batch, CPU_COST_MODEL, "cpu",
                         mesh_axes={"data": 4}) == "repeat"


def test_fuse_added_gemms_refuses_constrained_members():
    """A member GEMM whose output carries a sharding constraint must not
    vanish into a fused concat-GEMM (the constraint would be silently
    dropped) — the pass refuses, like CSE."""
    from repro.core.passes.fusion import fuse_added_gemms

    def build(member_sharding):
        g = TaskGraph("fa")
        xa = g.add_input("xa", TensorType((4, 8), "float32"))
        xb = g.add_input("xb", TensorType((4, 8), "float32"))
        wa = g.add_input("wa", TensorType((8, 16), "float32"))
        wb = g.add_input("wb", TensorType((8, 16), "float32"))
        out_t = TensorType((4, 16), "float32")
        ma = g.add("matmul", (xa, wa), out_t, pdims=(0, 1),
                   rdims=(("k", 8),), k=8, exposed=True,
                   sharding=member_sharding)
        mb = g.add("matmul", (xb, wb), out_t, pdims=(0, 1),
                   rdims=(("k", 8),), k=8, exposed=True)
        add = g.add("ew", (ma, mb), out_t, pdims=(0, 1), fn="add")
        g.set_outputs([add])
        return g

    assert fuse_added_gemms(build(None)) == 1
    g = build(("model", None))
    assert fuse_added_gemms(g) == 0, \
        "fusing would drop the member's sharding constraint"
    assert any(n.sharding == ("model", None) for n in g.nodes.values())


def test_sharding_in_node_key_and_signature():
    a = _twin_ew_graph(("data", None), ("data", None))
    b = _twin_ew_graph(("model", None), ("model", None))
    assert a.signature() != b.signature()
    n = a.nodes[1]
    assert n.key() != b.nodes[1].key()


# ---------------------------------------------------------------------------
# mesh fingerprint in the program keys (stale-program regression)
# ---------------------------------------------------------------------------


def test_cfg_key_fingerprints_full_mesh_shape():
    res = run_mesh_subprocess("""
        from repro.core.tapir import TapirConfig, _cfg_key
        from repro.launch.mesh import make_test_mesh
        cfg = TapirConfig(mode="tapir")
        k0 = _cfg_key(cfg, "cpu")
        with jax.set_mesh(make_test_mesh(data=2, model=4)):
            k1 = _cfg_key(cfg, "cpu")
        with jax.set_mesh(make_test_mesh(data=4, model=2)):
            k2 = _cfg_key(cfg, "cpu")   # ALSO has a model axis
        with jax.set_mesh(make_test_mesh(data=2, model=4)):
            k1b = _cfg_key(cfg, "cpu")
        result["all_distinct"] = len({k0, k1, k2}) == 3
        result["stable"] = k1 == k1b
    """)
    assert res["all_distinct"], \
        "two model-axis meshes of different shape collided in _cfg_key"
    assert res["stable"]


def test_programs_miss_on_mesh_change_hit_on_occupancy():
    res = run_mesh_subprocess("""
        import repro.configs as C
        from repro.models.base import get_model
        from repro.serve import ServeConfig
        from repro.core.tapir import cache_stats, use
        from repro.launch.mesh import make_test_mesh

        cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                                  compute_dtype="float32")
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        mesh = make_test_mesh(data=2, model=4)
        with jax.set_mesh(mesh), use(ServeConfig(target="cpu").tapir_config()):
            sp = model.slot_params(params)
            cache = model.init_slot_cache(2, 32)
            toks = jnp.asarray(rng.integers(1, 100, (1, 8)), jnp.int32)
            _, cache = model.prefill_into_slot(sp, toks, cache, 0, 6)
            step = jnp.asarray(rng.integers(1, 100, (2, 1)), jnp.int32)
            _, cache = model.decode_step_slots(sp, step, cache)
            miss0 = cache_stats()["misses"]
            # occupancy changes on the FIXED mesh: admit, decode, free
            _, cache = model.prefill_into_slot(sp, toks, cache, 1, 5)
            for _ in range(3):
                _, cache = model.decode_step_slots(sp, step, cache)
            cache["pos"] = cache["pos"].at[0].set(0)
            _, cache = model.decode_step_slots(sp, step, cache)
            result["occupancy_misses"] = cache_stats()["misses"] - miss0
            result["hits"] = cache_stats()["hits"]
        # a different mesh shape must RECOMPILE, not replay
        m_before = cache_stats()["misses"]
        with jax.set_mesh(make_test_mesh(data=4, model=2)), \\
                use(ServeConfig(target="cpu").tapir_config()):
            cache2 = model.init_slot_cache(2, 32)
            _, cache2 = model.decode_step_slots(sp, step, cache2)
        result["mesh_change_misses"] = cache_stats()["misses"] - m_before
    """)
    assert res["occupancy_misses"] == 0, \
        "occupancy change on a fixed mesh must replay, not re-trace"
    assert res["hits"] > 0
    assert res["mesh_change_misses"] > 0, \
        "a mesh change must recompile — replaying would execute programs " \
        "whose constraints were resolved for the wrong axis sizes"


# ---------------------------------------------------------------------------
# mesh == single device: the model split bitwise, greedy tokens bitwise
# ---------------------------------------------------------------------------


def test_region_forward_on_mesh_matches_single_device():
    res = run_mesh_subprocess("""
        import repro.configs as C
        from repro.models.base import get_model
        from repro.core.tapir import (TapirConfig, use, clear_cache,
                                      cached_graphs)
        from repro.launch.mesh import make_test_mesh

        cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                                  compute_dtype="float32",
                                  param_dtype="float32")
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(1, 100, (4, 16)),
                                       jnp.int32)}
        with use(TapirConfig(mode="tapir")):
            ref = np.asarray(model.forward(params, batch))
        outs = {}
        for d, m in ((2, 1), (2, 4)):
            clear_cache()
            with jax.set_mesh(make_test_mesh(data=d, model=m)), \\
                    use(TapirConfig(mode="tapir")):
                outs[m] = np.asarray(model.forward(params, batch))
        result["annotated"] = sum(
            1 for g in cached_graphs().values()
            for n in g.nodes.values() if n.sharding)
        got = outs[4]
        # the same mesh program without the model split
        result["tp_bitwise"] = bool(np.array_equal(got, outs[1]))
        result["bitwise"] = bool(np.array_equal(ref, got))
        result["rel_diff"] = float(np.max(np.abs(ref - got))
                                   / np.max(np.abs(ref)))
        result["greedy_equal"] = bool(np.array_equal(ref.argmax(-1),
                                                     got.argmax(-1)))
        # does a GEMM give a column block the same bits as the whole
        # product?  (the stacked and concatenated QKV / gate-up shapes
        # compute the same columns at different N)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
        w = params["blocks"]["wg"][0]
        n = w.shape[1] // 4
        mm = jax.jit(jnp.matmul)
        result["dot_shape_stable"] = bool(np.array_equal(
            np.asarray(mm(x, w))[:, :n], np.asarray(mm(x, w[:, :n]))))
    """)
    assert res["annotated"] > 0, "no sharding constraint reached the mesh"
    # the tensor-parallel split itself moves no bits
    assert res["tp_bitwise"], "the model split changed the mesh forward"
    assert res["greedy_equal"], f"mesh forward diverged: {res['rel_diff']}"
    # Against one device without a mesh the logits are bitwise only where
    # GEMM bits do not depend on N: a model axis makes fuse_shared_input
    # stack the QKV / gate-up weights instead of concatenating them, and
    # XLA:CPU's f32 GEMM at these widths is not shape-stable
    if res["dot_shape_stable"]:
        assert res["bitwise"], f"mesh forward diverged: {res['rel_diff']}"
    assert res["rel_diff"] <= 1e-5, \
        f"mesh forward beyond f32 rounding: {res['rel_diff']}"


def _slot_engine_body(arch: str) -> str:
    return f"""
        import repro.configs as C
        from repro.models.base import get_model
        from repro.serve import Request, ServeConfig, ServingEngine
        from repro.core.tapir import clear_cache, cached_graphs
        from repro.launch.mesh import make_test_mesh

        cfg = dataclasses.replace(C.get_smoke("{arch}"),
                                  compute_dtype="float32")
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        lens, news = [6, 4, 7, 5, 6], [4, 8, 6, 5, 7]
        prompts = [rng.integers(1, 100, size=n).astype(np.int32)
                   for n in lens]
        def mk():
            return [Request(rid=i, prompt=p.copy(), max_new=m)
                    for i, (p, m) in enumerate(zip(prompts, news))]

        eng = ServingEngine(model, params, batch=2, max_len=32,
                            cfg=ServeConfig(target="cpu"))
        ref = eng.run(mk())

        clear_cache()
        mesh = make_test_mesh(data=2, model=4)
        eng_m = ServingEngine(model, params, mesh=mesh, batch=2,
                              max_len=32, cfg=ServeConfig(target="cpu"))
        # the mesh fallback is gone: slot path, not padded waves
        result["slot_path"] = bool(eng_m._slot_capable)
        eng_m._run_padded_waves = None   # would raise if ever taken
        out = eng_m.run(mk())
        result["bitwise"] = all(a.out == b.out and a.done and b.done
                                for a, b in zip(ref, out))
        result["stats"] = {{k: float(v)
                            for k, v in eng_m.last_stats.items()}}
        # sharding constraints were captured on the mesh programs
        result["annotated"] = sum(
            1 for g in cached_graphs().values()
            for n in g.nodes.values() if n.sharding)
    """


def test_slot_serving_on_mesh_bitwise_dense():
    res = run_mesh_subprocess(_slot_engine_body("qwen2_5_3b"))
    assert res["slot_path"], "mesh serving must use the slot path"
    assert res["bitwise"], "mesh slot outputs diverged from single device"
    assert res["annotated"] > 0, \
        "mesh slot programs captured no sharding annotations"
    assert res["stats"]["admitted"] == 5 and res["stats"]["tokens"] == 30


def test_slot_serving_on_mesh_bitwise_moe():
    res = run_mesh_subprocess(_slot_engine_body("moonshot_v1_16b_a3b"),
                              timeout=580)
    assert res["slot_path"] and res["bitwise"]
    assert res["annotated"] > 0


# ---------------------------------------------------------------------------
# the replayed constraint is observable in the lowered computation
# ---------------------------------------------------------------------------


def test_captured_constraint_observable_in_lowered_computation():
    res = run_mesh_subprocess("""
        import repro.configs as C
        from repro.core import tapir
        from repro.core.lowering import emit
        from repro.core.tapir import TapirConfig, use
        from repro.launch.mesh import make_test_mesh
        from repro.models import layers as L
        from repro.models.base import get_model

        cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                                  compute_dtype="float32")
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        p0 = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32),
                                    params["blocks"])
        mesh = make_test_mesh(data=2, model=4)
        with jax.set_mesh(mesh), use(TapirConfig(mode="tapir")):
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (2, 1, cfg.d_model))
            # page-pool layout: 2 slots x 1 page (pl=32) + trash + shared
            ck = jnp.zeros((5, 32, cfg.n_kv_heads, cfg.hd), jnp.float32)
            cv = jnp.zeros_like(ck)
            pos = jnp.asarray([3, 0], jnp.int32)
            ptab = jnp.asarray([[1], [2]], jnp.int32)
            cos_t, sin_t = L.full_rope_table(32, cfg.hd)
            g = tapir.trace_region(model._slot_block_body, p0, x,
                                   cos_t, sin_t, ck, cv, pos, ptab)
            ann = [list(n.sharding) for n in g.nodes.values()
                   if n.sharding]
            result["n_annotated"] = len(ann)
            # heads dim of q rides over "model" (4 divides n_heads=4)
            result["model_constrained"] = any("model" in a for a in ann)
            fn = emit(g, "cpu")
            inputs = {name: jnp.zeros(g.nodes[nid].ttype.shape,
                                      g.nodes[nid].ttype.dtype)
                      for name, nid in g.inputs}
            jaxpr = str(jax.make_jaxpr(lambda d: fn(d))(inputs))
            result["constraint_in_jaxpr"] = "sharding_constraint" in jaxpr
    """)
    assert res["n_annotated"] >= 3, \
        "q/scatter/output constraints must survive the pass pipeline"
    assert res["model_constrained"], \
        "no annotation references the model axis"
    assert res["constraint_in_jaxpr"], \
        "lowering must replay annotations as with_sharding_constraint"

"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e:2x2
host and XLA's TPU compiler compiles for its first device.  What the TPU
compiler refuses (block shapes off the (8, 128) tiling, VMEM overruns,
Mosaic lowering gaps) fails here instead of on the chip.  Shapes are the
published widths of qwen2_5_3b (d_model 2048, d_ff 11008, 16/2 heads of
128), of rwkv6_7b's scan (64 heads of 64) and of granite-4.0-h-small's
Mamba2 state (128 heads of 64 x 128).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tapir
from repro.core.autodiff import _make_vjp_fn
from repro.core.lowering import node_callable
from repro.core.passes import run_pipeline
from repro.core.schedule import (cost_model_for, pick_attention_tiles,
                                 pick_matmul_tiles)
from repro.core.tapir import TapirConfig, use
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_matmul import ops as fm_ops
from repro.kernels.linear_scan import ops as ls_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.ssm_state_step import ops as ss_ops

V5E = cost_model_for("TPU v5 lite")
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to jax's persistent
    # cache but cannot be read back without the chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *sds):
    compiled = jax.jit(fn).lower(*sds).compile()
    assert "tpu_custom_call" in compiled.as_text(), "Pallas kernel missing"
    return compiled


@pytest.mark.parametrize("m,k,n,epilogue", [
    (8, 2048, 2048, "row"),          # decode: 4-8 slots, bias epilogue
    (2048, 2048, 11008, "none"),     # prefill: the MLP up-projection
    (96, 4096, 16768, "none"),       # decode: granite's Mamba2 in-projection
                                     # at 96 slots, one row block of 96
])
def test_fused_matmul_compiles(one_chip, m, k, n, epilogue):
    tile = pick_matmul_tiles(m, n, k, "bfloat16", V5E)
    args = [_sds((m, k), BF16, one_chip), _sds((k, n), BF16, one_chip)]
    if epilogue == "row":
        args.append(_sds((n,), BF16, one_chip))

    def f(x, w, *b):
        epi = [("add", [b[0]], {})] if b else []
        return fm_ops.fused_matmul(x, w, epilogue=epi, tile=tile,
                                   out_dtype="bfloat16")

    _compile(f, *args)


def test_flash_attention_compiles(one_chip):
    s, hq, hkv, d = 2048, 16, 2, 128
    tile = pick_attention_tiles(s, s, d, "bfloat16", V5E)
    q = _sds((1, s, hq, d), BF16, one_chip)
    kv = _sds((1, s, hkv, d), BF16, one_chip)

    def f(q, k, v):
        return fa_ops.flash_attention(q, k, v, causal=True,
                                      block_q=tile["bq"],
                                      block_kv=tile["bkv"])

    _compile(f, q, kv, kv)


def test_linear_scan_compiles(one_chip):
    b, s, h, d = 1, 512, 64, 64
    t = _sds((b, s, h, d), BF16, one_chip)
    u = _sds((h, d), jnp.float32, one_chip)

    def f(q, k, v, w, u):
        return ls_ops.linear_scan(q, k, v, w, u=u, chunk=ls_ops.SAFE_CHUNK)

    _compile(f, t, t, t, t, u)


def test_paged_attention_compiles_in_place(one_chip):
    """The decode region's pattern at published widths: 16 slots of 64
    pages of 64 positions, 16/2 heads of 128, bf16.  The step writes one
    row into each pool and the kernel reads the pools through the page
    table; the pools are neither copied nor gathered on the way (the
    kernel's head-interleaved view is a bitcast of the pool)."""
    import re
    slots, pps, page_len, hq, hkv, d = 16, 64, 64, 16, 2, 128
    n_pages = slots * pps + 1
    pool = _sds((n_pages, page_len, hkv, d), BF16, one_chip)
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)   # noqa: E731

    def step(q, ck, cv, ptab, lens, knew, phys, off):
        ck = ck.at[phys, off].set(knew)
        cv = cv.at[phys, off].set(knew)
        return pa_ops.paged_attention(q, ck, cv, ptab, lens), ck, cv

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        _sds((slots, 1, hq, d), BF16, one_chip), pool, pool,
        i32(slots, pps), i32(slots), _sds((slots, hkv, d), BF16, one_chip),
        i32(slots), i32(slots)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " gather(" not in text
    pool_hlo = f"bf16[{n_pages},{page_len},{hkv},{d}]"
    assert not re.search(rf"= {re.escape(pool_hlo)}\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_slot_decode_binds_paged_kernel_on_v5e(one_chip):
    """One slot-decode step of a layer with qwen2_5_3b's attention (16/2
    heads of 128; 16 slots x 4096 in 64-position pages), cache donated as
    the engine's region programs donate it: the registry binds the paged
    kernel, and the program neither copies nor gathers a page pool."""
    import dataclasses
    import re

    import repro.configs as C
    from repro.models import layers as L
    from repro.models.base import get_model

    cfg = dataclasses.replace(
        C.get_smoke("qwen2_5_3b"), d_model=2048, n_heads=16, n_kv_heads=2,
        head_dim=128, d_ff=512, vocab=512, n_layers=1,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    model = get_model(cfg)
    slots, max_len = 16, 4096

    def layers_and_head():
        sp = model.slot_params(model.init_params(jax.random.PRNGKey(0)))
        return {**{k: v for k, v in sp.items() if k != "layers"},
                "layers": [d for _, d in sp["layers"]]}

    def step(lh, tokens, cache):
        sp = {**lh, "layers": [("dense", d) for d in lh["layers"]]}
        return model.decode_step_slots(sp, tokens, cache)

    cache = model.slot_cache_specs(slots, max_len)
    L.full_rope_table(max_len, cfg.hd, fraction=model._rope_frac())
    sds = lambda tree: jax.tree_util.tree_map(   # noqa: E731
        lambda v: _sds(v.shape, v.dtype, one_chip), tree)
    tapir.clear_cache()
    with use(TapirConfig(mode="tapir", backend="tpu", cost_model=V5E)):
        text = jax.jit(step, donate_argnums=(2,)).lower(
            sds(jax.eval_shape(layers_and_head)),
            _sds((slots, 1), jnp.int32, one_chip), sds(cache)
        ).compile().as_text()
        report = tapir.explain()
    tapir.clear_cache()
    assert "paged_attention" in report and "impl=paged_kernel" in report
    assert "paged_attention" in text and "tpu_custom_call" in text
    pool = cache["k"][0]
    pool_hlo = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    assert not re.search(rf"= {re.escape(pool_hlo)}\S* (copy|gather)\(",
                         text)
    assert not re.search(r"= bf16\[16,64,64,2,128\]\S* gather\(", text)


def test_fused_kernel_node_reverse_mode_compiles(one_chip):
    """The captured training step differentiates each node's own lowering
    (``core.autodiff``): here one GEMM node bound to ``fused_kernel`` with
    a bias epilogue, through the same VJP rule, in one program with the
    node's forward as the step has it."""
    m, k, n = 512, 2048, 2048
    zeros = [jnp.zeros((m, k), BF16), jnp.zeros((k, n), BF16),
             jnp.zeros((n,), BF16)]
    with use(TapirConfig(mode="tapir", backend="tpu", cost_model=V5E)):
        g = tapir.capture_region(lambda x, w, b: tapir.linear(x, w, b),
                                 *zeros)
        run_pipeline(g, "tapir", V5E, "tpu")
    node = next(nd for nd in g.nodes.values() if nd.op == "matmul")
    assert node.schedule.impl == "fused_kernel"
    call = node_callable(node, "tpu")
    operands = [g.nodes[o].ttype for o in call.operands]
    vjp = _make_vjp_fn(call, tuple(range(len(operands))), "store")
    ct = _sds(node.ttype.shape, node.ttype.dtype, one_chip)
    sds = [_sds(t.shape, t.dtype, one_chip) for t in operands]
    compiled = _compile(lambda c, *v: (call(*v), vjp(c, *v)), ct, *sds)
    assert "convolution" in compiled.as_text()    # the backward GEMMs


def test_slot_decode_on_v5e_mesh_has_no_partial_sums(v5e_devices):
    """Slot decode under a (data=1, model=4) v5e mesh with the engine's
    weight layout (``slot_param_shardings``): the column-split projections
    (the 2 KV heads' columns included) are gathered before every
    contraction that follows them, so no GEMM reduces over a split K and
    the program holds no all-reduce of partial sums."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh

    import repro.configs as C
    from repro.models import layers as L
    from repro.models.base import get_model
    from repro.serve.engine import slot_cache_shardings, slot_param_shardings

    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                              param_dtype="bfloat16")
    assert cfg.n_kv_heads % 4 != 0      # KV heads cannot split over model=4
    model = get_model(cfg)
    slots, max_len = 4, 256
    sp = model.slot_params(model.init_params(jax.random.PRNGKey(0)))
    kinds = [k for k, _ in sp["layers"]]
    cache = model.slot_cache_specs(slots, max_len)
    # the memoized RoPE table is built eagerly, as the engine does
    L.full_rope_table(cache["ptab"].shape[1] * cache["k"][0].shape[1],
                      cfg.hd, fraction=model._rope_frac())
    mesh = Mesh(np.array(v5e_devices[:4]).reshape(1, 4), ("data", "model"))
    p_sh = slot_param_shardings(model, sp, mesh)
    p_sds = {**jax.tree_util.tree_map(
        lambda v, sh: _sds(v.shape, v.dtype, sh),
        {k: v for k, v in sp.items() if k != "layers"},
        {k: v for k, v in p_sh.items() if k != "layers"}),
        "layers": [jax.tree_util.tree_map(
            lambda v, sh: _sds(v.shape, v.dtype, sh), d, dsh)
            for (_, d), (_, dsh) in zip(sp["layers"], p_sh["layers"])]}
    c_sds = jax.tree_util.tree_map(
        lambda v, sh: _sds(v.shape, v.dtype, sh), cache,
        slot_cache_shardings(model, mesh, slots, max_len),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    def step(layers_and_head, tokens, cache):
        sp = {**layers_and_head,
              "layers": list(zip(kinds, layers_and_head["layers"]))}
        return model.decode_step_slots(sp, tokens, cache)

    tapir.clear_cache()
    with jax.set_mesh(mesh), \
            use(TapirConfig(mode="tapir", backend="tpu", cost_model=V5E)):
        text = jax.jit(step).lower(
            p_sds, jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            c_sds).compile().as_text()
    tapir.clear_cache()
    assert p_sh["layers"][0][1]["wk"].spec == (None, "model")
    assert p_sh["layers"][0][1]["wd"].spec == (None, None)
    ops = re.findall(r" (all-reduce|reduce-scatter|all-gather)(?:-start)?\(",
                     text)
    assert "all-gather" in ops, "nothing was split over the mesh"
    assert "all-reduce" not in ops and "reduce-scatter" not in ops


def test_ssm_state_step_compiles_in_place_at_published_widths(one_chip):
    """granite-4.0-h-small's decode state update: 96 slots (and 12 park
    rows) of 128 heads x 64 x 128 f32 state, updated in place: the state
    buffer is aliased, not copied."""
    S, R, H, P, N = 96, 108, 128, 64, 128
    g = ss_ops.heads_per_row(H, P)
    f32 = jnp.float32
    compiled = jax.jit(ss_ops.ssm_state_step, donate_argnums=0).lower(
        _sds((R, H // g, N, g * P), f32, one_chip),
        _sds((S, H), f32, one_chip), _sds((S, H, P), f32, one_chip),
        _sds((S, N), f32, one_chip), _sds((S, N), f32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    state = R * H * P * N * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= state
    assert compiled.memory_analysis().temp_size_in_bytes < state // 4


def test_mamba_slot_decode_binds_the_state_kernel_on_v5e(one_chip):
    """One decode step of a Mamba2 + MoE layer of granite-4.0-h-small
    (d_model 4096, 128 heads of 64, state 128; 2 of 72 experts held to
    keep the compile short) for 96 slots: the registry binds the state
    kernel, explain names the experts held, and every fused GEMM reads
    its weight once."""
    import dataclasses
    import re

    import repro.configs as C
    from repro.models.base import get_model

    cfg = dataclasses.replace(
        C.get_config("granite_4_0_h_small"), n_layers=1,
        layer_types=("mamba",), expert_range=(0, 2), vocab=512,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    model = get_model(cfg)
    slots, max_len = 96, 4096

    def layers_and_head():
        sp = model.slot_params(model.init_params(jax.random.PRNGKey(0)))
        return {**{k: v for k, v in sp.items() if k != "layers"},
                "layers": [d for _, d in sp["layers"]]}

    def step(lh, tokens, cache):
        sp = {**lh, "layers": [("mamba", d) for d in lh["layers"]]}
        return model.decode_step_slots(sp, tokens, cache)

    cache = model.slot_cache_specs(slots, max_len)
    sds = lambda tree: jax.tree_util.tree_map(   # noqa: E731
        lambda v: _sds(v.shape, v.dtype, one_chip), tree)
    tapir.clear_cache()
    with use(TapirConfig(mode="tapir", backend="tpu", cost_model=V5E)):
        text = jax.jit(step, donate_argnums=(2,)).lower(
            sds(jax.eval_shape(layers_and_head)),
            _sds((slots, 1), jnp.int32, one_chip), sds(cache)
        ).compile().as_text()
        report = tapir.explain()
    tapir.clear_cache()
    assert "ssm_state_step" in report and "impl=kernel" in report
    assert "experts held: 0..1 of 72 routed" in report
    # every fused decode GEMM (the in-projection among them) is one row
    # block of the 96 slots: its weight streams once a step
    passes = re.findall(r"weight passes: (\d+) \(rows (\d+)", report)
    assert passes and set(passes) == {("1", "96")}
    assert "ssm_state_step" in text and "tpu_custom_call" in text

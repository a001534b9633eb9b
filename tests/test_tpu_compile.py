"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e:2x2
host and XLA's TPU compiler compiles for its first device.  What the TPU
compiler refuses (block shapes off the (8, 128) tiling, VMEM overruns,
Mosaic lowering gaps) fails here instead of on the chip.  Shapes are the
published widths of qwen2_5_3b (d_model 2048, d_ff 11008, 16/2 heads of
128) and of rwkv6_7b's scan (64 heads of 64).

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

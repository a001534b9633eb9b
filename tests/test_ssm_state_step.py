"""The one-position SSM state update (``ssm_state_step``): the Pallas
kernel in interpret mode against the jnp composite, the packed state
layout, and the impl registry's choice between them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tapir
from repro.core.passes import optimize_graph
from repro.core.schedule import CPU_COST_MODEL, assign_schedules, cost_model_for
from repro.core.tapir import TapirConfig, use
from repro.kernels.ssm_state_step import ops, ref

V5E = cost_model_for("TPU v5 lite")


def _args(S, R, H, P, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    g = ops.heads_per_row(H, P)
    h = jax.random.normal(k[0], (R, H // g, N, g * P), jnp.float32)
    a = jax.random.uniform(k[1], (S, H), minval=0.3, maxval=1.0)
    u = jax.random.normal(k[2], (S, H, P))
    return (h, a, u, jax.random.normal(k[3], (S, N)),
            jax.random.normal(k[4], (S, N)))


@pytest.mark.parametrize("S,R,H,P,N", [
    (1, 1, 4, 16, 16),       # one slot, every head in one lane row
    (3, 5, 8, 16, 8),        # park rows past the slots stay as they were
    (2, 2, 8, 64, 128),      # published head and state sizes, 2 heads/row
    (4, 6, 16, 32, 16),      # 4 heads a row, several row blocks
    (2, 3, 4, 128, 8),       # a head fills the lanes alone
])
def test_kernel_matches_composite(S, R, H, P, N):
    args = _args(S, R, H, P, N)
    y0, h0 = ref.ssm_state_step_ref(*args)
    y1, h1 = ops.ssm_state_step(*args, interpret=True)
    # both are f32 throughout; they differ only in the order of the N-sum
    # of y (a sublane reduction against an einsum)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(h1[S:]), np.asarray(args[0][S:]))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=1e-6,
                               atol=1e-6)


def test_packed_state_roundtrips_and_matches_the_recurrence():
    """``pack_state`` is a layout only; the update is h <- a h + u B^T and
    y = h C, per head, on the unpacked [H, N, P] state."""
    S, H, P, N = 2, 6, 16, 8
    g = ops.heads_per_row(H, P)
    assert g == 6 and ops.heads_per_row(128, 64) == 2
    h, a, u, bm, cm = _args(S, S, H, P, N, seed=3)
    hs = ref.unpack_state(h, P)
    np.testing.assert_array_equal(np.asarray(ref.pack_state(hs, g)),
                                  np.asarray(h))
    y, hn = ref.ssm_state_step_ref(h, a, u, bm, cm)
    hs, hn, a, u, bm, cm = (np.asarray(t, np.float64) for t in
                            (hs, ref.unpack_state(hn, P), a, u, bm, cm))
    want = a[:, :, None, None] * hs + bm[:, None, :, None] * u[:, :, None, :]
    np.testing.assert_allclose(hn, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y),
                               np.einsum("shnp,sn->shp", want, cm),
                               rtol=1e-5, atol=1e-5)


def _graph(backend, cm, force=None, mesh_axes=None):
    """One ``ssm_state_step`` (96 slots' worth of one layer's rows, cut to
    4) scheduled for ``backend``, optionally under a mesh."""
    args = _args(4, 4, 8, 64, 128)
    with use(TapirConfig(mode="tapir")):
        g = tapir.capture_region(tapir.ssm_state_step, *args)
    optimize_graph(g, cm)
    assign_schedules(g, cm, backend=backend, mesh_axes=mesh_axes,
                     force_impl=force)
    return g, [n for n in g.nodes.values() if n.op == "ssm_state_step"]


def test_registry_binds_kernel_on_tpu_and_composite_elsewhere():
    _, nodes = _graph("tpu", V5E)
    assert len(nodes) == 2 and {n.schedule.impl for n in nodes} == {"kernel"}
    costs = nodes[0].schedule.impl_costs
    assert costs["kernel"] < costs["composite"]
    _, nodes = _graph("cpu", CPU_COST_MODEL)
    assert {n.schedule.impl for n in nodes} == {"composite"}
    assert str(nodes[0].schedule.impl_costs["kernel"]).startswith("n/a")
    _, nodes = _graph("tpu", V5E, mesh_axes={"model": 4})
    assert {n.schedule.impl for n in nodes} == {"composite"}
    assert "GSPMD" in nodes[0].schedule.impl_costs["kernel"]


def test_state_node_donates_its_rows_and_orders_after_y():
    g, nodes = _graph("cpu", CPU_COST_MODEL)
    y, hn = sorted(nodes, key=lambda n: n.attrs["out"])
    assert hn.donates == hn.inputs[0] and y.donates is None
    assert y.nid in hn.anti
    assert g.donated_inputs() == [hn.inputs[0]]


def test_force_impl_changes_the_jaxpr_and_signature():
    from repro.core.lowering import emit

    def jaxpr_of(g):
        args = {n: jnp.zeros(tuple(g.nodes[nid].ttype.shape),
                             g.nodes[nid].ttype.dtype)
                for n, nid in g.inputs}
        return str(jax.make_jaxpr(lambda a: emit(g, "tpu")(a))(args))

    g_k, (n_k, _) = _graph("tpu", V5E, force=(("ssm_state_step", "kernel"),))
    g_c, (n_c, _) = _graph("tpu", V5E,
                           force=(("ssm_state_step", "composite"),))
    assert (n_k.schedule.impl, n_c.schedule.impl) == ("kernel", "composite")
    j_k, j_c = jaxpr_of(g_k), jaxpr_of(g_c)
    # the two output nodes lower through ONE kernel call
    assert j_k.count("pallas_call") == 1 and "pallas_call" not in j_c
    assert g_k.signature() != g_c.signature()
    with pytest.raises(ValueError, match="unavailable"):
        _graph("cpu", CPU_COST_MODEL, force=(("ssm_state_step", "kernel"),))


def test_explain_shows_both_costs():
    txt = tapir.explain(_graph("tpu", V5E)[0])
    assert "ssm_state_step" in txt and "impl=kernel" in txt
    assert "kernel=" in txt and "composite=" in txt


def test_kernel_shape_rules():
    assert ops.kernel_unsupported(96, 128, 64, 128) == ""
    assert "8-row" in ops.kernel_unsupported(4, 8, 64, 12)
    assert "lane" in ops.kernel_unsupported(4, 6, 48, 16)
    assert ops.pick_rows_per_block(64, 128, 128) == 16

"""A tiny training cell through the harness on the CPU: a run that is
correct, and runs with the timed path broken underneath that are not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import common, spec, traffic, train
from bench.tests import tiny

SEED = 2**41 + 9


def unchanged(step):
    """A step that returns its state unchanged."""
    def wrapped(state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, met = step(state, batch)
        return keep, met
    return wrapped


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def wrapped(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return wrapped


def test_train_cell_is_correct(cpu_harness):
    result, checks = bench_run.run_cell(tiny.cell("train"), SEED, 1.0, False,
                                        cpu_harness)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"train_tok_s", "setup_s"}
    assert result["attempted"] > 0
    assert [c["name"] for c in checks] == ["loss_gap", "grad_gap",
                                           "change_gap", "grad_median"]


def test_train_cell_traced_reads_mfu(cpu_harness):
    result, _ = bench_run.run_cell(tiny.cell("train"), SEED, 1.0, True,
                                   cpu_harness)
    assert result["metrics"]["train_mfu"]["value"] > 0
    assert "device_idle.train" not in result["metrics"]


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_train_fault_is_not_correct(cpu_harness, fault):
    result, checks = bench_run.run_cell(tiny.cell("train"), SEED, 1.0, False,
                                        cpu_harness, wrap_step=fault)
    assert not result["correct"], checks


def test_train_fp8_control_is_not_correct(cpu_harness):
    """The control and the reference fed half of each batch, scored by the
    run's own checks, are not correct."""
    cell = tiny.cell("train")
    for seed in (1, 2, 3):
        result, checks, ctx = train.run(cell, seed, 0.5, False, common.now(),
                                        cpu_harness, control="fp8")
        assert result["correct"], checks
        for side in (ctx["control"], ctx["faults"]["half_batch"]):
            scored = ctx["score"](side)
            assert [c["name"] for c in scored] == [c["name"] for c in checks]
            assert not all(c["ok"] for c in scored), scored


def test_reference_in_row_blocks_matches_the_whole_batch():
    """The reference's token-weighted blocks give the whole batch's loss
    and gradient, for a batch that the blocks do not divide evenly."""
    cell = tiny.cell("train")
    conf = cell.conf
    ad, ref = spec.adapter(conf), spec.reference(conf)
    params = ad.reference_params(conf, 5)
    toks = traffic.train_batch(dict(cell.traffic, batch=3), 5, 0,
                               int(conf["vocab_size"]))
    lg = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref.loss(conf, p, t, y)))
    whole, gw = lg(params, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]))
    blocks, gb = train._loss_and_grad(lg, params, toks)
    assert float(blocks) == pytest.approx(float(whole), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gb),
                    jax.tree_util.tree_leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)

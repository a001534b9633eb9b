"""A tiny serving cell through the harness on the CPU: a run that is
correct, and runs with the timed path broken underneath that are not."""
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.harness import common, serve
from bench.tests import tiny

SEED = 2**40 + 5


class Fault:
    """The program's model with one fault planted in the decode step."""

    def __init__(self, model, kind: str):
        self._model, self._kind, self._calls = model, kind, 0

    def __getattr__(self, name):
        return getattr(self._model, name)

    def decode_step_slots(self, sp, tokens, cache):
        pos = cache["pos"]
        logits, cache = self._model.decode_step_slots(sp, tokens, cache)
        self._calls += 1
        if self._kind == "token" and self._calls % 5 == 0:
            # every slot's next token altered where it is produced
            top = jnp.argmax(logits, axis=-1)
            logits = logits.at[jnp.arange(logits.shape[0]),
                               (top + 1) % logits.shape[-1]].set(1e4)
        if self._kind == "state":
            cache["pos"] = pos          # the step leaves its state unchanged
        return logits, cache


def test_serve_cell_is_correct(cpu_harness):
    result, checks = bench_run.run_cell(tiny.cell("serve"), SEED, 1.0, False,
                                        cpu_harness)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert [c["name"] for c in checks] == ["max_logit_gap",
                                           "served_tokens_compared"]


def test_serve_cell_traced_reads_its_host_metrics(cpu_harness):
    result, _ = bench_run.run_cell(tiny.cell("serve"), SEED + 1, 1.0, True,
                                   cpu_harness)
    assert result["correct"]
    got = result["metrics"]
    for name in ("engine_host_share", "decode_step_ms", "prefill_ms_per_ktok",
                 "serve_mfu"):
        assert got[name]["value"] > 0, name
    # no TPU plane in a CPU trace: the device's metrics find nothing
    assert "decode_roofline" not in got and "device_idle.serve" not in got
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("kind", ["token", "state"])
def test_serve_fault_is_not_correct(cpu_harness, kind):
    result, checks = bench_run.run_cell(
        tiny.cell("serve"), SEED, 1.0, False, cpu_harness,
        wrap_model=lambda m: Fault(m, kind))
    assert not result["correct"], checks


def test_serve_fp8_control_is_not_correct(cpu_harness):
    """The control, scored by the run's own checks, is not correct."""
    cell = tiny.cell("serve")
    for seed in (1, 2, 3):
        result, checks, ctx = serve.run(cell, seed, 1.0, False,
                                        common.now(), cpu_harness,
                                        control="fp8")
        assert result["correct"], checks
        ctl = ctx["score"](ctx["control"])
        assert [c["name"] for c in ctl] == [c["name"] for c in checks]
        assert not all(c["ok"] for c in ctl), ctl

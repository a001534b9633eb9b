"""The cell this configuration and mix add: what it loads, the sizes its
mix gives, the hybrid's work counts against numbers worked by
hand, and a tiny granitemoehybrid serving cell through the harness on
the CPU."""
import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import spec, traffic, work_hybrid
from bench.reference import granitemoehybrid as ref
from bench.tests import tiny_hybrid

SEED = 2**40 + 11


def test_new_cell_loads_with_its_metrics():
    g = spec.load_cell("granite_h_chat")
    assert (g.chips, g.traffic["slots"], g.traffic["max_len"]) == (1, 96,
                                                                   4096)
    assert g.conf["model_type"] == "granitemoehybrid"
    names = {m["name"] for m in g.per_layer}
    assert {"state_decode_roofline", "ssm_step_roofline", "decode_step_ms",
            "decode_head_share", "device_idle.serve"} <= names
    # readers that count qwen2's dense layers do not read this model
    assert not names & {"decode_roofline", "serve_mfu"}
    assert {m["name"] for m in g.end_to_end} == {"serve_tok_s",
                                                 "itl_p95_ms", "setup_s"}


def test_mix_sizes_come_out_as_named():
    chat = spec.load_json(f"{spec.BENCH_DIR}/traffic/chat.json")
    g = spec.load_json(f"{spec.BENCH_DIR}/traffic/chat_96x4096.json")
    # the chat lengths, at 96 slots, on a grid of 96 quantiles
    assert (g["prompt"], g["output"]) == (chat["prompt"], chat["output"])
    sizes = traffic.sizes(g)
    assert len(sizes) == 96
    assert np.median([p for p, _ in sizes]) == pytest.approx(1020, rel=0.02)
    assert np.median([o for _, o in sizes]) == pytest.approx(129, rel=0.05)
    # every request, the mid-flight ones too, fits its slot
    for p, o in traffic.sizes(g) + traffic.initial_sizes(g):
        assert p + o - 1 <= g["max_len"]


def test_hybrid_counts_worked_by_hand():
    D = ref.dims(spec.load_json(
        f"{spec.BENCH_DIR}/configs/granite_4_0_h_small.json"))
    # in_proj 4096 x (2 x 8192 + 2 x 128 + 128), out_proj 8192 x 4096,
    # conv 4 x 8448 + its bias, dt_bias/A_log/D 3 x 128, the gated norm
    # 8192 and the input norm 4096
    assert work_hybrid.mamba_params(D) == 102_291_072
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, norm
    assert work_hybrid.attention_params(D) == 41_947_136
    # router 4096x72, 9 experts + the shared one: 3 x 4096 x (9 x 768 +
    # 1536), norm
    assert work_hybrid.ffn_params(D) == 104_108_032
    # 9 Mamba2 + 1 attention mixers, 10 FFNs, final norm, tied head
    assert work_hybrid.held_weight_bytes(D, 2) == 2 * 2_414_692_992
    # 9 x (128 x 64 x 128 f32 + 3 x 8448 bf16)
    assert work_hybrid.state_row_bytes(D, 2) == 38_204_928
    # 96 rows x 4 B x (state read and written + a + u + y + B + C)
    assert work_hybrid.ssm_step_bytes(D, 96) == 96 * 4 * 2_113_920
    flops, nbytes = work_hybrid.decode_step_work(
        D, [100, 300], work_hybrid.held_weight_bytes(D, 2), 2, 2)
    kv = 2 * 8 * 128 * 2                     # one attention layer, K and V
    assert nbytes == (2 * 2_414_692_992 + 2 * 2 * 38_204_928 + kv * 400
                      + kv * 2)
    assert flops == work_hybrid.token_flops(D, 100) + \
        work_hybrid.token_flops(D, 300)
    assert work_hybrid.token_flops(D, 300) - work_hybrid.token_flops(D, 100) \
        == 4 * 32 * 128 * 200


def test_tiny_hybrid_serve_cell_is_correct(cpu_harness):
    """The harness's serving path on the tiny configuration: the engine's
    slot loop, and the reference's ``embed``, ``layer`` (of both kinds)
    and ``logits`` through ``serve.logit_gaps``."""
    result, checks = bench_run.run_cell(tiny_hybrid.cell(), SEED, 1.0,
                                        False, cpu_harness)
    assert result["correct"], checks
    assert checks[1]["value"] >= 20
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}


def test_tiny_hybrid_traced_finds_no_device_metric_on_the_cpu(cpu_harness):
    result, _ = bench_run.run_cell(tiny_hybrid.cell(), SEED + 1, 1.0, True,
                                   cpu_harness)
    got = result["metrics"]
    assert got["decode_step_ms"]["value"] > 0
    # no TPU plane in a CPU trace: the kernel and device shares find
    # nothing to read
    assert "state_decode_roofline" not in got
    assert "ssm_step_roofline" not in got

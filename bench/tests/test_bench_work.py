"""Operation and byte counts against qwen2.5-3B numbers worked by hand."""
import pytest

from bench.harness import spec, work
from bench.reference import qwen2 as ref


def dims(name):
    return ref.dims(spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json"))


def test_qwen2_5_3b_parameter_counts():
    D = dims("qwen2_5_3b")
    # q 2048x2048, k and v 2048x256 each, o 2048x2048, gate/up/down
    # 3 x 2048x11008
    assert work.layer_matmul_params(D) == 77_070_336
    # + q/k/v biases (2048 + 2 x 256) + two norm scales (2 x 2048)
    assert work.layer_params(D) == 77_076_992
    assert work.head_params(D) == 311_164_928
    assert work.matmul_params(D) == 3_085_697_024


def test_qwen2_5_3b_decode_work():
    D = dims("qwen2_5_3b")
    # every layer, the final norm and the tied head, 2 bytes each
    assert work.decode_weight_bytes(D, 2) == 6_171_877_376
    # K and V, 36 layers x 2 kv heads x 128, bf16
    assert work.kv_bytes_per_position(D, 2) == 36_864
    flops, nbytes = work.decode_step_work(D, [100, 300], 6_171_877_376, 2)
    assert flops == 2 * 2 * 3_085_697_024 + 4 * 36 * 16 * 128 * 400
    assert nbytes == 6_171_877_376 + 36_864 * 400 + 36_864 * 2


def test_qwen2_5_3b_prefill_flops():
    D = dims("qwen2_5_3b")
    n = 1000
    body = 2 * 36 * 77_070_336 * n
    attn = 4 * 36 * 16 * 128 * n * (n + 1) // 2
    assert work.prefill_flops(D, n) == body + attn + 2 * 311_164_928


def test_qwen_train_token_flops():
    D = dims("qwen2_5_3b_train")
    matmul = 4 * 77_070_336 + 2048 * 37984
    assert work.matmul_params(D) == matmul == 386_072_576
    attn = 3 * 4 * 4 * 16 * 128 * 2049 / 2
    assert work.train_token_flops(D, 2048) == pytest.approx(
        6 * matmul + attn)
    # about 9.9 TFLOP for a step of 2 x 2048 tokens
    assert work.train_token_flops(D, 2048) * 4096 == pytest.approx(
        9.9e12, rel=0.01)

"""Tiny stand-ins for the benchmark's cells, for CPU tests: the qwen2
configuration at a few dozen widths, and mixes of a few requests."""
from __future__ import annotations

import copy

from bench.harness import spec

CONF = {
    "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 4096,
    "max_position_embeddings": 1024, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "program": {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"},
    # at 64 wide a larger spread keeps the logits as close together as at
    # the published width, so that rounding can move the first choice
    "init": {"std": 0.15, "residual_scaled": True, "bias_std": 0.02,
             "norm_std": 0.1},
    # limits between the program's readings (bf16) and the fp8 control's
    # at this size, on seeds 1-3 (CPU): gap 0.066 vs 0.31
    "correct": {"max_logit_gap": 0.15},
}
TRAIN_CONF = dict(copy.deepcopy(CONF), vocab_size=512,
                  init={"std": 0.02, "residual_scaled": True,
                        "bias_std": 0.02, "norm_std": 0.1},
                  program={"param_dtype": "float32",
                           "compute_dtype": "bfloat16"},
                  # largest of the program / smallest of the fp8 control /
                  # of half the batch, on seeds 1-3 (CPU): loss 2.2e-5 /
                  # 2.1e-4 / 4.3e-3, grad 6.5e-3 / 8.9e-3 / 0.12, change
                  # 8.2e-3 / 9.3e-3 / 0.029 (a state left unchanged: 1),
                  # median tensor's grad 1.9e-4 / 1.0e-3 / 7.8e-3
                  correct={"loss_gap": 8e-5, "grad_gap": 0.04,
                           "change_gap": 0.1, "grad_median": 5e-4})
CHAT = {"kind": "serve", "slots": 4, "max_len": 128,
        "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 48},
        "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
        "distinct": 16, "strata": 4, "epochs": 30,
        "check": {"requests": 3, "min_tokens": 20}}
JOB = {"kind": "train", "batch": 2, "seq": 32, "remat": "auto",
       "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                     "weight_decay": 0.0, "grad_clip": 1.0,
                     "warmup_steps": 100, "total_steps": 10000,
                     "min_lr_frac": 0.1}}


def cell(kind: str, conf: dict = None, mix: dict = None) -> spec.Cell:
    """A serving (``"serve"``) or training (``"train"``) cell reporting the
    metrics of ``qwen_chat`` or ``qwen_train``."""
    real = spec.load_cell({"serve": "qwen_chat", "train": "qwen_train"}[kind])
    conf = copy.deepcopy(conf or (CONF if kind == "serve" else TRAIN_CONF))
    mix = copy.deepcopy(mix or (CHAT if kind == "serve" else JOB))
    return spec.Cell(name="tiny_" + kind, chips=1, config_name="tiny",
                     conf=conf, traffic=mix,
                     end_to_end=real.end_to_end, per_layer=real.per_layer)

"""A tiny stand-in for the ``granite_4_0_h_small`` configuration, for CPU
tests: the same layer pattern and mechanisms at a few dozen widths."""
from __future__ import annotations

import copy

from bench.harness import spec

CONF = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 512, "max_position_embeddings": 1024,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "attention_bias": False, "position_embedding_type": "nope",
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "num_local_experts": 8, "router_experts": 8, "experts_held": [0, 8],
    "num_experts_per_tok": 3,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "program": {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"},
    "init": {"std": 0.15, "embed_std": 0.3, "res_std": 0.053,
             "conv_std": 0.2, "bias_std": 0.02, "norm_std": 0.1,
             "a_log_std": 0.25, "dt_bias_mean": -4.0, "dt_bias_std": 0.5},
    "correct": {"max_logit_gap": 0.15},
}
CHAT = {"kind": "serve", "slots": 4, "max_len": 128,
        "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 48},
        "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
        "distinct": 16, "strata": 4, "epochs": 30,
        "check": {"requests": 3, "min_tokens": 20}}


def f32(conf: dict = None) -> dict:
    """``conf`` computed in float32 throughout."""
    conf = copy.deepcopy(conf or CONF)
    conf["program"] = {"param_dtype": "float32", "compute_dtype": "float32"}
    return conf


def cell(conf: dict = None, mix: dict = None) -> spec.Cell:
    """A serving cell of the tiny configuration reporting the metrics of
    ``granite_h_chat``."""
    real = spec.load_cell("granite_h_chat")
    return spec.Cell(name="tiny_hybrid", chips=1, config_name="tiny_hybrid",
                     conf=copy.deepcopy(conf or CONF),
                     traffic=copy.deepcopy(mix or CHAT),
                     end_to_end=real.end_to_end, per_layer=real.per_layer)

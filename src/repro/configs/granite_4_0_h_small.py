"""IBM Granite 4.0-H Small [granitemoehybrid; huggingface.co/ibm-granite/
granite-4.0-h-small] — Mamba2 + NoPE GQA attention (9:1), a 72-expert
top-10 MoE with a shared expert in every layer — published config + a
reduced smoke variant."""
from repro.models.base import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name='granite-4.0-h-small',
    family='granite_hybrid',
    n_layers=40,
    layer_types=_PERIOD * 4,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    shared_ff=1536,
    n_experts=72,
    top_k=10,
    vocab=100352,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    norm_eps=1e-5,
    attn_scale=0.0078125,
    embed_scale=12.0,
    residual_scale=0.22,
    logit_scale=16.0,
    tie_embeddings=True,
    max_seq=131072,
)

SMOKE = ModelConfig(
    name='granite-h-smoke',
    family='granite_hybrid',
    n_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=32,
    shared_ff=48,
    n_experts=8,
    top_k=3,
    vocab=512,
    ssm_state=16,
    ssm_head_dim=16,
    ssm_expand=2,
    norm_eps=1e-5,
    attn_scale=1.0 / 16,
    embed_scale=12.0,
    residual_scale=0.22,
    logit_scale=16.0,
    tie_embeddings=True,
    max_seq=256,
)

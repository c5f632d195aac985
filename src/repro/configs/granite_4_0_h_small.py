"""Granite 4.0-H Small (32B-A9B) [huggingface.co/ibm-granite/
granite-4.0-h-small, config.json]: 40L d_model=4096, layer_types repeat
with period 10 (Mamba-2 x5, attention, Mamba-2 x4). Mamba-2: 128 heads of
64, d_state 128, 1 group, d_conv 4 with bias, expand 2, chunk 256. GQA
attention 32H/8kv of 128, NoPE, scaled by attention_multiplier 1/128.
Every layer is MoE: 72 experts of width 768, top-10 (softmax over the
top-10 logits), plus one shared SwiGLU of width 1536. Multipliers:
embedding 12, residual 0.22, logits divided by 16. Tied embeddings,
vocab 100352, bf16 parameters."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    num_experts=72,
    experts_per_token=10,
    shared_experts=2,           # one shared SwiGLU of 2 x 768 = 1536
    moe_every=1,
    moe_offset=0,
    attn_period=10,
    attn_offset=5,
    ssm_type="mamba2",
    d_state=128,
    d_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    mlp_type="swiglu",
    norm_type="rmsnorm",
    norm_eps=1e-5,
    pos_emb="none",
    tie_embeddings=True,
    param_dtype="bfloat16",
)

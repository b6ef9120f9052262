"""Granite-4.0-H-Micro — hybrid Mamba-2 / GQA decoder, 1 attention layer
in 10 (layers 5, 15, 25, 35), a SwiGLU FFN after every mixer, NoPE
attention, muP-style scale factors, tied embeddings.
[https://huggingface.co/ibm-granite/granite-4.0-h-micro config.json;
Mamba-2 arXiv:2405.21060]"""
from repro.configs.base import ArchConfig, SSMConfig

# layer_types: "mamba" x5, "attention", "mamba" x4, repeated four times
PERIOD = ("ssm",) * 5 + ("attn",) + ("ssm",) * 4

FULL = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                               # shared_intermediate_size
    vocab_size=100_352,
    qkv_bias=False,
    position_embedding="none",
    attention_multiplier=0.015625,
    block_pattern=PERIOD,
    mlp="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk_size=256, conv_bias=True),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    tie_embeddings=True,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)

SMOKE = FULL.replace(
    name="granite-4.0-h-micro-smoke",
    num_layers=6, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, block_pattern=("ssm", "attn", "ssm"),
    attention_multiplier=1.0 / 16,
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, conv_width=4,
                  chunk_size=8, conv_bias=True),
)

"""Granite-4.0-H-Small (32B-A9B): 36 Mamba-2 + 4 NoPE GQA attention
layers, each followed by 72 routed experts (top-10) and a shared MLP
[hf:ibm-granite/granite-4.0-h-small]."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, d_ff_shared=1536, vocab_size=100352,
    n_experts=72, top_k=10,
    ssm_state=128, ssm_head_dim=64, d_inner=8192, ssm_chunk=256,
    conv_width=4,
    layer_pattern="MMMMMAMMMM" * 4,    # attention at 5, 15, 25, 35
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=16.0, attention_multiplier=1 / 128,
    position_embedding="nope",
    activation="silu", norm="rmsnorm", norm_eps=1e-5,
    max_seq=131072, supports_long_context=True,
)

"""SmallThinker-21BA3B: 52 GQA attention layers, three of every four
over a 4096-position window with RoPE and the fourth global with no
position encoding, each followed by 64 routed ReGLU experts (top-6, no
shared expert) routed on the attention block's input
[hf:PowerInfer/SmallThinker-21BA3B-Instruct]."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="smallthinker-21b-a3b", family="hybrid",
    n_layers=52, d_model=2560, n_heads=28, n_kv_heads=4, head_dim=128,
    d_ff=768, vocab_size=151936,
    n_experts=64, top_k=6,
    layer_pattern="ASSS" * 13,         # sliding_window_layout [0,1,1,1]
    sliding_window=4096,
    position_embedding="nope",         # rope_layout: 0 on global layers
    pre_attention_router=True,
    activation="relu", norm="rmsnorm", norm_eps=1e-6,
    rope_theta=1.5e6, max_seq=16384,
)

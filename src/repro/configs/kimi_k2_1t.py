"""kimi-k2-1t-a32b — 61 layers, d_model 7168, 64 heads of multi-head
latent attention (q rank 1536, kv rank 512, qk 128 + 64 RoPE, v 128),
vocab 163840; 384 routed experts of width 2048, top-8, sigmoid routing
scaled by 2.827, one shared expert; the first layer is dense, width
18432.  Published config:
https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json

`workloads.lm_extract` lowers the latent attention, the shared expert
and the leading dense layer.  `models/` implements none of them: it
builds a stand-in grouped attention from `n_heads` / `n_kv_heads` /
`head_dim` (here 64 heads of 128, the published v width) and a MoE
FFN in every layer.  Adafactor-class optimizer state to fit HBM."""
import dataclasses

from .base import ArchConfig

# `routed_scaling_factor`: the normalized top-k weights are scaled by it
# (`workloads.lm_reference.moe` takes it; no GEMM depends on it).
ROUTED_SCALING_FACTOR = 2.827

CONFIG = ArchConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,
    head_dim=128,
    d_ff=2048,
    vocab_size=163840,
    n_experts=384,
    experts_per_token=8,
    n_shared_experts=1,
    first_k_dense=1,
    dense_d_ff=18432,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    activation="swiglu",
    rope_theta=50000.0,
    optimizer="adafactor",
    param_dtype="bfloat16",
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
)


def reduced() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
        head_dim=32, d_ff=64, dense_d_ff=192, vocab_size=512, n_experts=8,
        experts_per_token=2, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)

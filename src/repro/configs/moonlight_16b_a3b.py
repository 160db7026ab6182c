"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B config.json] — the
DeepSeek-V3 block at d_model 2048: MLA without q-LoRA, 64 routed experts
top-6 chosen by sigmoid score plus a correction bias (noaux_tc), 2 shared.

The whole model holds all 64 experts (ep_size 1); one chip's share of an
expert-parallel deployment is `dataclasses.replace(CONFIG, ep_size=8,
ep_rank=r)`, whose router still scores all 64.  Balance-loss weight and
bias step from the DeepSeek-V3 report (arXiv:2412.19437 §4.2).
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="mla_moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11_264,           # the first (dense) layer's SwiGLU width
    vocab_size=163_840,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    d_ff_expert=1408,
    first_dense_layers=1,
    router_score="sigmoid",
    router_bias=True,
    routed_scaling=2.446,
    balance_alpha=1e-4,
    bias_rate=1e-3,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    head_dim=192,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    activation="silu",
))

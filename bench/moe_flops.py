"""Model FLOPs of a DeepSeek-V3-block MoE decoder's train step at one
chip's expert share, kept with the benchmark so that no program change can
move the yardstick.

Matmul FLOPs only (2mnk), forward plus twice that for the backward pass,
causal attention counted over the half of the scores it needs, no
recomputation, and the routed experts at their expected share: K * G / E
pairs a token (G experts held of the E the router scores).  The
convention of `flops/accounting.step_flops` with `executed=False`, which a
test holds these functions to.
"""
from __future__ import annotations


def gmm_flops_per_pair(cfg: dict) -> float:
    """One (token, expert) pair through a SwiGLU expert, forward and
    backward: three d x f matmuls, 2 d f each, times 3."""
    return 3.0 * 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, kvr, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    V, L = cfg["vocab_size"], cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    G, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    E = G * cfg["ep_size"]
    q = (2 * d * qr + 2 * qr * H * (dn + dr)) if qr else 2 * d * H * (dn + dr)
    attention = (q + 2 * d * (kvr + dr) + 2 * kvr * H * (dn + dv)
                 + 2 * H * dv * d
                 + 2 * (seq * 0.5) * H * (dn + dr) + 2 * (seq * 0.5) * H * dv)
    swiglu = lambda f: 2 * d * f * 3
    moe = (2 * d * E + K * G / E * swiglu(cfg["moe_intermediate_size"])
           + swiglu(cfg["moe_intermediate_size"] * cfg["n_shared_experts"]))
    per_token = (L * attention + n_dense * swiglu(cfg["intermediate_size"])
                 + (L - n_dense) * moe + 2 * d * V)
    return 3.0 * batch * seq * per_token

"""Model FLOPs of a dense decoder's train step, kept with the benchmark so
that no program change can move the yardstick.

Matmul FLOPs only (2mnk), forward plus twice that for the backward pass,
causal attention counted over the half of the scores it needs, and no
recomputation: the convention of `flops/accounting.step_flops` with
`executed=False`, which a test holds this function to.
"""
from __future__ import annotations


def dense_train_flops(cfg: dict, batch: int, seq: int) -> float:
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    V, L = cfg["vocab_size"], cfg["num_hidden_layers"]
    mlp_mats = 3 if cfg["hidden_act"] == "silu" else 2
    per_token_layer = (2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
                       + 2 * 2 * (seq * 0.5) * H * hd
                       + 2 * d * ff * mlp_mats)
    forward = batch * seq * (L * per_token_layer + 2 * d * V)
    return 3.0 * forward

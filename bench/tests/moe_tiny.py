"""The MoE train cell cut to a size the CPU runs in seconds: the same
file, every width shrunk, 4 experts held of 8 (ep_size 2), top-2."""
from bench import spec

CELL = "moonlight_ep8_train_s8192"


def moe_cell(**changes):
    cell = spec.resolve(CELL)
    cell.config = dict(
        cell.config, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=16, moe_intermediate_size=32,
        n_routed_experts=4, ep_size=2, num_experts_per_tok=2,
        n_shared_experts=1, num_hidden_layers=3, vocab_size=256, **changes)
    cell.mix = dict(cell.mix, batch=4, seq=32)
    # the cell's limits are set for its own size; at this size bf16 reads
    # further from the float32 reference (routing flips weigh more among
    # 32 tokens a row), and a near-tie of routing spans more score
    cell.config["limits"] = {"grad1_norm_gap": 0.1,
                             "delta3_norm_gap": 0.1, "dropped_pairs": 0,
                             "nonfinite_losses": 0}
    cell.config["tie_margin"] = 1e-2
    return cell

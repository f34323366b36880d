"""Model FLOPs of the whole serving step, per token, from the
configuration's shapes: the absorbed-MLA projections, the attention over
the attended latent rows, the dense or expert FFN of each layer, and the
output head.  The embedding gather costs no FLOPs."""
from __future__ import annotations


def linear_flops_per_token(arch: dict) -> int:
    """FLOPs of every matrix product that does not depend on the context
    length, for one token through all layers and the head."""
    D, H = arch["d_model"], arch["heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    nope, rope, dv = arch["qk_nope"], arch["qk_rope"], arch["v_head"]
    attn = (D * qr + qr * H * (nope + rope) + D * (kvr + rope)
            + H * nope * kvr + H * kvr * dv + H * dv * D)
    dense_ffn = 3 * D * arch["d_ff"]
    moe_ffn = (arch["top_k"] * 3 * D * arch["d_ff_expert"]
               + arch["shared_experts"] * 3 * D * arch["d_ff_expert"]
               + D * arch["experts"])
    n_moe = sum(arch["moe_layers"])
    macs = (arch["layers"] * attn + (arch["layers"] - n_moe) * dense_ffn
            + n_moe * moe_ffn + D * arch["vocab"])
    return 2 * macs


def attention_flops(arch: dict, attended_rows: int) -> int:
    """Absorbed-form attention FLOPs of one query row that attends
    ``attended_rows`` latent rows, over all layers."""
    latent = arch["kv_lora_rank"] + arch["qk_rope"]
    return (2 * arch["layers"] * arch["heads"] * attended_rows
            * (latent + arch["kv_lora_rank"]))


def step_flops(arch: dict, tokens: int, attended_rows: int) -> int:
    """FLOPs of ``tokens`` query rows that attend ``attended_rows`` latent
    rows in all (summed over the rows)."""
    return (tokens * linear_flops_per_token(arch)
            + attention_flops(arch, attended_rows))

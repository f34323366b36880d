"""DeepSeek-V3 (DeepSeek-R1, DeepSeek-V3): MLA with low-rank q and kv
projections, a dense GLU FFN in the leading layers, MoE with one shared
expert after them.  The plain reference is bench/reference/mla_moe.py, the
step's model FLOPs bench/work/step.py."""
from __future__ import annotations

from reference import mla_moe
from work import step

logits = mla_moe.logits


def arch(c: dict) -> dict:
    """Shapes of a DeepSeek-V3-type MLA + MoE stack from config.json keys."""
    n = int(c["num_hidden_layers"])
    first = int(c["first_k_dense_replace"])
    freq = int(c["moe_layer_freq"])
    return {
        "layers": n,
        "d_model": int(c["hidden_size"]),
        "heads": int(c["num_attention_heads"]),
        "d_ff": int(c["intermediate_size"]),
        "vocab": int(c["vocab_size"]),
        "q_lora_rank": int(c["q_lora_rank"]),
        "kv_lora_rank": int(c["kv_lora_rank"]),
        "qk_nope": int(c["qk_nope_head_dim"]),
        "qk_rope": int(c["qk_rope_head_dim"]),
        "v_head": int(c["v_head_dim"]),
        "experts": int(c["n_routed_experts"]),
        "top_k": int(c["num_experts_per_tok"]),
        "d_ff_expert": int(c["moe_intermediate_size"]),
        "shared_experts": int(c["n_shared_experts"]),
        "moe_layers": [i >= first and (i - first) % freq == 0
                       for i in range(n)],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "dtype": c["dtype"],
    }


def model_config(c: dict, name: str):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    a = arch(c)
    if a["shared_experts"] > 1:
        raise ValueError("the MoE layer holds at most one shared expert")
    return ModelConfig(
        name=name, family="mla", num_layers=a["layers"],
        d_model=a["d_model"], num_heads=a["heads"],
        num_kv_heads=int(c["num_key_value_heads"]), head_dim=a["v_head"],
        d_ff=a["d_ff"], vocab_size=a["vocab"], attention_kind="mla",
        rope_theta=a["rope_theta"], norm_eps=a["norm_eps"],
        dtype=a["dtype"], remat=False,
        mla=MLAConfig(q_lora_rank=a["q_lora_rank"],
                      kv_lora_rank=a["kv_lora_rank"],
                      qk_nope_head_dim=a["qk_nope"],
                      qk_rope_head_dim=a["qk_rope"],
                      v_head_dim=a["v_head"]),
        moe=MoEConfig(num_experts=a["experts"], top_k=a["top_k"],
                      d_ff_expert=a["d_ff_expert"],
                      shared_expert=a["shared_experts"] == 1,
                      first_dense_layers=int(c["first_k_dense_replace"]),
                      every_k_layers=int(c["moe_layer_freq"])))


def step_flops(arch: dict, decode_rows, chunks) -> int:
    """Every query row attends each causal row before it: a decode row its
    whole context, row i of a chunk at ``start`` the first start + i + 1."""
    flops = step.step_flops(arch, len(decode_rows), sum(decode_rows))
    for s, c in chunks:
        flops += step.step_flops(arch, c, c * s + c * (c + 1) // 2)
    return flops

"""Configuration files: the published config.json keys as run, plus the
cut, the deployment and the departures.  ``arch`` reads the shapes the
benchmark's yardstick needs (work counts, the plain reference);
``model_config`` builds the program's ``ModelConfig`` from the same keys.
"""
from __future__ import annotations

import json
from pathlib import Path

MODEL_TYPES = ("deepseek_v3", "kimi_k2")


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def arch(c: dict) -> dict:
    """Shapes of a DeepSeek-V3-type MLA + MoE stack from config.json keys."""
    if c["model_type"] not in MODEL_TYPES:
        raise ValueError(f"unsupported model_type {c['model_type']!r}")
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

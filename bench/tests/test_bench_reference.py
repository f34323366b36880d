"""The plain float32 reference against the served path at a small size:
run_paged's recorded prefill and first-decode logits and its greedy
tokens, for both configurations' layer patterns (3 dense + 1 MoE as
DeepSeek-R1's cut, 1 dense + 3 MoE as Kimi-K2's)."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import cell, config, traffic  # noqa: E402
from reference import mla_moe  # noqa: E402


def tiny(first_dense: int, dtype: str = "float32") -> dict:
    return {"model_type": "deepseek_v3", "hidden_size": 64,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "num_hidden_layers": 4, "num_attention_heads": 4,
            "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "n_routed_experts": 4,
            "num_experts_per_tok": 2, "n_shared_experts": 1,
            "first_k_dense_replace": first_dense, "moe_layer_freq": 1,
            "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
            "dtype": dtype}


FLAGS = {"--batch": 3, "--prompt": 48, "--shared-prefix": 16, "--gen": 6,
         "--page-size": 8, "--prefill-chunk": 16, "--kv-dtype": "fp",
         "--rescale": "amla", "--prefix-cache": True}


def test_weights_are_the_programs_draws():
    import jax

    from repro.models import model
    c = tiny(1)
    cfg = config.model_config(c, "tiny")
    a = config.arch(c)
    params = model.init(jax.random.PRNGKey(11), cfg)
    emb_key, keys = mla_moe.layer_keys(11, a)
    np.testing.assert_array_equal(np.asarray(params["embed"], np.float32),
                                  np.asarray(mla_moe.embed_weights(emb_key,
                                                                   a)))
    w_moe = mla_moe.layer_weights(keys[2], a, True)     # 2nd of 3 MoE
    blk = params["groups"][1]["b0"]
    np.testing.assert_array_equal(np.asarray(blk["mix"]["w_uk"][1]),
                                  np.asarray(w_moe["w_uk"]))
    np.testing.assert_array_equal(np.asarray(blk["ffn"]["router"][1]),
                                  np.asarray(w_moe["router"]))
    np.testing.assert_array_equal(
        np.asarray(blk["ffn"]["shared"]["w_down"][1]),
        np.asarray(w_moe["shared"]["w_down"]))


@pytest.mark.parametrize("first_dense", [3, 1])
def test_served_path_agrees_with_reference(first_dense):
    from repro.launch import serve
    c = tiny(first_dense)
    a = config.arch(c)
    flags = dict(FLAGS, **{"--seed": 5, "--requests": 4})
    res = serve.run_paged(serve.parse_args(traffic.argv(flags)),
                          config.model_config(c, "tiny"), record_logits=True)
    reqs = traffic.make_requests(flags, 4, 5, a["vocab"])
    faults, failed = cell.counter_faults(reqs, res, {})
    assert not faults and failed == 0
    assert res["prefill_tokens_saved"] > 0          # prefix hits served
    got = cell.compare(config.architecture(c), a, 5, reqs, res["outputs"],
                       res["seed_logits"], res["first_decode_logits"])
    assert got["tokens"] == sum(r.gen for r in reqs)
    # float32 on both sides: rounding of a different summation order only
    assert got["served_gap"] == 0.0
    assert got["logit_err"] < 1e-4

"""Operation and byte counts of the kernels and of the whole step, against
hand-computed shapes at a small size; the architecture lookup by
``model_type``, and what it gives for the shipped configuration files."""
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import config  # noqa: E402
from work import etap_decode, etap_prefill, step  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

ARCH = {"layers": 2, "d_model": 8, "heads": 2, "d_ff": 16, "vocab": 10,
        "q_lora_rank": 4, "kv_lora_rank": 4, "qk_nope": 2, "qk_rope": 2,
        "v_head": 3, "experts": 4, "top_k": 2, "d_ff_expert": 5,
        "shared_experts": 1, "moe_layers": [False, True]}


def test_decode_counts_rows_inside_lengths_at_pool_dtype():
    # 2 sequences attending 3 and 5 rows; latent 6, dv 4, 2 heads
    f, b = etap_decode.work([3, 5], heads=2, latent=6, dv=4, pool_bytes=2)
    assert f == 2 * 2 * 8 * (6 + 4)
    # 8 rows x 6 cols x 2 B, q 2x2x6x2 B, out 2x2x4x2 B
    assert b == 8 * 6 * 2 + 2 * 2 * 6 * 2 + 2 * 2 * 4 * 2
    f1, b1 = etap_decode.work([3, 5], heads=2, latent=6, dv=4, pool_bytes=1)
    assert f1 == f and b - b1 == 8 * 6


def test_prefill_counts_causal_rows():
    # chunk of 3 rows at start 4: rows attend 5, 6, 7
    f, b = etap_prefill.work(4, 3, heads=2, latent=6, dv=4, pool_bytes=2)
    assert f == 2 * 2 * (5 + 6 + 7) * (6 + 4)
    assert b == 7 * 6 * 2 + 3 * 2 * 6 * 2 + 3 * 2 * 4 * 2


def test_step_flops_by_hand():
    attn = (8 * 4 + 4 * 2 * 4 + 8 * 6 + 2 * 2 * 4 + 2 * 4 * 3 + 2 * 3 * 8)
    dense = 3 * 8 * 16
    moe = 2 * 3 * 8 * 5 + 1 * 3 * 8 * 5 + 8 * 4
    macs = 2 * attn + dense + moe + 8 * 10
    assert step.linear_flops_per_token(ARCH) == 2 * macs
    # absorbed attention: 2 layers x 2 heads x rows x (latent 6 + dv 4)
    assert step.attention_flops(ARCH, 7) == 2 * 2 * 2 * 7 * 10
    assert step.step_flops(ARCH, 3, 7) == 3 * 2 * macs + 2 * 2 * 2 * 7 * 10
    # the architecture's entry point: 3 decode rows attending 1 + 2 + 4 rows,
    # and a chunk of 3 rows at start 4 attending 5 + 6 + 7
    v3 = config.architecture({"model_type": "deepseek_v3"})
    assert v3.step_flops(ARCH, (1, 2, 4), ()) == \
        3 * 2 * macs + 2 * 2 * 2 * 7 * 10
    assert v3.step_flops(ARCH, (), ((4, 3),)) == \
        3 * 2 * macs + 2 * 2 * 2 * 18 * 10
    assert v3.step_flops(ARCH, (1, 2, 4), ((4, 3),)) == \
        6 * 2 * macs + 2 * 2 * 2 * 25 * 10


def test_arch_reads_the_configuration_files():
    root = Path(__file__).resolve().parents[1] / "configs"
    r1 = config.arch(config.load(root / "deepseek-r1-tp8.json"))
    assert r1["moe_layers"] == [False, False, False, True]
    assert (r1["d_model"], r1["heads"], r1["d_ff"], r1["experts"],
            r1["top_k"], r1["kv_lora_rank"] + r1["qk_rope"]) == \
        (7168, 16, 18432, 8, 8, 576)
    kimi = config.arch(config.load(root / "kimi-k2-tp8.json"))
    assert kimi["moe_layers"] == [False, True, True, True]
    assert (kimi["heads"], kimi["vocab"], kimi["rope_theta"]) == \
        (8, 20480, 50000.0)


def _model_config(name, heads, vocab, rope_theta, first_dense):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name=name, family="mla", num_layers=4, d_model=7168,
        num_heads=heads, num_kv_heads=heads, d_ff=18432, vocab_size=vocab,
        head_dim=128, attention_kind="mla", rope_theta=rope_theta,
        norm_eps=1e-06, dtype="bfloat16", remat=False,
        mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(num_experts=8, top_k=8, d_ff_expert=2048,
                      shared_expert=True, first_dense_layers=first_dense,
                      every_k_layers=1))


# read from the shipped files before the architectures moved to bench/arch/
GOLDEN = {
    "deepseek-r1-tp8": (
        {"heads": 16, "vocab": 16160,
         "moe_layers": [False, False, False, True], "rope_theta": 10000.0},
        (16, 16160, 10000.0, 3), 4708589871104),
    "kimi-k2-tp8": (
        {"heads": 8, "vocab": 20480,
         "moe_layers": [False, True, True, True], "rope_theta": 50000.0},
        (8, 20480, 50000.0, 1), 4225775669248),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_shipped_configurations_read_as_before(name):
    """The arch dict, the ModelConfig and the model FLOPs of one tick (two
    decode rows of a 12,289- and a 16,384-token context, a first chunk and
    one after a 12,288-token cached prefix) equal the numbers read before
    the lookup by model_type."""
    varying, mc, flops = GOLDEN[name]
    c = config.load(CONFIGS / f"{name}.json")
    assert config.arch(c) == {
        "layers": 4, "d_model": 7168, "d_ff": 18432, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope": 128, "qk_rope": 64, "v_head": 128,
        "experts": 8, "top_k": 8, "d_ff_expert": 2048, "shared_experts": 1,
        "norm_eps": 1e-06, "dtype": "bfloat16", **varying}
    assert config.model_config(c, name) == _model_config(name, *mc)
    mod = config.architecture(c)
    assert mod.step_flops(config.arch(c), [12289, 16384],
                          [(0, 512), (12288, 512)]) == flops


def test_unknown_model_type_names_the_file_it_looked_for(tmp_path):
    c = {"model_type": "no_such_arch"}
    with pytest.raises(ValueError, match=r"arch/no_such_arch\.py"):
        config.arch(c)
    with pytest.raises(ValueError, match="no_such_arch"):
        config.model_config(c, "x")
    with pytest.raises(ValueError, match=re.escape(str(tmp_path / "no_such_arch.py"))):
        config.architecture(c, tmp_path)
    with pytest.raises(ValueError, match="unsupported model_type"):
        config.architecture({"model_type": "../harness/config"})

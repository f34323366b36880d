"""Operation and byte counts of the kernels and of the whole step, against
hand-computed shapes at a small size."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import config  # noqa: E402
from work import etap_decode, etap_prefill, step  # noqa: E402

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

"""DeepSeek-V3.2 (DeepSeek Sparse Attention) in the benchmark, on the CPU
at a tiny size: a deepseek_v32 configuration runs a cell through the
harness to correct and its float8 control misses the limits; the model
FLOPs and the new kernels' work on hand-counted cases; and each new
metric reader on a small synthetic trace, None where its operations are
absent."""
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell, config, dsa, tap, traffic  # noqa: E402
from test_bench_reference import FLAGS, tiny  # noqa: E402
from work import dsa_index, etap_decode, step  # noqa: E402

# limits of the tiny cell.  It runs in float32: at 48-row contexts with a
# top-24 selection, bfloat16 activations move index scores across the
# threshold and a moved row is a large share of the attention, so the
# program would read as far from the reference as the control.  In
# float32 the program reads served_gap 0 and logit_err ~1e-6, the float8
# control far above both limits.
LIMITS = {"served_gap": 0.02, "logit_err": 0.06}


def load(name: str):
    """A metric reader, as the harness loads it."""
    return cell.metric_reader(ROOT, name)


def tiny32(dtype: str = "float32", topk: int = 24) -> dict:
    """A 3 dense + 1 MoE DeepSeek-V3.2 stack whose top-k is well below the
    contexts the tiny cell serves (up to 48 rows)."""
    return dict(tiny(3, dtype), model_type="deepseek_v32", index_n_heads=4,
                index_head_dim=16, index_topk=topk)


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with a tiny deepseek_v32 configuration and
    cell added as new files and BENCHMARK.json entries."""
    root = tmp_path / "co"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    b = root / "bench"
    (b / "configs" / "tiny32.json").write_text(json.dumps(tiny32()))
    mix = {"why": "tiny", "flags": dict(
        FLAGS, **{"--arrival-rate": 0.5, "--trace": "burst",
                  "--burst-size": 1, "--batch": 4}),
        "requests_per_second": 2.0, "warmup_requests": 3,
        "trace": {"start_frac": 0.3, "ticks": 6, "python_ticks": 3}}
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (b / "workloads" / "tiny32-cell.json").write_text(json.dumps(
        {"config": "tiny32", "traffic": "tiny-mix",
         "check": {"requests": 2, "limits": LIMITS}}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny32", "source": "test",
                            "file": "bench/configs/tiny32.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny32-cell", "config": "tiny32",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        if m["name"] in ("batch_occupancy", "dsa_selected_share"):
            m["workloads"].append("tiny32-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(cell, "enable_compile_cache", lambda root: None)
    return root


def run_tiny(root, seed, traced=False, control=False):
    return cell.run(root, "tiny32-cell", seed, 4, traced, 0.0,
                    require_chip=False, control=control)


def test_tiny_v32_cell_runs_to_correct(checkout):
    out = run_tiny(checkout, 3_000_000_321, traced=True)
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    share = out["metrics"]["dsa_selected_share"]["value"]
    assert 0 < share < 100          # the top-k left rows out
    assert out["metrics"]["batch_occupancy"]["value"] > 0


def test_v32_control_misses_the_limits(checkout):
    for seed in (0, 1):
        assert run_tiny(checkout, seed)["correct"] is True
        out = run_tiny(checkout, seed, control=True)
        assert out["correct"] is False
        assert any(v["value"] > v["limit"] for v in out["checks"].values())


def test_v32_configuration_builds_the_indexer():
    c = config.load(BENCH / "configs" / "deepseek-v32-tp8.json")
    cfg = config.model_config(c, "deepseek-v32-tp8")
    ix = cfg.mla.indexer
    assert (ix.n_heads, ix.head_dim, ix.topk) == (64, 128, 2048)
    assert cfg.num_layers == 7 and cfg.moe.first_dense_layers == 3
    r1 = config.model_config(
        config.load(BENCH / "configs" / "deepseek-r1-tp8.json"), "r1")
    assert r1.mla.indexer is None


# ------------------------------------------------ the recorded selection
@pytest.fixture(scope="module")
def bf16_run():
    """The tiny stack served in bfloat16 with its selections recorded, and
    the sequences and rows the check hands the reference."""
    from reference import mla_dsa
    from repro.launch import serve
    c = tiny32("bfloat16")
    a = config.arch(c)
    v32 = config.architecture(c, BENCH / "arch")
    cfg = v32.model_config(c, "tiny")
    flags = dict(FLAGS, **{"--seed": 5, "--requests": 3})
    res = serve.run_paged(serve.parse_args(traffic.argv(flags)), cfg,
                          record_logits=True)
    reqs = traffic.make_requests(flags, 3, 5, a["vocab"])
    seqs, rows = [], []
    for r in reqs:
        out = list(res["outputs"][r.id])
        seqs.append(np.concatenate([r.prompt, np.asarray(out[:-1],
                                                         np.int32)]))
        rows.append(np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(out)))

    def ref(selected=None, check=None):
        return mla_dsa.logits(a, 5, seqs, rows, selected=selected,
                              band=v32.NEAR_TIE, overlap=v32.SHARE,
                              check=check)
    return SimpleNamespace(
        ref=ref, reqs=reqs, res=res, own=ref(), seqs=seqs,
        selected=[res["dsa_selected"][r.id]["rows"] for r in reqs])


def _logit_err(run, ref) -> float:
    """The check's logit_err of the recorded rows against ``ref``."""
    err = scale = 0.0
    for i, r in enumerate(run.reqs):
        for j, row in ((0, run.res["seed_logits"][r.id]),
                       (1, run.res["first_decode_logits"][r.id])):
            err = max(err, float(np.max(np.abs(row - ref[i][j]))))
            scale = max(scale, float(np.max(np.abs(ref[i][j]))))
    return err / scale


def test_recorded_selection_takes_the_near_ties_out(bf16_run):
    """In bfloat16 the program keeps other rows than the float32 reference
    near the k-th score, and a moved row is a large share of a top-24
    attention: under its own selection the reference reads the program
    0.49 away (seed 5).  Attending the program's recorded rows, which pass
    the gate on every sequence, it reads 0.019: the rest is rounding."""
    check = {}
    adopted = bf16_run.ref(bf16_run.selected, check)
    assert all(c["adopted"] for c in check["seqs"].values())
    assert len(check["seqs"]) == 3
    own, got = _logit_err(bf16_run, bf16_run.own), _logit_err(bf16_run,
                                                               adopted)
    assert own > 0.3 and got < own / 10


@pytest.mark.parametrize("fault", ["first-layer rows", "count", "share"])
def test_selection_that_fails_the_gate_is_not_adopted(bf16_run, fault):
    """A record whose first-layer rows are swapped for unselected ones
    (caught by the margin), one position short (the count), or a later
    layer's rows swapped for unselected ones (the share) is refused for its
    whole sequence: that sequence reads exactly as with no record, the
    others keep theirs."""
    i = max(range(3), key=lambda k: len(bf16_run.seqs[k]))
    record = dict(bf16_run.selected[i])
    pos = len(bf16_run.seqs[i]) - 1           # the last row the check reads
    sel = np.array(record[pos], np.int64)
    n = sel.shape[1]
    assert pos + 1 >= 2 * n                   # room for n unselected rows
    layer = 2 if fault == "share" else 0
    if fault == "count":
        sel = sel[:, 1:]
    else:
        sel[layer] = np.setdiff1d(np.arange(pos + 1), sel[layer])[:n]
    record[pos] = sel
    selected = list(bf16_run.selected)
    selected[i] = record
    check = {}
    got = bf16_run.ref(selected, check)
    assert [check["seqs"][k]["adopted"] for k in range(3)] == \
        [k != i for k in range(3)]
    assert check["seqs"][i]["missed"] == (
        [(pos, k) for k in range(4)] if fault == "count" else [(pos, layer)])
    np.testing.assert_array_equal(got[i], bf16_run.own[i])


# ------------------------------------------------------------ the counts
ARCH = dict(config.arch(tiny32(topk=4)), layers=1, moe_layers=[False])


def test_causal_rows_by_hand():
    # rows at positions 2, 3, 4, 5 score 3, 4, 5, 6 rows and keep 3, 4, 4, 4
    assert dsa_index.causal_rows(2, 4, 4) == (18, 15)
    assert dsa_index.causal_rows(0, 1, 4) == (1, 1)
    assert dsa_index.causal_rows(10, 1, 4) == (11, 4)


def test_index_work_by_hand():
    # 2 query rows scoring 3 and 5 rows, 4 heads of 16, bf16 keys
    flops, nbytes = dsa_index.work([3, 5], 4, 16, 2)
    assert flops == 8 * (2 * 4 * 16 + 2 * 4)
    assert nbytes == 8 * 16 * 2 + 2 * 4 * 16 * 2 + 2 * 4 * 4 + 8 * 4


def test_v32_step_flops_caps_attention_and_adds_the_indexer():
    from arch import deepseek_v32
    a = ARCH
    # one decode row with 10 causal rows, one chunk of 2 rows at start 1
    got = deepseek_v32.step_flops(a, [10], [(1, 2)])
    attended = 4 + 2 + 3                       # min(4, 10), min(4,2), 3
    scored = 10 + 2 + 3
    per_row = 2 * 4 * 16 + 2 * 4
    want = (step.step_flops(a, 3, attended)
            + scored * per_row + 3 * dsa_index.projection_flops(a))
    assert got == want
    assert dsa_index.projection_flops(a) == 2 * (32 * 4 * 16 + 64 * 16
                                                 + 64 * 4)


# ------------------------------------------------------------- readers
def _run(ops, modules, ticks, counters=None, arch=None):
    return SimpleNamespace(
        trace={"ops": ops, "modules": modules, "window_s": 1.0,
               "busy_s": 0.5},
        trace_ticks=(0, len(ticks)), ticks=ticks, pool_bytes=2,
        arch=arch or dict(ARCH, layers=2, index_topk=4, heads=4,
                          kv_lora_rank=32, qk_rope=8),
        peaks={"bf16_flops": 1e12, "hbm_bytes_s": 1e11},
        result={"metrics": {"counters": counters or {}}})


# two decode steps of 2 layers each: index kernel, select ops, sparse
# decode; one prefill program with its own index kernel
OPS = []
for s0 in (0, 10_000):
    for layer in range(2):
        t = s0 + layer * 4_000
        OPS += [("%dsa_index_decode_paged.3 = f32[8,1,256]", t, 1_000),
                ("%sort.5 = (f32[8,256])", t + 1_200, 500),
                ("%gather.9 = bf16[8,4,40]", t + 1_800, 300),
                ("%dsa_sparse_decode.4 = bf16[8,4,32]", t + 2_200, 700)]
OPS += [("%dsa_index_prefill_paged.7 = f32[1,2,256]", 30_000, 900),
        ("%etap_prefill_mla_paged.1 = bf16[1,8,32]", 31_000, 800)]
MODULES = [("serve_decode_step", 0, 8_000),
           ("serve_decode_step", 10_000, 8_000),
           ("serve_prefill_chunk", 29_900, 2_000)]
TICKS = [tap.TickWork(0.0, (10, 3), ()), tap.TickWork(1.0, (11,), ())]


def test_dsa_step_and_select_readers():
    run = _run(OPS, MODULES, TICKS)
    assert load("dsa_decode_step_ms")(run) == pytest.approx(8e-3)
    # per step: 2 layers x (500 + 300) ns
    assert load("dsa_select_ms")(run) == pytest.approx(1.6e-3)
    empty = _run([o for o in OPS if "dsa" not in o[0]], MODULES, TICKS)
    assert load("dsa_decode_step_ms")(empty) is None
    assert load("dsa_select_ms")(empty) is None
    assert dsa.select_ns(OPS)[:2] == [800, 800]


def test_dsa_kernel_roofline_readers():
    run = _run(OPS, MODULES, TICKS)
    A = run.arch
    f, b = dsa_index.work([10, 3], 4, 16, 2)
    f2, b2 = dsa_index.work([11], 4, 16, 2)
    t_ns = 4 * 1_000
    want = 100.0 * max(2 * (f + f2) / 1e12, 2 * (b + b2) / 1e11) / (t_ns / 1e9)
    assert load("dsa_index_roofline")(run) == pytest.approx(want)
    f, b = etap_decode.work([4, 3], 4, 40, 32, 2)
    f2, b2 = etap_decode.work([4], 4, 40, 32, 2)
    t_ns = 4 * 700
    want = 100.0 * max(2 * (f + f2) / 1e12, 2 * (b + b2) / 1e11) / (t_ns / 1e9)
    assert A["kv_lora_rank"] + A["qk_rope"] == 40
    assert load("dsa_decode_roofline")(run) == pytest.approx(want)
    bare = _run([o for o in OPS if "dsa" not in o[0]], MODULES, TICKS)
    assert load("dsa_index_roofline")(bare) is None
    assert load("dsa_decode_roofline")(bare) is None
    assert load("dsa_index_roofline")(
        SimpleNamespace(trace=None)) is None


def test_dsa_selected_share_reader():
    read = load("dsa_selected_share")
    run = _run(OPS, MODULES, TICKS, {"dsa/rows_scored": 200,
                                     "dsa/rows_selected": 50})
    assert read(run) == pytest.approx(25.0)
    assert read(_run(OPS, MODULES, TICKS, {"serve/ticks": 3})) is None

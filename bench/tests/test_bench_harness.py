"""The harness, driven on the CPU at a tiny size with its look for a chip
skipped: it finds a configuration, a traffic mix, a cell and a per-layer
metric that were added as new files; a run with the timed path broken
underneath comes out not correct; the control (the reference in float8 in
the program's place) misses the cell's limits; and without a TPU the
command exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell  # noqa: E402
from test_bench_reference import FLAGS, tiny  # noqa: E402

# limits of the tiny bfloat16 cell, from its readings through the harness
# on the CPU over seeds 0-5: the program reads served_gap 0-0.0021 and
# logit_err 0.014-0.021, the float8 control 0.015-0.088 and 0.146-0.316
LIMITS = {"served_gap": 0.02, "logit_err": 0.06}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with a tiny configuration, traffic mix, cell
    and per-layer metric added as new files and BENCHMARK.json entries."""
    root = tmp_path / "co"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    b = root / "bench"
    (b / "configs" / "tiny.json").write_text(json.dumps(tiny(3, "bfloat16")))
    mix = {"why": "tiny", "flags": dict(
        FLAGS, **{"--arrival-rate": 0.5, "--trace": "burst",
                  "--burst-size": 1, "--batch": 4}),
        "requests_per_second": 2.0,
        "warmup_requests": 3,
        "trace": {"start_frac": 0.3, "ticks": 6, "python_ticks": 3}}
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (b / "workloads" / "tiny-cell.json").write_text(json.dumps(
        {"config": "tiny", "traffic": "tiny-mix",
         "check": {"requests": 2, "limits": LIMITS}}))
    (b / "metrics" / "tiny_metric.py").write_text(
        "def read(run):\n    return 42.0 + 0 * run.result['steps']\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-cell", "config": "tiny",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tiny_metric", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "serve loop", "moves": "output_tok_s",
                              "workloads": ["tiny-cell"]})
    for m in spec["per_layer"]:
        if m["name"] == "batch_occupancy":
            m["workloads"].append("tiny-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    # tests never turn the persistent compile cache on
    monkeypatch.setattr(cell, "enable_compile_cache", lambda root: None)
    return root


def run_tiny(root, traced=False, seed=3_000_000_123):
    return cell.run(root, "tiny-cell", seed, 4, traced, 0.0,
                    require_chip=False)


def test_finds_added_files_and_runs_them(checkout):
    c = cell.load_cell(checkout, "tiny-cell")
    assert c.config["hidden_size"] == 64 and c.mix["flags"]["--batch"] == 4
    names = [m["name"] for m in cell.metric_entries(c, "per_layer")]
    assert "tiny_metric" in names and "prefix_hit_share" not in names
    out = run_tiny(checkout, traced=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8
    assert out["metrics"]["tiny_metric"] == {"value": 42.0, "unit": "%"}
    assert out["metrics"]["batch_occupancy"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(LIMITS)


def _altered_token(monkeypatch):
    """Every decode step's logits pushed towards token 3."""
    from repro.models import model
    step = model.decode_step

    def broken(*a, **k):
        logits, cache = step(*a, **k)
        return logits.at[:, 3].add(1e3), cache
    monkeypatch.setattr(model, "decode_step", broken)


def _half_left_out(monkeypatch):
    """The program serves only the first half of the requests."""
    from repro.launch import serve
    make = serve._make_requests
    monkeypatch.setattr(serve, "_make_requests",
                        lambda args, vocab: make(args, vocab)[::2])


TINY_ARCH = """\"\"\"A test architecture: DeepSeek-V3's, with a reference that
leaves a mark beside this file for each call.\"\"\"
from pathlib import Path

from arch import deepseek_v3

arch, model_config = deepseek_v3.arch, deepseek_v3.model_config
step_flops = deepseek_v3.step_flops


def logits(arch, seed, seqs, rows, *, mode):
    with (Path(__file__).parent / "tiny_v3.calls").open("a") as f:
        f.write(mode + "\\n")
    return deepseek_v3.logits(arch, seed, seqs, rows, mode=mode)
"""


def test_new_architecture_is_new_files_only(checkout):
    """An architecture added as bench/arch/<model_type>.py, with a
    configuration of that model_type, runs the cell to correct, and the
    check goes through the new module's reference."""
    b = checkout / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "arch" / "tiny_v3.py").write_text(TINY_ARCH)
    (b / "configs" / "tiny.json").write_text(json.dumps(
        dict(tiny(3, "bfloat16"), model_type="tiny_v3")))
    before.pop(b / "configs" / "tiny.json")      # the test's own file
    out = run_tiny(checkout, traced=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["batch_occupancy"]["value"] > 0
    assert (b / "arch" / "tiny_v3.calls").read_text().split() == ["fp32"]
    assert all(p.read_bytes() == v for p, v in before.items())


@pytest.mark.parametrize("fault", [_altered_token, _half_left_out])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    out = run_tiny(checkout)
    assert out["correct"] is False


def test_control_misses_the_limits(checkout):
    """The reference in float8 put in the program's place, on the same
    prompts and served tokens, comes out not correct against the cell's own
    limits on every seed, where the program comes out correct."""
    for seed in (0, 1, 2):
        assert run_tiny(checkout, seed=seed)["correct"] is True
        out = cell.run(checkout, "tiny-cell", seed, 4, False, 0.0,
                       require_chip=False, control=True)
        assert out["correct"] is False
        assert any(v["value"] > v["limit"] for v in out["checks"].values())


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    cmd = [sys.executable, "bench/run.py", "--workload", "r1-doc-decode",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout

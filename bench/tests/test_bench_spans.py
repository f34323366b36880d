"""The metrics that read the serve loop's own spans, on hand-built span
and operation lists with known answers (times in ns)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import cell, spans  # noqa: E402

# two ticks, [0,100) and [120,200), and the 20 ns between them
SPANS = [
    ("serve.tick", 0, 100),
    ("sched.admit", 0, 10), ("prefix.match", 2, 4),
    ("serve.prefill.launch", 10, 10), ("serve.prefill.wait", 20, 18),
    ("prefix.insert", 38, 2),
    ("serve.decode.launch", 40, 10), ("serve.decode.wait", 50, 30),
    ("serve.decode.retire", 80, 10),
    ("serve.tick", 120, 80),
    ("serve.decode.launch", 120, 10), ("serve.decode.wait", 130, 50),
    ("python.gc", 185, 10),
]
# device busy [12,36) (a prefill chunk), [45,78) and [125,176) (decode
# steps): idle [0,12), [36,45), [78,125), [176,200)
OPS = [("%etap_prefill_mla_paged.9 = bf16[1,512]", 12, 24),
       ("%etap_decode_mla_paged.12 = bf16[16,16,512]", 45, 33),
       ("%etap_decode_mla_paged.12 = bf16[16,16,512]", 125, 51)]
# idle by span: host work 12 + (2 + 5) + (10 + 5) + 10 (gc) = 44; waits
# 2 + 2 + 4 = 8; in a tick under no leaf span [90,100), [180,185),
# [195,200) = 20; between the ticks [100,120) = 20
IDLE = {"range": 200, "idle": 92, "host_work": 44, "wait": 8,
        "tick_other": 20, "outside": 20, "gc": 10}


def _run(serve_spans, hist):
    return SimpleNamespace(
        serve_spans=serve_spans,
        trace={"ops": OPS, "window_s": 2e-7, "busy_s": 1.08e-7},
        result={"metrics": {"histograms": hist}})


@pytest.mark.parametrize("name,want", [
    # tick 1: 100 - (18 + 30) waits = 52; tick 2: 80 - 50 = 30
    ("host_ms_per_tick", 41e-6),
    # prefix.match 4 + prefix.insert 2 over 2 ticks
    ("prefix_host_ms_per_tick", 3e-6),
    ("prefill_wait_p90_ms", 12.5),
    # 44 of 200 ns: the gap between the ticks and the waits do not count
    ("idle_host_work_share", 22.0),
])
def test_span_metric_readers(name, want):
    run = _run(SPANS, {"sched/prefill_wait_ms": {"count": 5, "p90": 12.5}})
    assert cell.metric_reader(BENCH.parent, name)(run) \
        == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_ms_per_tick",
                                  "prefix_host_ms_per_tick",
                                  "prefill_wait_p90_ms",
                                  "idle_host_work_share"])
def test_span_metrics_say_nothing_without_spans(name):
    """A program without the spans or the histogram: no reading, no
    error."""
    assert cell.metric_reader(BENCH.parent, name)(_run(None, {})) is None


def test_idle_by_span_and_clock_shares():
    assert spans.idle_by_span(OPS, SPANS) == IDLE
    decode = [op for op in OPS if "decode" in op[0]]
    assert spans.inside_share(decode, SPANS, "serve.decode.") == 100.0
    # a decode operation half outside its spans
    late = [("%etap_decode_mla_paged.12", 90, 20)]
    assert spans.inside_share(late, SPANS, "serve.decode.") == 0.0
    assert spans.inside_share(late + decode, SPANS, "serve.decode.") \
        == pytest.approx(100.0 * 84 / 104)
    assert spans.idle_by_span(OPS, [ev for ev in SPANS
                                    if ev[0] != "serve.tick"]) is None


def test_interval_algebra():
    a = [[0, 10], [20, 30]]
    b = [[5, 25]]
    assert spans._inter(a, b) == [[5, 10], [20, 25]]
    assert spans._minus(a, b) == [[0, 5], [25, 30]]
    assert spans._minus(a, []) == a and spans._inter(a, []) == []

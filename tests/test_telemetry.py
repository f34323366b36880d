"""Telemetry layer (DESIGN.md §15): histogram algebra, ring-buffer
bounds, Chrome trace-event schema, spans on the profiler's clock, and the
serve-loop acceptance criteria — telemetry-on is BITWISE output-identical
to telemetry-off (fp and int8+prefix-cache legs, profiler session on and
off), the exported trace covers the full request lifecycle under a
contended burst, and the profiler's trace holds the serve loop's spans."""
import dataclasses
import gc
import json
import math
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.runtime import telemetry


# ------------------------------------------------------------- histogram
def _exact_nearest_rank(vals, q):
    s = sorted(vals)
    return s[max(1, math.ceil(q / 100.0 * len(s))) - 1]


def test_histogram_resolution_pin():
    """Quantization contract: every percentile of a positive sample is
    within ~rel_err of the EXACT nearest-rank percentile — the
    equal-or-better-than-raw-lists resolution the scheduler's class_stats
    migration relies on (the old _pct helper interpolated over raw
    lists; the histogram must not be meaningfully coarser)."""
    rng = np.random.default_rng(0)
    vals = np.exp(rng.normal(size=2000)).tolist()          # lognormal > 0
    h = telemetry.Histogram.from_values(vals, rel_err=0.01)
    for q in (10, 50, 90, 99, 99.9):
        exact = _exact_nearest_rank(vals, q)
        assert abs(h.percentile(q) - exact) <= 0.015 * exact, q
    assert h.count == 2000
    assert abs(h.mean - np.mean(vals)) <= 0.015 * np.mean(vals)
    assert h.vmin == min(vals) and h.vmax == max(vals)


def test_histogram_zero_and_negative():
    h = telemetry.Histogram.from_values([-1.0, 0.0, 5.0])
    assert h.zero == 2 and h.count == 3
    assert h.percentile(50) == 0.0                 # rank 2 of 3 → zero bucket
    assert abs(h.percentile(100) - 5.0) <= 0.015 * 5.0
    assert telemetry.Histogram(0.01).percentile(50) == 0.0   # empty → 0


def _hist_state(h):
    return (dict(h.counts), h.zero, h.vmin, h.vmax, h.to_dict())


def _check_merge(a, b, c):
    ha = telemetry.Histogram.from_values(a)
    hb = telemetry.Histogram.from_values(b)
    hc = telemetry.Histogram.from_values(c)
    frozen = (_hist_state(ha), _hist_state(hb))
    # commutative + associative, exactly (integer bucket counts)
    assert _hist_state(ha.merge(hb)) == _hist_state(hb.merge(ha))
    assert _hist_state(ha.merge(hb).merge(hc)) \
        == _hist_state(ha.merge(hb.merge(hc)))
    # merge of split streams == single-pass over the concatenation
    assert _hist_state(ha.merge(hb)) \
        == _hist_state(telemetry.Histogram.from_values(list(a) + list(b)))
    # operands untouched
    assert (_hist_state(ha), _hist_state(hb)) == frozen


def _rand_lists(seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(3):
        n = int(rng.integers(0, 50))
        out.append((rng.standard_normal(n) * 10.0 ** rng.integers(-3, 4))
                   .tolist())
    return out


if HAVE_HYPOTHESIS:
    _floats = st.lists(
        st.floats(min_value=-1e9, max_value=1e9,
                  allow_nan=False, allow_infinity=False), max_size=60)

    @settings(max_examples=60, deadline=None)
    @given(_floats, _floats, _floats)
    def test_histogram_merge_property(a, b, c):
        _check_merge(a, b, c)
else:
    def test_histogram_merge_property():
        """Deterministic stand-in for the hypothesis property (keeps the
        tier-1 skip count flat when hypothesis is absent)."""
        for seed in range(60):
            _check_merge(*_rand_lists(seed))


def test_histogram_merge_resolution_mismatch():
    with pytest.raises(AssertionError):
        telemetry.Histogram(0.01).merge(telemetry.Histogram(0.05))


# -------------------------------------------------------------- registry
def test_registry_kinds_and_snapshot():
    reg = telemetry.MetricsRegistry()
    reg.counter("a/n").inc(3)
    reg.inc("a/n", 2)
    reg.gauge("a/g").set(7)
    reg.observe("a/h", 1.0)
    assert reg.counter("a/n").value == 5         # create-or-get, one object
    assert reg.value("a/n") == 5 and reg.value("a/g") == 7.0
    with pytest.raises(AssertionError):          # one name, one kind
        reg.gauge("a/n")
    snap = reg.snapshot()
    json.dumps(snap)                             # plain JSON types only
    assert snap["schema_version"] == telemetry.OBS_SCHEMA_VERSION
    assert snap["counters"] == {"a/n": 5}
    assert snap["gauges"] == {"a/g": 7.0}
    assert snap["histograms"]["a/h"]["count"] == 1
    assert reg.op_count() == 2 + 1 + 1           # incs + sets + records


# --------------------------------------------------------------- tracing
def test_tracer_ring_bounded():
    tr = telemetry.Tracer(capacity=8, clock=iter(range(10 ** 6)).__next__)
    for i in range(100):
        tr.instant(f"e{i}")
    assert tr.recorded == 100 and tr.dropped == 92
    evs = tr.to_events()
    assert len(evs) == 8 + 1                     # ring + process_name meta
    assert evs[-1]["name"] == "e99"              # newest survive


def test_trace_export_schema(tmp_path):
    tr = telemetry.Tracer(capacity=64)
    tr.instant("enqueued", tid=1001, args={"req": 1})
    with tr.span("serve.prefill.launch", req=1, tokens=8):
        pass
    t0 = tr.now_us()
    tr.complete("serve.decode.wait", t0)
    path = str(tmp_path / "trace.json")
    stats = tr.export(path)
    assert stats["recorded"] == 3 and stats["dropped"] == 0
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["otherData"]["schema_version"] == telemetry.OBS_SCHEMA_VERSION
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] == "t"
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)                      # monotonic after sort


# ------------------------------------------------------------ end to end
def _serve(argv, cfg):
    from repro.launch import serve
    return serve.run_paged(serve.parse_args(argv), cfg)


def _no_moe_cfg():
    from repro.configs import get_config, reduced
    return dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                               moe=None)


BURST = ["--reduced", "--batch", "2", "--prompt", "24", "--gen", "8",
         "--requests", "6", "--page-size", "8", "--prefill-chunk", "8",
         "--cache-layout", "paged", "--priority-classes", "3",
         "--arrival-rate", "0.25", "--trace", "burst", "--burst-size", "3",
         "--retry-backoff", "4", "--preemption", "recompute",
         "--spec-tokens", "2", "--seed", "0"]


def test_serve_trace_bitwise_and_lifecycle(tmp_path):
    """ACCEPTANCE: under a multi-tenant burst with speculation, a
    --trace-out/--metrics-out run is bitwise output-identical to a plain
    run, and the exported trace covers prefill / decode / verify spans
    plus the lifecycle instants (preemption/restore on the contended fp
    leg).  Also pins that class_stats() percentiles and the registry
    snapshot read the SAME histograms — one percentile code path."""
    cfg = _no_moe_cfg()
    plain = _serve(BURST, cfg)
    tpath, mpath = str(tmp_path / "t.json"), str(tmp_path / "m.json")
    inst = _serve(BURST + ["--trace-out", tpath, "--metrics-out", mpath],
                  cfg)
    assert inst["outputs"] == plain["outputs"]
    assert inst["tokens_served"] == plain["tokens_served"]

    doc = json.load(open(tpath))
    evs = doc["traceEvents"]
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    names = {e["name"] for e in evs}
    need = {"enqueued", "admitted", "finished", "serve.tick",
            "serve.prefill.wait", "serve.decode.wait", "serve.verify.wait"}
    if inst["kv_dtype"] == "fp":       # quantized legs widen slots and may
        assert inst["sched"]["preempts_recompute"] > 0   # never contend
        need |= {"preempted", "restored"}
    assert need <= names, names - need

    met = json.load(open(mpath))
    assert met["meta"]["schema_version"] == telemetry.OBS_SCHEMA_VERSION
    snap = inst["metrics"]
    assert met["metrics"] == snap
    assert snap["counters"]["serve/decode_tokens"] == inst["decode_tokens"]
    # one arrival -> first-chunk wait per request, restores not again
    assert snap["histograms"]["sched/prefill_wait_ms"]["count"] == 6
    # class_stats() and the snapshot render from the same histograms
    for cls, cstats in inst["classes"].items():
        hd = snap["histograms"][f"sched/class{cls}/ttft_ms"]
        assert cstats["ttft_p50_ms"] == hd["p50"]
        assert cstats["ttft_p99_ms"] == hd["p99"]
        assert cstats["n"] == snap["counters"][f"sched/class{cls}/done"]


def test_serve_bitwise_int8_prefix(tmp_path):
    """ACCEPTANCE: the bitwise telemetry-on == telemetry-off identity
    holds on the int8 + prefix-cache path too."""
    cfg = _no_moe_cfg()
    base = ["--reduced", "--batch", "2", "--prompt", "16", "--gen", "8",
            "--requests", "3", "--page-size", "8", "--prefill-chunk", "8",
            "--cache-layout", "paged", "--kv-dtype", "int8",
            "--shared-prefix", "2", "--seed", "0"]
    plain = _serve(base, cfg)
    tpath, mpath = str(tmp_path / "t.json"), str(tmp_path / "m.json")
    inst = _serve(base + ["--trace-out", tpath, "--metrics-out", mpath],
                  cfg)
    assert inst["outputs"] == plain["outputs"]
    assert {"serve.prefill.wait", "serve.decode.wait", "prefix.match"} \
        <= {e["name"] for e in json.load(open(tpath))["traceEvents"]}
    assert json.load(open(mpath))["metrics"]["counters"][
        "serve/decode_tokens"] == inst["decode_tokens"]


def test_fault_injection_counters_pinned():
    """Satellite: one --fault-rate drill's counter totals line up across
    subsystems — every injected fault is one scheduler failure and one
    observed worker restart, all flowing through the one registry."""
    cfg = _no_moe_cfg()
    res = _serve(["--reduced", "--batch", "2", "--prompt", "16", "--gen",
                  "8", "--requests", "3", "--page-size", "8",
                  "--prefill-chunk", "8", "--cache-layout", "paged",
                  "--fault-rate", "0.2", "--seed", "0"], cfg)
    c = res["metrics"]["counters"]
    assert c["ft/injected_faults"] > 0
    assert c["ft/injected_faults"] == c["sched/failures"]
    assert c["ft/injected_faults"] == c["serve/worker_restarts"]
    assert c["serve/worker_restarts"] == res["worker_restarts"]
    assert c["ft/heartbeats"] == c["serve/ticks"]
    assert c["serve/replayed_tokens"] == res["replayed_tokens"]


# ------------------------------------------------- spans on the profiler
def test_tracer_without_ring_records_nothing():
    """capacity=0 (no --trace-out): spans reach the profiler only."""
    tr = telemetry.Tracer(capacity=0)
    with tr.span("serve.tick", step_num=3):
        with tr.span("prefix.match") as sp:
            sp.set_metadata(blocks=2)
    tr.instant("enqueued", tid=1001)
    tr.complete("serve.decode.wait", tr.now_us())
    assert tr.recorded == 0 and len(tr.to_events()) == 1   # meta only


def test_gc_spans_cover_collections_and_uninstall():
    tr = telemetry.Tracer(capacity=64)
    n_callbacks = len(gc.callbacks)
    enabled = gc.isenabled()
    gc.disable()                                  # only the collections below
    try:
        with tr.gc_spans():
            assert len(gc.callbacks) == n_callbacks + 1
            gc.collect()
        assert len(gc.callbacks) == n_callbacks
        gc.collect()                              # no longer recorded
    finally:
        if enabled:
            gc.enable()
    spans = [e for e in tr.to_events() if e["name"] == "python.gc"]
    assert len(spans) == 1 and spans[0]["args"] == {"generation": 2}


def _host_events(log_dir) -> dict:
    """{line name: [(name, start_ns, dur_ns, stats)]} of the host planes
    of the newest xplane under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out.setdefault(line.name, []).extend(
                    (ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
                    for ev in line.events)
    return out


def _profiled(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


SPAN_RUN = ["--reduced", "--batch", "2", "--prompt", "16", "--gen", "6",
            "--requests", "4", "--page-size", "8", "--prefill-chunk", "8",
            "--cache-layout", "paged", "--shared-prefix", "8",
            "--arrival-rate", "0.5", "--seed", "0"]


@pytest.fixture(scope="module")
def profiled_serve(tmp_path_factory):
    """One tiny run_paged with no profiler session and one inside
    ``jax.profiler.trace``: (plain result, traced result, spans)."""
    cfg = _no_moe_cfg()
    plain = _serve(SPAN_RUN, cfg)
    log_dir = tmp_path_factory.mktemp("xplane")
    with _profiled(log_dir):
        traced = _serve(SPAN_RUN, cfg)
    return plain, traced, _host_events(log_dir)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_profiled_serve_spans_nest_in_ticks(profiled_serve):
    """The xplane holds one serve.tick per tick run; every prefill and
    decode span nests inside a tick; one wait span per device call the
    run's counters report."""
    _, res, host = profiled_serve
    spans = [ev for ev in host["python"]
             if ev[0].startswith(("serve.", "sched.", "prefix."))]
    ticks = [ev for ev in spans if ev[0] == "serve.tick"]
    c = res["metrics"]["counters"]
    assert len(ticks) == c["serve/ticks"]
    assert sorted(ev[3]["step_num"] for ev in ticks) \
        == list(range(c["serve/ticks"]))
    work = [ev for ev in spans
            if ev[0].startswith(("serve.prefill.", "serve.decode."))]
    assert work and all(any(_inside(ev, t) for t in ticks) for ev in work)
    waits = [ev for ev in spans if ev[0].endswith(".wait")]
    assert len(waits) == c["serve/prefill_chunks"] + c["serve/decode_steps"]
    names = {ev[0] for ev in spans}
    assert {"sched.admit", "prefix.match", "prefix.insert",
            "serve.prefill.launch", "serve.decode.launch",
            "serve.decode.retire"} <= names
    # the jitted serve programs carry their names into the trace
    modules = {ev[3].get("hlo_module") for evs in host.values()
               for ev in evs}
    assert {"jit_serve_decode_step", "jit_serve_prefill_chunk"} <= modules


def test_profiled_serve_is_bitwise_and_records_prefill_wait(profiled_serve):
    plain, res, _ = profiled_serve
    assert res["outputs"] == plain["outputs"]
    h = res["metrics"]["histograms"]["sched/prefill_wait_ms"]
    assert h["count"] == 4 and h["max"] > 0.0


def test_span_and_device_op_share_one_clock(tmp_path):
    """A jitted matmul run inside serve.tick > serve.decode.wait: its
    operation event on the CPU client's thread lies inside the wait span,
    so spans and operations are on one clock."""
    tr = telemetry.Tracer(capacity=0)
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    with _profiled(tmp_path):
        with tr.span("serve.tick", step_num=0):
            with tr.span("serve.decode.wait"):
                f(x).block_until_ready()
    host = _host_events(tmp_path)
    (wait,) = [ev for ev in host["python"] if ev[0] == "serve.decode.wait"]
    (tick,) = [ev for ev in host["python"] if ev[0] == "serve.tick"]
    ops = [ev for name, evs in host.items()
           if name.startswith("tf_XLAPjRtCpuClient") for ev in evs
           if ev[0].startswith("dot_general")]
    assert ops and _inside(wait, tick)
    assert all(_inside(op, wait) for op in ops)

"""One run of one benchmark cell.

Set-up: read the cell's files, check the chip, warm every shape the cell's
traffic uses with a short ``run_paged`` pass (its programs go to the
persistent compile cache), then the measured ``run_paged`` call: the
window is its serve loop.  After the window: the end-to-end or per-layer
metrics, the program's counters against the benchmark's own copy of the
request generator, and the comparison with the plain reference that
decides ``correct``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from harness import config as config_mod
from harness import kernels
from harness import peaks as peaks_mod
from harness import tap, traffic
from harness import trace as trace_mod
from harness.stats import nearest_rank

BENCH = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
POOL_BYTES = {"fp": 2, "int8": 1, "fp8": 1}
# lines of a TPU plane in the profiler's trace: one event per operation,
# one per program execution
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class NoChip(RuntimeError):
    pass


def _say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the files
def load_cell(root: Path, name: str) -> SimpleNamespace:
    """The cell's entry in BENCHMARK.json and the files it names:
    bench/workloads/<cell>.json, bench/traffic/<traffic>.json, the
    configuration's file and its architecture's bench/arch/<model_type>.py."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    bench = root / "bench"
    cell = json.loads((bench / "workloads" / f"{name}.json").read_text())
    mix = json.loads((bench / "traffic" / f"{wl['traffic']}.json")
                     .read_text())
    if cell["config"] != wl["config"] or cell["traffic"] != wl["traffic"]:
        raise SystemExit(f"{name}: workload file and BENCHMARK.json differ")
    config = config_mod.load(root / cfg_entry["file"])
    return SimpleNamespace(name=name, spec=spec, entry=wl, cell=cell,
                           mix=mix, config_name=wl["config"], config=config,
                           architecture=config_mod.architecture(
                               config, bench / "arch"),
                           chips=int(wl["chips"]), root=root)


def metric_entries(c: SimpleNamespace, kind: str) -> list:
    """The BENCHMARK.json metrics of ``kind`` this cell reports."""
    return [m for m in c.spec[kind]
            if "workloads" not in m or c.name in m["workloads"]]


def metric_reader(root: Path, name: str):
    """bench/metrics/<name>.py's ``read``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- device
def device_info(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"JAX finds {len(devs)} {d.platform} device(s); this "
                     f"cell needs {chips} TPU chip(s)")
    return d, devs


def enable_compile_cache(root: Path) -> None:
    """The persistent cache lives inside the checkout, at a fixed path,
    and keeps every program, however small or fast to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileLog:
    """Lowering and compile events of jax.monitoring, with the wall clock
    at which each ended."""

    def __init__(self):
        import jax
        self.events = []
        self.hits = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, name, secs, fun_name="", **_):
        if name in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), name, float(secs),
                                fun_name))

    def _hit(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._hit)


# ---------------------------------------------------------------- trace
class Tracer:
    """Starts and stops the device trace at fixed ticks of the loop: one
    segment with the host's own trace events for the device numbers, then
    a short one with the Python tracer on, whose host events name what the
    host ran in the device's idle gaps."""

    def __init__(self, out_dir: Path, start: int, ticks: int, py_ticks: int):
        self.dir = out_dir
        self.plan = {start: ("dev", 0), start + ticks: ("stop", None),
                     start + ticks + 1: ("py", 1),
                     start + ticks + 1 + py_ticks: ("stop", None)}
        self.ticks = (start, start + ticks)
        self.window = {}
        self.active = None

    def __call__(self, k: int) -> None:
        step = self.plan.get(k)
        if step is None:
            return
        import jax
        kind, py = step
        if kind == "stop":
            self.window[self.active][1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.active = None
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = py
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir / kind), profiler_options=opts)
        self.active = kind
        self.window[kind] = [time.perf_counter(), None]

    def close(self) -> None:
        """Stop a segment the loop ended inside of."""
        if self.active is not None:
            import jax
            self.window[self.active][1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.active = None


def tpu_planes(data: dict) -> list:
    return [(k, v) for k, v in data["devices"].items()
            if k.startswith("/device:TPU")]


def reduce_trace(tr: Tracer) -> dict | None:
    """Device numbers of the first segment, gap attribution of the second."""
    path = trace_mod.newest_xplane(tr.dir / "dev")
    if path is None or tr.window.get("dev", [None, None])[1] is None:
        return None
    data = trace_mod.load(path)
    per_dev = []
    for _, lines in sorted(tpu_planes(data)):
        ops = lines.get(OPS_LINE, [])
        if ops:
            per_dev.append((ops, lines.get(MODULES_LINE, [])))
    if not per_dev:
        return None
    ops, modules = per_dev[0]
    t0, t1 = tr.window["dev"]
    out = {
        "window_s": t1 - t0,
        "busy_s": sum(trace_mod.busy_ns(o) for o, _ in per_dev)
        / len(per_dev) / 1e9,
        "ops": ops, "modules": modules,
        "device_ops": trace_mod.top(
            [(kernels.short(n), s, d) for n, s, d in ops], 10),
        "idle_gaps": [],
    }
    py = trace_mod.newest_xplane(tr.dir / "py")
    if py is not None:
        pdata = trace_mod.load(py)
        pdevs = [lines.get(OPS_LINE, [])
                 for _, lines in sorted(tpu_planes(pdata))]
        pops = pdevs[0] if pdevs else []
        if pops:
            lo = min(s for _, s, _ in pops)
            hi = max(s + d for _, s, d in pops)
            idle = trace_mod.gaps(pops, lo, hi)
            # the Python tracer's line with the most events: the loop's
            py_lines = [v for k, v in pdata["host"].items()
                        if k.startswith("python")] or list(
                            pdata["host"].values())
            host = max(py_lines, key=len, default=[])
            out["idle_gaps"] = trace_mod.attribute(idle, host, 10)
    return out


# ------------------------------------------------------------ the check
def sample_requests(reqs: list, finished: dict, n: int, seed: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    done = [r for r in reqs if r.id in finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.gen, -r.id))
    rest = [r for r in done if r.id != longest.id]
    rng = np.random.default_rng(seed + 7)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def _numbers(ref, served, got) -> tuple:
    """(widest gap, largest |logit error|, largest |reference logit|) of
    one request: ``served`` tokens against the reference rows ``ref``,
    ``got`` {row: logits} the rows to hold against the reference."""
    best = ref.max(axis=-1)
    picked = ref[np.arange(len(served)), served]
    err = scale = 0.0
    for j, row in got.items():
        if row is None or j >= len(ref):
            continue
        err = max(err, float(np.max(np.abs(np.asarray(row, np.float32)
                                           - ref[j]))))
        scale = max(scale, float(np.max(np.abs(ref[j]))))
    return float(np.max(best - picked)), err, scale


def compare(architecture, arch, seed, sample, outputs, seed_logits,
            first_logits, control: bool = False) -> dict:
    """The numbers ``correct`` is decided by, for the sampled requests.

    served_gap: the widest gap by which a served (greedy) token's
    reference logit lies below the reference's best at that position.
    logit_err: the largest |program - reference| logit over the rows the
    program recorded (each request's last prefill row and first decode
    row), over the largest |reference| logit of those rows.
    With ``control`` the reference in float8 is put in the program's place,
    on the same prompts and served tokens: its own argmax at each position
    is the served token, its rows are the recorded ones.  The reference
    is the configuration's architecture module's ``logits``."""
    seqs, rows = [], []
    for r in sample:
        served = list(outputs[r.id])
        seqs.append(np.concatenate([r.prompt, np.asarray(served[:-1],
                                                         np.int32)]))
        rows.append(np.arange(len(r.prompt) - 1,
                              len(r.prompt) - 1 + len(served)))
    ref = architecture.logits(arch, seed, seqs, rows, mode="fp32")
    alt = architecture.logits(arch, seed, seqs, rows, mode="fp8") \
        if control else None
    gap = err = scale = 0.0
    tokens = 0
    for i, r in enumerate(sample):
        if alt is None:
            served = np.asarray(outputs[r.id])
            got = {0: seed_logits.get(r.id), 1: first_logits.get(r.id)}
        else:
            served = np.argmax(alt[i], axis=-1)
            got = {0: alt[i][0], 1: alt[i][1] if len(alt[i]) > 1 else None}
        tokens += len(served)
        g, e, s = _numbers(ref[i], served, got)
        gap, err, scale = max(gap, g), max(err, e), max(scale, s)
    return {"tokens": tokens, "served_gap": gap,
            "logit_err": err / scale if scale else None}


def counter_faults(reqs, res, final) -> tuple:
    """Where the program's counters disagree with the request set:
    (faults, number of requests at fault)."""
    faults = []
    outs = res["outputs"]
    for r in reqs:
        if r.id not in outs:
            faults.append(f"request {r.id} not finished")
        elif len(outs[r.id]) != r.gen:
            faults.append(f"request {r.id} served {len(outs[r.id])} of "
                          f"{r.gen} tokens")
        elif r.id in final and final[r.id][0] != len(r.prompt):
            faults.append(f"request {r.id} prompt {final[r.id][0]} tokens, "
                          f"generator {len(r.prompt)}")
    n_bad = len(faults)
    if len(outs) != len(reqs):
        faults.append(f"{len(outs)} requests finished, {len(reqs)} sent")
    dec = sum(r.gen for r in reqs)
    if res["decode_tokens"] != dec:
        faults.append(f"decode tokens {res['decode_tokens']} != {dec}")
    pro = sum(len(r.prompt) for r in reqs)
    seen = res["prefill_tokens"] + res["prefill_tokens_saved"]
    if seen != pro:
        faults.append(f"prompt tokens {seen} != {pro}")
    return faults, n_bad


# ------------------------------------------------------------------ run
def run(root: Path, name: str, seed: int, seconds: int, traced: bool,
        t_start: float, require_chip: bool = True,
        control: bool = False) -> dict:
    c = load_cell(root, name)
    enable_compile_cache(root)
    dev, devs = device_info(c.chips, require_chip)
    pk = peaks_mod.peaks(dev.device_kind) if require_chip else \
        peaks_mod.PEAKS.get(dev.device_kind)

    from repro.kernels import softmax_state
    from repro.launch import serve
    clog = CompileLog()
    arch = c.architecture.arch(c.config)
    cfg = c.architecture.model_config(c.config, c.config_name)
    n = max(1, round(float(c.mix["requests_per_second"]) * seconds))
    prog_seed = traffic.program_seed(c.mix["flags"], n, seed, arch["vocab"])
    flags = dict(c.mix["flags"], **{"--seed": prog_seed, "--requests": n})
    reqs = traffic.make_requests(flags, n, prog_seed, arch["vocab"])

    # ---- set-up: warm every shape the window uses
    warm = traffic.warmup_flags(flags, reqs, int(c.mix["warmup_requests"]),
                                arch["vocab"])
    wargs = serve.parse_args(traffic.argv(warm))
    softmax_state.set_default_mode(wargs.rescale)
    serve.run_paged(wargs, cfg, record_logits=True)
    gc.collect()
    mem_setup = dev.memory_stats() or {}

    # ---- the window: the measured run_paged call, from its first tick
    # to its return on the harness's own clock
    args = serve.parse_args(traffic.argv(flags))
    rec = tap.Record()
    tracer = None
    if traced:
        out_dir = root / ".bench_runs" / name
        shutil.rmtree(out_dir, ignore_errors=True)
        tr = c.mix["trace"]
        last = max(r.arrival for r in reqs)
        tracer = Tracer(out_dir, max(1, int(tr["start_frac"] * last)),
                        int(tr["ticks"]), int(tr["python_ticks"]))
    mem_first = {}

    def on_tick(k: int) -> None:
        if k == 0:
            mem_first.update(dev.memory_stats() or {})
        if tracer is not None:
            tracer(k)
    rec.on_tick = on_tick
    try:
        with tap.installed(rec):
            res = serve.run_paged(args, cfg, record_logits=True)
    finally:
        if tracer is not None:
            tracer.close()
    t_ret = time.perf_counter()
    clog.close()
    loop = (rec.ticks[0][0], t_ret)
    loop_s = loop[1] - loop[0]
    setup_s = loop[0] - t_start
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    used = [res["pool_blocks"] - f for f in rec.pool_free]
    _say(f"memory: peak {mem_setup.get('peak_bytes_in_use')} B after the "
         f"warm-up, {mem_first.get('peak_bytes_in_use')} B at the window's "
         f"first tick, {peak} B at its end; in use "
         f"{mem_first.get('bytes_in_use')} B at the first tick, "
         f"{stats.get('bytes_in_use')} B at the end; pool blocks held "
         f"max {max(used, default=0)} mean "
         f"{sum(used) / max(len(used), 1):.1f} of {res['pool_blocks']}")

    # ---- end-to-end numbers
    first, last_t, itl = {}, {}, []
    for t, rid, t_arr in rec.deliveries:
        if rid not in first:
            first[rid] = (t - t_arr) * 1e3
        else:
            itl.append((t - last_t[rid]) * 1e3)
        last_t[rid] = t
    ttft = list(first.values())
    _say(f"samples: {len(ttft)} requests, {len(itl)} inter-token gaps, "
         f"{res['decode_tokens']} decode tokens, {res['prefill_tokens']} "
         f"prefill tokens ({res['prefill_tokens_saved']} from the prefix "
         f"cache) in {loop_s:.3f} s (the program's own timers: "
         f"{res['t_prefill'] + res['t_decode']:.3f} s); "
         f"{len(rec.ticks)} ticks")
    if itl:
        _say("itl ms by percentile: " + ", ".join(
            f"p{q} {nearest_rank(itl, q):.4f}"
            for q in (50, 75, 80, 85, 90, 95, 97, 99)))
    starts = [t for t, _ in rec.ticks] + [t_ret]
    long_ticks = sorted(((b - a, k) for k, (a, b)
                         in enumerate(zip(starts, starts[1:]))), reverse=True)
    _say("longest ticks: " + ", ".join(f"tick {k} {d * 1e3:.1f} ms"
                                       for d, k in long_ticks[:5]))
    e2e = {
        "output_tok_s": res["decode_tokens"] / loop_s,
        "itl_p90_ms": nearest_rank(itl, 90) if itl else None,
        "setup_s": setup_s,
    }
    inside = clog.inside(*loop)
    backend = [e for e in inside
               if e[1].endswith("backend_compile_duration")]
    hits_in = [h for h in clog.hits if loop[0] <= h <= loop[1]]
    _say(f"inside the window: {len(inside)} lowering/compile events, "
         f"{sum(e[2] for e in inside):.3f} s; {len(backend)} backend "
         f"compiles of which {len(hits_in)} persistent-cache hits: "
         + ", ".join(f"{e[3]} {e[2]:.3f}s" for e in backend[:12]))

    faults, failed = counter_faults(reqs, res, rec.final)
    for f in faults[:20]:
        _say(f"counter fault: {f}")

    # ---- per-layer numbers (traced run)
    ticks = tap.tick_work(rec)
    per_layer, reduced = {}, None
    if traced:
        reduced = reduce_trace(tracer)
        ctx = SimpleNamespace(
            result=res, arch=arch, architecture=c.architecture,
            flags=flags, requests=reqs, ticks=ticks,
            trace=reduced, trace_ticks=tracer.ticks, peaks=pk,
            pool_bytes=POOL_BYTES[flags.get("--kv-dtype", "fp")],
            compile_events=inside, loop=loop, ttft_ms=ttft)
        for m in metric_entries(c, "per_layer"):
            v = metric_reader(root, m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- correctness: program state is gone; the reference runs now
    outputs = res["outputs"]
    seed_logits, first_logits = res["seed_logits"], res["first_decode_logits"]
    del res
    gc.collect()
    chk = c.cell["check"]
    sample = sample_requests(reqs, outputs, int(chk["requests"]), prog_seed)
    t_ref = time.perf_counter()
    got = compare(c.architecture, arch, prog_seed, sample, outputs,
                  seed_logits, first_logits, control) if sample else {
        "served_gap": None, "logit_err": None, "tokens": 0}
    _say(f"{'control' if control else 'reference'}: {len(sample)} "
         f"requests, {got['tokens']} served tokens, "
         f"{time.perf_counter() - t_ref:.1f} s")
    checks = {k: {"value": got[k], "limit": float(chk["limits"][k])}
              for k in ("served_gap", "logit_err")}
    correct = (not faults and failed == 0
               and all(v["value"] is not None and v["value"] <= v["limit"]
                       for v in checks.values()))

    if traced:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metric_entries(c, "end_to_end")}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(reqs),
           "failed": int(failed), "metrics": metrics, "device": device}
    if traced and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv, t_start: float, require_chip: bool = True) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    root = BENCH.parent
    try:
        out = run(root, a.workload, a.seed, a.seconds, bool(a.trace),
                  t_start, require_chip)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0

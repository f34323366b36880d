"""Serve-loop observability front end (DESIGN.md §15).

``runtime/telemetry.py`` owns the primitives (registry / tracer); this
module owns everything user-facing:

  emit()           the ONE sanctioned stdout chokepoint for serve-loop
                   reporting.  ``benchmarks/lint_prints.py`` fails CI on
                   bare ``print(`` anywhere else in the runtime + serve
                   loop, so every line a serve run shows went through a
                   registry snapshot first — no stat can appear in the
                   human summary without also being in ``--metrics-out``.

  summarize()      renders the ``[serve]`` summary lines from ONE
                   registry snapshot + a context dict of run facts
                   (flags, timings, sample tokens).  The structured
                   snapshot is the source of truth; the text is a view.

  write_metrics()  ``--metrics-out``: the full snapshot as
                   schema-versioned JSON with the same provenance block
                   the BENCH_*.json artifacts carry (git sha, jax
                   version) so CI can archive and diff it.

  write_trace()    ``--trace-out``: Chrome trace-event JSON.  Open in
                   https://ui.perfetto.dev (drag the file in) or
                   chrome://tracing.  Thread 0 is the engine timeline
                   (the serve loop's spans, each request's carrying
                   ``req``); thread 1000+id is request id's lifecycle
                   instants.
"""
from __future__ import annotations

import json
import subprocess

import jax

from repro.runtime.telemetry import OBS_SCHEMA_VERSION


def emit(line: str) -> None:
    """Print one serve-summary line.  The only print site the lint
    allows outside telemetry itself."""
    print(line)


def obs_meta(config: str) -> dict:
    """Provenance block for exported artifacts — the bench_meta pattern
    (benchmarks/run.py) with the telemetry schema version."""
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    return {"schema_version": OBS_SCHEMA_VERSION, "config": config,
            "git_sha": sha, "jax_version": jax.__version__}


def write_metrics(path: str, snapshot: dict, config: str) -> dict:
    """Write the registry snapshot as schema-versioned JSON."""
    doc = {"meta": obs_meta(config), "metrics": snapshot}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return doc


def write_trace(tracer, path: str) -> dict:
    """Export the ring buffer as Chrome trace-event JSON; returns the
    tracer's export stats (events written / recorded / dropped)."""
    return tracer.export(path)


def _c(snap: dict, name: str) -> int:
    return int(snap["counters"].get(name, 0))


def summarize_paged(snap: dict, ctx: dict) -> list:
    """The ``[serve]`` summary for the paged loop, rendered from the
    registry snapshot.  ``ctx`` carries run facts that are configuration
    or wall-clock, not metrics: flags, timings, sample tokens and the
    per-class stats dict."""
    tokens_served = _c(snap, "serve/decode_tokens")
    steps = _c(snap, "serve/decode_steps")
    prefill_tokens = _c(snap, "serve/prefill_tokens")
    prefill_chunks = _c(snap, "serve/prefill_chunks")
    interleaved = _c(snap, "serve/interleaved_steps")
    t_prefill, t_decode = ctx["t_prefill"], ctx["t_decode"]
    lines = [
        (f"[serve] arch={ctx['arch']} layout=paged mode={ctx['mode']} "
         f"B={ctx['batch_slots']} requests={ctx['n_requests']} "
         f"page={ctx['page_size']} blocks={ctx['pool_blocks']} "
         f"host_blocks={ctx['host_blocks']} chunk={ctx['chunk']} "
         f"budget={ctx['budget']} kv_dtype={ctx['kv_dtype']} "
         f"rescale={ctx['rescale']} prefix_cache="
         f"{'on' if ctx['prefix'] is not None else 'off'} "
         f"preemption={ctx['preemption']} spec_tokens={ctx['spec_tokens']} "
         f"pool_row_major="
         f"{int(snap['gauges'].get('paged/pool_row_major', 0))}"),
        (f"[serve] {tokens_served} tokens in {steps} decode steps "
         f"({tokens_served / max(steps, 1):.2f} tokens/step occupancy); "
         f"{prefill_chunks} prefill chunks, {interleaved} steps "
         f"interleaved prefill+decode; prefill {t_prefill*1e3:.1f}ms; "
         f"decode {t_decode*1e3:.1f}ms "
         f"({tokens_served/max(t_decode, 1e-9):.1f} tok/s); "
         f"requests refused at least once: {ctx['refusals']}"),
    ]
    pstats = ctx["prefix"]
    lines.append(
        f"[serve] token split: {prefill_tokens} prefill + {tokens_served} "
        f"decode run, {ctx['prefill_tokens_saved']} prefill skipped"
        + (f"; prefix cache: {pstats['hits']}/{pstats['lookups']} hits "
           f"({pstats['hit_rate']:.0%}), {pstats['cached_blocks']} blocks "
           f"cached, {pstats['evictions']} evicted" if pstats else ""))
    sstats = ctx["sched"]
    if sstats["preemptions"] or sstats["failures"] or sstats["refusals"]:
        lines.append(
            f"[serve] pressure: {sstats['preemptions']} preemptions "
            f"({sstats['preempts_swap']} swap / "
            f"{sstats['preempts_recompute']} recompute), "
            f"{sstats['restores_swap']}+{sstats['restores_recompute']} "
            f"restores, {_c(snap, 'serve/replayed_tokens')} tokens "
            f"replayed, {sstats['refusals']} transient refusals, "
            f"{sstats['failures']} injected failures "
            f"({_c(snap, 'serve/worker_restarts')} worker restarts)")
        for cls, st in ctx["classes"].items():
            lines.append(
                f"[serve]   class {cls}: n={st['n']} "
                f"preempt={st['preemptions']} "
                f"ttft p50/p99 {st['ttft_p50_ms']:.1f}/"
                f"{st['ttft_p99_ms']:.1f}ms itl p50/p99 "
                f"{st['itl_p50_ms']:.2f}/{st['itl_p99_ms']:.2f}ms")
    if ctx["spec_tokens"] > 0:
        proposed = _c(snap, "serve/spec_proposed")
        accepted = _c(snap, "serve/spec_accepted")
        lines.append(
            f"[serve] speculation: k={ctx['spec_tokens']} "
            f"draft={ctx['spec_draft']}; "
            f"{_c(snap, 'serve/spec_verify_steps')} verify launches, "
            f"{accepted}/{proposed} drafts accepted "
            f"({accepted / max(proposed, 1):.0%})")
    for stats, flag in ((ctx.get("trace_stats"), "--trace-out"),
                        (ctx.get("metrics_path"), "--metrics-out")):
        if stats and flag == "--trace-out":
            lines.append(
                f"[serve] trace: {stats['events']} events -> "
                f"{stats['path']} ({stats['dropped']} dropped of "
                f"{stats['recorded']} recorded)")
        elif stats:
            lines.append(f"[serve] metrics snapshot -> {stats}")
    lines.append("[serve] sample generation (request 0): "
                 f"{ctx['sample']}")
    return lines


def summarize_dense(snap: dict, ctx: dict) -> list:
    tokens_served = _c(snap, "serve/decode_tokens")
    t_prefill, t_decode = ctx["t_prefill"], ctx["t_decode"]
    lines = [
        (f"[serve] arch={ctx['arch']} layout=dense mode={ctx['mode']} "
         f"rescale={ctx['rescale']} B={ctx['batch']} "
         f"prompt={ctx['prompt']} gen={ctx['gen']}"),
        (f"[serve] prefill {t_prefill*1e3:.1f}ms; decode "
         f"{t_decode/ctx['gen']*1e3:.2f}ms/token "
         f"({tokens_served/t_decode:.1f} tok/s, {tokens_served} tokens)"),
    ]
    if ctx.get("metrics_path"):
        lines.append(f"[serve] metrics snapshot -> {ctx['metrics_path']}")
    lines.append(f"[serve] sample generation (seq 0): {ctx['sample']}")
    return lines

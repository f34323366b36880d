"""The serve loop's own spans, read from the traced run's device trace.

The program opens a ``jax.profiler.TraceAnnotation`` for every span of its
serve loop (``repro.runtime.telemetry.Tracer.span``), so under the
harness's profiler session the spans land on the host plane of the same
``.xplane.pb`` as the device's operations, on one clock.  ``of`` reads
them from the ``dev`` segment of the run just made; the reductions below
take plain ``(name, start_ns, dur_ns)`` lists, so they are tested on
hand-built events.  A program without these spans gives no ``serve.tick``:
``of`` then returns None and so does every metric that reads it.

Span names: ``serve.tick`` holds one iteration of the serve loop; the
``*.wait`` spans are where the host blocks on the device; every other
span is host work.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

from harness import trace

TICK = "serve.tick"
WAITS = frozenset({"serve.prefill.wait", "serve.decode.wait",
                   "serve.verify.wait"})
HOST_WORK = frozenset({"sched.admit", "prefix.match", "prefix.insert",
                       "serve.prefill.launch", "serve.decode.launch",
                       "serve.decode.retire", "serve.verify.launch",
                       "python.gc"})
PREFIX = frozenset({"prefix.match", "prefix.insert"})
NAMES = WAITS | HOST_WORK | {TICK}
RUNS = Path(__file__).resolve().parents[2] / ".bench_runs"


def of(run) -> list | None:
    """The spans of the ``dev`` segment written in this run's window, or
    None where it holds no ``serve.tick``.  Read once per run."""
    if not hasattr(run, "serve_spans"):
        run.serve_spans = _load(run)
    return run.serve_spans


def _load(run) -> list | None:
    files = sorted(RUNS.glob("*/dev/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    # written inside this run's window: not a segment of an earlier run
    since = time.time() - (time.perf_counter() - run.loop[0])
    if files[-1].stat().st_mtime < since:
        return None
    host = trace.load(files[-1])["host"]
    out = sorted((ev for evs in host.values() for ev in evs
                  if ev[0] in NAMES), key=lambda ev: ev[1])
    return out if any(ev[0] == TICK for ev in out) else None


# ------------------------------------------------------ interval algebra
def _iv(events) -> list:
    return [list(x) for x in trace.union(events)]


def _inter(a: list, b: list) -> list:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _minus(a: list, b: list) -> list:
    """``a`` less ``b`` (merged, sorted interval lists)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def _len(ivs: list) -> int:
    return sum(e - s for s, e in ivs)


def _named(spans, names) -> list:
    return [ev for ev in spans if ev[0] in names]


# ------------------------------------------------------------ reductions
def _ticks(spans) -> list:
    return [[s, s + d] for name, s, d in spans if name == TICK]


def host_ms_per_tick(spans) -> float | None:
    """Mean over the ``serve.tick`` spans of the tick's duration less the
    time its ``*.wait`` spans cover, in ms."""
    ticks = _ticks(spans)
    if not ticks:
        return None
    waits = _iv(_named(spans, WAITS))
    return sum((e - s) - _len(_inter([[s, e]], waits))
               for s, e in ticks) / len(ticks) / 1e6


def prefix_ms_per_tick(spans) -> float | None:
    """Summed ``prefix.match`` + ``prefix.insert`` time inside the
    ``serve.tick`` spans over their number, in ms."""
    ticks = _ticks(spans)
    if not ticks:
        return None
    inside = _inter(_iv(_named(spans, PREFIX)), _iv(_named(spans, {TICK})))
    return _len(inside) / len(ticks) / 1e6


def idle_by_span(ops, spans) -> dict | None:
    """Device idle time (gaps between the merged operation intervals)
    from the first ``serve.tick``'s start to the last one's end, in ns,
    split by what the program's spans say the host was doing:
    ``host_work`` (inside a host-work span), ``wait`` (inside a wait span
    and no host-work span), ``tick_other`` (inside a tick, no leaf
    span), ``outside`` (between ticks).  ``gc`` is the part inside
    ``python.gc`` (a share of ``host_work``)."""
    ticks = _iv(_named(spans, {TICK}))
    if not ticks:
        return None
    lo, hi = ticks[0][0], ticks[-1][1]
    idle = [list(g) for g in trace.gaps(
        [ev for ev in ops if ev[1] < hi and ev[1] + ev[2] > lo], lo, hi)]
    work = _inter(idle, _iv(_named(spans, HOST_WORK)))
    rest = _minus(idle, work)
    wait = _inter(rest, _iv(_named(spans, WAITS)))
    rest = _minus(rest, wait)
    in_tick = _inter(rest, ticks)
    return {"range": hi - lo, "idle": _len(idle), "host_work": _len(work),
            "wait": _len(wait), "tick_other": _len(in_tick),
            "outside": _len(_minus(rest, in_tick)),
            "gc": _len(_inter(idle, _iv(_named(spans, {"python.gc"}))))}


def inside_share(ops, spans, prefix: str) -> float | None:
    """Share (%) of the time covered by the operations that lies inside
    the spans whose name starts with ``prefix``: whether spans and device
    operations are on one clock."""
    busy = _iv(ops)
    if not busy:
        return None
    within = _iv([ev for ev in spans if ev[0].startswith(prefix)])
    return 100.0 * _len(_inter(busy, within)) / _len(busy)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)

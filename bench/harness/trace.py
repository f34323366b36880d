"""Reduction of a profiler trace to the numbers the metrics read.

Events are plain ``(name, start_ns, dur_ns)`` tuples, so the arithmetic is
tested on hand-built lists; ``load`` is the thin adapter from the
``.xplane.pb`` file that ``jax.profiler`` writes.
"""
from __future__ import annotations

import re
from pathlib import Path


def union(events) -> list:
    """Merged [start, end) intervals covered by the events."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(events) -> int:
    return sum(e - s for s, e in union(events))


def gaps(events, lo=None, hi=None) -> list:
    """Idle [start, end) intervals between the merged events, and before
    the first and after the last when the window edges lo/hi are given."""
    iv = union(events)
    out = []
    prev = lo if lo is not None else (iv[0][0] if iv else None)
    for s, e in iv:
        if prev is not None and s > prev:
            out.append((prev, s))
        prev = e if prev is None else max(prev, e)
    if hi is not None and prev is not None and hi > prev:
        out.append((prev, hi))
    return out


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def time_ns(events, pattern: str) -> int:
    """Summed duration of the events whose name matches ``pattern``."""
    return sum(d for _, _, d in matching(events, pattern))


def containing(outer, inner, pattern: str) -> list:
    """The outer events (program executions) that hold an inner event
    (operation) whose name matches ``pattern``."""
    marks = sorted(s for _, s, _ in matching(inner, pattern))
    out = []
    j = 0
    for ev in sorted(outer, key=lambda e: e[1]):
        _, s, d = ev
        while j < len(marks) and marks[j] < s:
            j += 1
        if j < len(marks) and marks[j] < s + d:
            out.append(ev)
    return out


def top(events, k: int = 10) -> list:
    """[name, seconds] of the k names with the most summed time."""
    tot = {}
    for name, _, d in events:
        tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [[n, t / 1e9] for n, t in best]


def attribute(idle, host_events, k: int = 10) -> list:
    """[name, seconds] of what the host ran in the idle gaps: each gap goes
    whole to the innermost host event (of one thread, properly nested)
    that covers its midpoint, summed by name; '(no host event)' where none
    covers it."""
    host = sorted(host_events, key=lambda h: (h[1], -h[2]))
    tot = {}
    stack, i = [], 0
    for gs, ge in sorted(idle):
        mid = (gs + ge) / 2
        while i < len(host) and host[i][1] <= mid:
            while stack and stack[-1][1] + stack[-1][2] <= host[i][1]:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= mid:
            stack.pop()
        name = stack[-1][0] if stack else "(no host event)"
        tot[name] = tot.get(name, 0) + (ge - gs)
    best = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [[n, t / 1e9] for n, t in best]


def load(path) -> dict:
    """Planes of an ``.xplane.pb``: {"devices": {plane: {line: events}},
    "host": {line: events}} with events as (name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": {}}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)) for ev in line.events]
        if plane.name.startswith("/device:"):
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for name, evs in lines.items():
                out["host"].setdefault(name, []).extend(evs)
    return out


def newest_xplane(log_dir) -> Path | None:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None

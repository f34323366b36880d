"""Prefix cache: host time of the prefix cache per tick — the summed
prefix.match and prefix.insert spans inside the traced serve.tick spans
over their number (bench/harness/spans.py)."""
from harness import spans


def read(run):
    s = spans.of(run)
    return spans.prefix_ms_per_tick(s) if s else None

"""Serve loop: host time of one tick — each traced serve.tick span less
the time its *.wait spans (the host blocked on the device) cover,
averaged over the serve.tick spans wholly inside the device trace's
first segment (bench/harness/spans.py)."""
from harness import spans


def read(run):
    s = spans.of(run)
    return spans.host_ms_per_tick(s) if s else None

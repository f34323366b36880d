"""Serve loop: seconds of tracing, lowering and backend compilation (or
loading from the persistent cache) that ran inside the window, from
jax.monitoring's events (the loop's per-call jitted entries)."""


def read(run):
    return sum(e[2] for e in run.compile_events)

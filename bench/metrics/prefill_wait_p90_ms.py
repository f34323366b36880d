"""Serve loop: 90th percentile over the run's requests of the time from
arrival to the dispatch of the request's first prefill chunk — the wait
behind admission and the prefill budget — from the program's own
histogram sched/prefill_wait_ms in the run's metrics snapshot."""


def read(run):
    h = run.result["metrics"]["histograms"].get("sched/prefill_wait_ms")
    return h["p90"] if h and h["count"] else None

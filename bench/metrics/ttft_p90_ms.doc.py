"""Serve loop: nearest-rank 90th percentile over all requests of the
time from arrival to the first delivered token.  In the doc cells it
moves 8-20% from seed to seed (the order of long and short prompts among
the requests that meet the cold prefix cache), too much to bound, so it
is reported here and not as an end-to-end metric."""
from harness.stats import nearest_rank


def read(run):
    return nearest_rank(run.ttft_ms, 90) if run.ttft_ms else None

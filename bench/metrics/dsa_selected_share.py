"""Model step: share of the rows DeepSeek Sparse Attention's indexer
scored that attention kept — the program's counters dsa/rows_selected
over dsa/rows_scored (per query row, from the host's lengths: a row at
position p scores its p + 1 causal rows and keeps min(index_topk, p + 1)),
summed over the whole measured run_paged call's decode steps and prefill
chunks, not only the traced ticks.  It is a property of the traffic's
lengths and the top-k: a check that the serve loop accounts for the
selection the traffic implies, which no change to the program moves."""


def read(run):
    c = run.result["metrics"]["counters"]
    scored, kept = c.get("dsa/rows_scored"), c.get("dsa/rows_selected")
    if not scored or kept is None:
        return None
    return 100.0 * kept / scored

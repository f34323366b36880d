"""Model step: device time of the selection of DeepSeek Sparse Attention
per decode step — in every layer, the operations between the index
kernel's end and the sparse decode's start (the exact top-k over the
index scores and the gather of the selected latent rows;
bench/harness/dsa.py), summed over the layers and averaged over the
decode-step programs in the traced ticks."""
from harness import dsa, trace


def read(run):
    if run.trace is None:
        return None
    per_layer = dsa.select_ns(run.trace["ops"])
    steps = trace.containing(run.trace["modules"], run.trace["ops"],
                             dsa.INDEX_DECODE)
    if not per_layer or not steps:
        return None
    return sum(per_layer) / len(steps) / 1e6

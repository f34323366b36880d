"""Model step: device time of one decode-step program under DeepSeek
Sparse Attention, averaged over the executions in the traced ticks.  The
step is told from the prefill program by the decode step's index kernel
it holds (bench/harness/dsa.py)."""
from harness import dsa, trace


def read(run):
    if run.trace is None:
        return None
    steps = trace.containing(run.trace["modules"], run.trace["ops"],
                             dsa.INDEX_DECODE)
    if not steps:
        return None
    return sum(d for _, _, d in steps) / len(steps) / 1e6

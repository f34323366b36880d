"""Model step: device time of one decode-step program, averaged over the
executions in the traced ticks.  The step is told from the prefill program
by the paged decode kernel it holds."""
from harness import trace
from harness.kernels import DECODE_KERNEL


def read(run):
    if run.trace is None:
        return None
    steps = trace.containing(run.trace["modules"], run.trace["ops"],
                             DECODE_KERNEL)
    if not steps:
        return None
    return sum(d for _, _, d in steps) / len(steps) / 1e6

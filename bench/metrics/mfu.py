"""Model step: model FLOPs of every token computed in the traced ticks
(decode rows and prefill chunks, at their attended lengths; bench/work/
step.py) over the traced window times the chip's bf16 peak."""
from work import step


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    a, b = run.trace_ticks
    flops = 0
    for t in run.ticks[a:b]:
        flops += step.step_flops(run.arch, len(t.decode_rows),
                                 sum(t.decode_rows))
        for s, c in t.chunks:
            flops += step.step_flops(run.arch, c, c * s + c * (c + 1) // 2)
    if not flops:
        return None
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"])

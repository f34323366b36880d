"""Model step: model FLOPs of every token computed in the traced ticks
(decode rows and prefill chunks, at their attended lengths; the
architecture's ``step_flops``, bench/arch/<model_type>.py) over the traced
window times the chip's bf16 peak."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    a, b = run.trace_ticks
    flops = sum(run.architecture.step_flops(run.arch, t.decode_rows,
                                            t.chunks)
                for t in run.ticks[a:b])
    if not flops:
        return None
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"])

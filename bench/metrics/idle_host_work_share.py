"""Device: share of the traced ticks in which the device idled while the
program's own host work ran — device idle time (gaps between the merged
operation intervals) inside the union of the host-work spans, over the
time from the first traced serve.tick's start to the last one's end
(bench/harness/spans.py).  Beside it, on standard error: the rest of the
idle time by span, and the share of each ETAP kernel's time that lies
inside its serve spans (the two clocks agree where it is near 100%)."""
from harness import spans, trace
from harness.kernels import DECODE_KERNEL, PREFILL_KERNEL


def read(run):
    s = spans.of(run)
    if run.trace is None or s is None:
        return None
    ops = run.trace["ops"]
    idle = spans.idle_by_span(ops, s)
    if not idle or not idle["range"]:
        return None
    spans.say("idle by span: " + ", ".join(
        f"{k} {v / 1e9:.6f} s ({100.0 * v / idle['range']:.3f}%)"
        for k, v in idle.items() if k != "range")
        + f" of {idle['range'] / 1e9:.6f} s of traced ticks")
    for kind, pattern in (("decode", DECODE_KERNEL),
                          ("prefill", PREFILL_KERNEL)):
        share = spans.inside_share(trace.matching(ops, pattern), s,
                                   f"serve.{kind}.")
        spans.say(f"etap {kind} kernel time inside serve.{kind}.* spans: "
                  f"{share}%")
    return 100.0 * idle["host_work"] / idle["range"]

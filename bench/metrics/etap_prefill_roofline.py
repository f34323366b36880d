"""Kernels: the chunked paged ETAP prefill kernel's share of its roofline
in the traced ticks (bench/work/etap_prefill.py per chunk, causal rows
only) over the kernel's summed device time."""
from harness import trace
from harness.kernels import PREFILL_KERNEL, say_bound
from work import etap_prefill


def read(run):
    if run.trace is None:
        return None
    t_ns = trace.time_ns(run.trace["ops"], PREFILL_KERNEL)
    a, b = run.trace_ticks
    A = run.arch
    flops = nbytes = 0
    for t in run.ticks[a:b]:
        for s, c in t.chunks:
            f, by = etap_prefill.work(s, c, A["heads"],
                                      A["kv_lora_rank"] + A["qk_rope"],
                                      A["kv_lora_rank"], run.pool_bytes)
            flops += f * A["layers"]
            nbytes += by * A["layers"]
    if not t_ns or not flops:
        return None
    return say_bound("etap_prefill_roofline", flops, nbytes, t_ns,
                     run.peaks)

"""Kernels: the paged ETAP decode kernel's share of its roofline in the
traced ticks: the larger of FLOPs / peak and bytes / HBM bandwidth
(bench/work/etap_decode.py, over the rows inside each slot's length, at
the pool's dtype) over the kernel's summed device time."""
from harness import trace
from harness.kernels import DECODE_KERNEL, say_bound
from work import etap_decode


def read(run):
    if run.trace is None:
        return None
    t_ns = trace.time_ns(run.trace["ops"], DECODE_KERNEL)
    a, b = run.trace_ticks
    A = run.arch
    flops = nbytes = 0
    for t in run.ticks[a:b]:
        if t.decode_rows:
            f, by = etap_decode.work(t.decode_rows, A["heads"],
                                     A["kv_lora_rank"] + A["qk_rope"],
                                     A["kv_lora_rank"], run.pool_bytes)
            flops += f * A["layers"]
            nbytes += by * A["layers"]
    if not t_ns or not flops:
        return None
    return say_bound("etap_decode_roofline", flops, nbytes, t_ns, run.peaks)

"""Kernels: DeepSeek Sparse Attention's decode kernel — ETAP decode over
each sequence's min(length, index_topk) selected latent rows — against
its roofline in the traced ticks (bench/work/etap_decode.py over the
selected rows, at the pool's dtype) over the kernel's summed device
time."""
from harness import dsa, trace
from harness.kernels import say_bound
from work import etap_decode


def read(run):
    if run.trace is None:
        return None
    t_ns = trace.time_ns(run.trace["ops"], dsa.SPARSE_DECODE)
    a, b = run.trace_ticks
    A = run.arch
    flops = nbytes = 0
    for t in run.ticks[a:b]:
        if t.decode_rows:
            rows = [min(n, A["index_topk"]) for n in t.decode_rows]
            f, by = etap_decode.work(rows, A["heads"],
                                     A["kv_lora_rank"] + A["qk_rope"],
                                     A["kv_lora_rank"], run.pool_bytes)
            flops += f * A["layers"]
            nbytes += by * A["layers"]
    if not t_ns or not flops:
        return None
    return say_bound("dsa_decode_roofline", flops, nbytes, t_ns, run.peaks)

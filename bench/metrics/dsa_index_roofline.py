"""Kernels: the decode step's index-score kernel (DeepSeek Sparse
Attention's lightning indexer) against its roofline in the traced ticks:
the larger of FLOPs / peak and bytes / HBM bandwidth (bench/work/
dsa_index.py, over every row inside each slot's length, the index keys at
the pool's dtype) over the kernel's summed device time."""
from harness import dsa, trace
from harness.kernels import say_bound
from work import dsa_index


def read(run):
    if run.trace is None:
        return None
    t_ns = trace.time_ns(run.trace["ops"], dsa.INDEX_DECODE)
    a, b = run.trace_ticks
    A = run.arch
    flops = nbytes = 0
    for t in run.ticks[a:b]:
        if t.decode_rows:
            f, by = dsa_index.work(t.decode_rows, A["index_heads"],
                                   A["index_dim"], run.pool_bytes)
            flops += f * A["layers"]
            nbytes += by * A["layers"]
    if not t_ns or not flops:
        return None
    return say_bound("dsa_index_roofline", flops, nbytes, t_ns, run.peaks)

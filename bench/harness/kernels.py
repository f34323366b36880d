"""How the device trace names the program's kernels, and the roofline
share they are held to."""
from __future__ import annotations

import sys

# The trace names a kernel's custom call after the jitted entry that
# wraps it (kernels/etap/ops.py): e.g. "%etap_decode_mla_paged.12 = ..."
# for the paged decode kernel (and its split-KV form), "%etap_prefill_mla_
# paged.9 = ..." for the chunked paged prefill kernel.
DECODE_KERNEL = r"^%etap_decode\w*_paged"
PREFILL_KERNEL = r"^%etap_prefill\w*_paged"


def short(op: str) -> str:
    """An operation's instruction name and result type, without its
    operands: "%copy.154 = bf16[1,8321,64,576]{...} copy(...)" ->
    "%copy.154 bf16[1,8321,64,576]"."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:120]
    kind = rest.split("{")[0] if not rest.startswith("(") else "(tuple)"
    return f"{head} {kind}"[:120]


def say_bound(name: str, flops: int, nbytes: int, t_ns: int,
              peaks: dict) -> float:
    """Share (%) of the roofline: the larger of the compute and memory
    times over the measured time; says which one binds."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_s"]
    print(f"[bench] {name}: {flops} FLOP, {nbytes} B in {t_ns} ns; "
          f"{'compute' if t_c >= t_m else 'memory'} bound "
          f"({t_c * 1e9:.0f} ns compute, {t_m * 1e9:.0f} ns memory)",
          file=sys.stderr, flush=True)
    return 100.0 * max(t_c, t_m) / (t_ns / 1e9)

"""How the device trace names DeepSeek Sparse Attention's work in a decode
step, and the selection's time between its kernels.

The program runs each layer's sparse decode as three steps under their
own names (src/repro/kernels/etap/ops.py, core/etap.py): the index-score
kernel ``dsa_index_decode_paged``, the selection (``dsa_select``: an exact
top-k and the gather of the selected latent rows, plain XLA operations)
and the ETAP decode over the gathered rows ``dsa_sparse_decode``.  A
prefill chunk's index kernel is ``dsa_index_prefill_paged``.

The check reads what the program selected: ``run_paged(record_logits=
True)`` returns, under an indexer, the positions every layer selected for
each request's last prefill row and each decode row (``dsa_selected``).
:func:`keep_selections` keeps them from the last such call, keyed by the
token sequence the check hands the reference, and :func:`recorded` looks
them up there (bench/arch/deepseek_v32.py).
"""
from __future__ import annotations

import functools
import hashlib

import numpy as np

from harness import trace

INDEX_DECODE = r"^%dsa_index_decode\w*_paged"
SPARSE_DECODE = r"^%dsa_sparse_decode"


def select_ns(ops) -> list:
    """Device time of each selection: the merged intervals of the
    operations that start after an index-decode kernel ends and end before
    the next sparse-decode kernel starts (the top-k and the gather, and
    whatever else the compiler placed between the two kernels)."""
    idx = {id(e) for e in trace.matching(ops, INDEX_DECODE)}
    dec = {id(e) for e in trace.matching(ops, SPARSE_DECODE)}
    out, after, between = [], None, []
    for ev in sorted(ops, key=lambda e: e[1]):
        if id(ev) in idx:
            after, between = ev[1] + ev[2], []
        elif id(ev) in dec:
            if after is not None:
                out.append(trace.busy_ns([e for e in between
                                          if e[1] + e[2] <= ev[1]]))
            after = None
        elif after is not None and ev[1] >= after:
            between.append(ev)
    return out


# the selections of the last run_paged(record_logits=True) call, by the
# digest of each request's prompt and served tokens but the last
RECORD: dict = {}


def _digest(seq) -> str:
    return hashlib.sha1(np.asarray(seq, np.int32).tobytes()).hexdigest()


def keep_selections() -> None:
    """Have ``repro.launch.serve.run_paged`` leave each recorded run's
    selections in RECORD (once per process; every later call replaces
    them)."""
    from repro.launch import serve
    if getattr(serve.run_paged, "keeps_selections", False):
        return
    inner = serve.run_paged

    @functools.wraps(inner)
    def run_paged(*args, **kw):
        res = inner(*args, **kw)
        if kw.get("record_logits"):
            RECORD.clear()
            for rid, rec in res.get("dsa_selected", {}).items():
                served = np.asarray(res["outputs"][rid][:-1], np.int32)
                RECORD[_digest(np.concatenate([rec["prompt"], served]))] = \
                    rec["rows"]
        return res

    run_paged.keeps_selections = True
    serve.run_paged = run_paged


def recorded(seqs) -> list | None:
    """The kept selections of each sequence ({position: [layers, n]}),
    None for a sequence without; None if no sequence has any."""
    got = [RECORD.get(_digest(s)) for s in seqs]
    return got if any(g is not None for g in got) else None

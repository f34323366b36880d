"""DeepSeek-V3.2: the DeepSeek-V3 stack (bench/arch/deepseek_v3.py) with
DeepSeek Sparse Attention in every layer: a lightning indexer of
``index_n_heads`` heads of ``index_head_dim`` scores every causal row, and
each query attends only its ``index_topk`` best.  The plain reference is
bench/reference/mla_dsa.py; the step's model FLOPs cap each query row's
attended rows at the top-k and add the indexer's (bench/work/dsa_index.py)."""
from __future__ import annotations

import dataclasses
import sys

from arch import deepseek_v3
from harness import dsa
from reference import mla_dsa
from work import dsa_index, step

# The gate on the program's recorded selections (bench/reference/
# mla_dsa.py): in the first layer no unselected row may score more than
# NEAR_TIE standard deviations of the row's index scores above the worst
# selected one, and in every layer at least SHARE of the selected rows are
# the reference's own top-k (readings in PERF.md §2).
NEAR_TIE = 0.1
SHARE = 0.5


def logits(arch: dict, seed: int, seqs: list, rows: list, *,
           mode: str = "fp32"):
    """The plain reference.  In ``"fp32"`` it attends, at the rows the
    program recorded, the program's selection where that passes the gate;
    the control (``"fp8"``) keeps its own selection."""
    selected = dsa.recorded(seqs) if mode == "fp32" else None
    check = {}
    out = mla_dsa.logits(arch, seed, seqs, rows, mode=mode,
                         selected=selected, band=NEAR_TIE, overlap=SHARE,
                         check=check)
    for i, c in sorted(check["seqs"].items()):
        print(f"[bench] dsa selection, sequence {i}: {c['rows']} recorded "
              f"row-layers, adopted {c['adopted']}; first-layer margin "
              f"{c['first_margin']:.4g} (band {NEAR_TIE}), later "
              f"{c['margin']:.4g}; smallest share {c['share']:.4g} "
              f"(floor {SHARE}); failed {len(c['missed'])} "
              f"{c['missed'][:4]}", file=sys.stderr, flush=True)
    return out


def arch(c: dict) -> dict:
    """DeepSeek-V3 shapes plus the indexer's."""
    return dict(deepseek_v3.arch(c), index_heads=int(c["index_n_heads"]),
                index_dim=int(c["index_head_dim"]),
                index_topk=int(c["index_topk"]))


def model_config(c: dict, name: str):
    """The program's ModelConfig: DeepSeek-V3's with the indexer set.
    The serve loop's recorded selections are kept from here on, for
    :func:`logits`."""
    from repro.configs.base import IndexerConfig
    dsa.keep_selections()
    cfg = deepseek_v3.model_config(c, name)
    a = arch(c)
    return dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, indexer=IndexerConfig(n_heads=a["index_heads"],
                                       head_dim=a["index_dim"],
                                       topk=a["index_topk"])))


def step_flops(arch: dict, decode_rows, chunks) -> int:
    """Each query row attends min(index_topk, causal rows) rows and the
    indexer scores every causal row; every token also pays the indexer's
    projections, in every layer."""
    topk, L = arch["index_topk"], arch["layers"]
    spans = [(n - 1, 1) for n in decode_rows] + list(chunks)
    tokens = scored = attended = 0
    for start, count in spans:
        s, a = dsa_index.causal_rows(start, count, topk)
        tokens, scored, attended = tokens + count, scored + s, attended + a
    f_idx, _ = dsa_index.work([scored], arch["index_heads"],
                              arch["index_dim"], 2)
    return (step.step_flops(arch, tokens, attended)
            + L * (f_idx + tokens * dsa_index.projection_flops(arch)))

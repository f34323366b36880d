"""Paged ETAP decode kernel (absorbed MLA): one query row per sequence
attends its ``rows`` latent rows; K is the whole latent row, V its first
``dv`` columns (one stream)."""
from __future__ import annotations


def work(rows, heads: int, latent: int, dv: int, pool_bytes: int,
         act_bytes: int = 2) -> tuple:
    """rows: attended latent rows of each sequence in the call (inside
    ``lengths``).  Returns (flops, bytes): QK^T over ``latent`` and PV over
    ``dv`` columns per head and row; the latent rows read once at the
    pool's dtype, plus q in and out."""
    n = len(rows)
    total = sum(int(r) for r in rows)
    flops = 2 * heads * total * (latent + dv)
    nbytes = (total * latent * pool_bytes
              + n * heads * latent * act_bytes
              + n * heads * dv * act_bytes)
    return flops, nbytes

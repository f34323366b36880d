"""Chunked paged ETAP prefill kernel (absorbed MLA): a chunk of ``c`` query
rows at positions start .. start + c - 1 attends causally over the pool."""
from __future__ import annotations


def work(start: int, c: int, heads: int, latent: int, dv: int,
         pool_bytes: int, act_bytes: int = 2) -> tuple:
    """Returns (flops, bytes) of one chunk: query row i attends
    start + i + 1 rows; the start + c latent rows are read once at the
    pool's dtype, plus the chunk's q in and out."""
    attended = c * start + c * (c + 1) // 2
    flops = 2 * heads * attended * (latent + dv)
    nbytes = ((start + c) * latent * pool_bytes
              + c * heads * latent * act_bytes
              + c * heads * dv * act_bytes)
    return flops, nbytes

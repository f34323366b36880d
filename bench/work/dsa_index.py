"""Lightning-indexer score kernel of DeepSeek Sparse Attention: each query
row scores each of its causal rows with ``heads`` dot products of width
``dim`` against the row's one index key, ReLU, and a weighted sum over the
heads."""
from __future__ import annotations


def work(rows, heads: int, dim: int, pool_bytes: int,
         act_bytes: int = 2) -> tuple:
    """rows: scored rows of each query row in the call (inside its
    length).  Returns (flops, bytes): 2·heads·dim per scored row for the
    dot products and 2·heads for the weighted head sum; the index keys read
    once at the pool's dtype, plus each query row's q (heads x dim) and
    fp32 head weights in and its fp32 scores out."""
    n = len(rows)
    total = sum(int(r) for r in rows)
    flops = total * (2 * heads * dim + 2 * heads)
    nbytes = (total * dim * pool_bytes + n * heads * dim * act_bytes
              + n * heads * 4 + total * 4)
    return flops, nbytes


def causal_rows(start: int, count: int, topk: int) -> tuple:
    """(scored, selected) rows of ``count`` query rows at positions
    start .. start + count - 1: row i scores its start + i + 1 causal rows
    and attends min(topk, start + i + 1) of them."""
    lo, hi = start + 1, start + count
    scored = (lo + hi) * count // 2
    below = max(0, min(hi, topk) - lo + 1)          # rows under the cap
    selected = (lo + lo + below - 1) * below // 2 + (count - below) * topk
    return scored, selected


def projection_flops(arch: dict) -> int:
    """FLOPs of the indexer's projections for one token in one layer: the
    index queries from the q latent, the index key and the head weights
    from the hidden state."""
    heads, dim = arch["index_heads"], arch["index_dim"]
    return 2 * (arch["q_lora_rank"] * heads * dim
                + arch["d_model"] * dim + arch["d_model"] * heads)

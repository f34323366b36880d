"""Lightning-indexer score kernel of DeepSeek Sparse Attention (Pallas TPU).

The index score of a past row s for query row t is

    I(t, s) = sum_h w[t, h] · ReLU(q[t, h] · k[s])

with 64 index heads of width 128 and ONE key row per token, kept in a
paged pool under the serve loop's block table (DESIGN.md §17).  The kernel
walks each sequence's pages through the table in ETAP's orientation: a
step's key rows sit on the GEMM's M side and the index heads of its query
rows on N,

    Sᵀ = K Qᵀ            [rows, Rt·heads]     (fp32 accumulation)
    Iᵀ = W_blk ReLU(Sᵀ)ᵀ [Rt, rows]           (the weighted head sum)

where W_blk [Rt, Rt·heads] holds each query row's head weights on its own
diagonal block, so the head sum is one more matmul that also leaves the
scores lane-major for the output.  It runs at HIGHEST precision: the
scores decide a top-k, and a bf16 rounding of ReLU(S) would move rows
across its threshold.

Each grid step holds ``pages`` pool pages (one BlockSpec operand per page,
each gathering ``table[b, j·pages + p]``); pages past a sequence's last
scored row re-name its last page, so their DMA is skipped, and their rows
come out -inf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAGES_PER_STEP = 16
ROW_TILE = 8


def _index_body(limit_ref, table_ref, q_ref, w_ref, *refs, pages: int,
                page: int, heads: int, row_tile: int):
    k_refs, o_ref = refs[:pages], refs[pages]
    j = pl.program_id(2)
    k = jnp.concatenate([r[0] for r in k_refs], axis=0)   # [rows, hd]
    # Sᵀ = K·Qᵀ — key rows on M, the tile's (row, head) columns on N
    sT = jax.lax.dot_general(
        k, q_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [rows, Rt*nh]
    sT = jnp.maximum(sT, 0.0)
    shape = (row_tile, row_tile * heads)
    own = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) // heads
           == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    w_blk = jnp.where(own, w_ref[0], 0.0)                 # [Rt, Rt*nh]
    out = jax.lax.dot_general(
        w_blk, sT, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # [Rt, rows]
    pos = j * pages * page + jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    limit = limit_ref[pl.program_id(0)]
    o_ref[0] = jnp.where(pos < limit, out, -jnp.inf)


def dsa_index_pallas(q, w, pool, table, limit, *, interpret: bool = True,
                     pages: int = PAGES_PER_STEP, row_tile: int = ROW_TILE):
    """Index scores over a paged key pool.

    q: [B, R, heads, hd] index queries (the pool's dtype); w: [B, R, heads]
    fp32 head weights; pool: [N, page, hd]; table: [B, max_blocks] int32;
    limit: [B] rows scored per sequence (rows >= limit come out -inf).
    Returns [B, R, max_blocks·page] fp32."""
    B, R, nh, hd = q.shape
    page = pool.shape[1]
    nb = table.shape[1]
    pages = min(pages, nb)
    nbp = -(-nb // pages) * pages
    if nbp != nb:
        table = jnp.pad(table, ((0, 0), (0, nbp - nb)))
    rt = R if R <= row_tile else row_tile
    Rp = -(-R // rt) * rt
    if Rp != R:
        q = jnp.pad(q, ((0, 0), (0, Rp - R), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, Rp - R), (0, 0)))
    q = q.reshape(B, Rp * nh, hd).astype(pool.dtype)
    w = w.reshape(B, 1, Rp * nh).astype(jnp.float32)

    def key_spec(p):
        def index(b, t, j, lim, tab):
            last = jnp.maximum(lim[b] - 1, 0) // page
            return (tab[b, jnp.minimum(j * pages + p, last)], 0, 0)
        return pl.BlockSpec((1, page, hd), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Rp // rt, nbp // pages),
        in_specs=[
            pl.BlockSpec((1, rt * nh, hd), lambda b, t, j, *_: (b, t, 0)),
            pl.BlockSpec((1, 1, rt * nh), lambda b, t, j, *_: (b, 0, t)),
        ] + [key_spec(p) for p in range(pages)],
        out_specs=pl.BlockSpec((1, rt, pages * page),
                               lambda b, t, j, *_: (b, t, j)),
    )
    body = functools.partial(_index_body, pages=pages, page=page, heads=nh,
                             row_tile=rt)
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Rp, nbp * page), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(limit.astype(jnp.int32), table.astype(jnp.int32), q, w,
      *([pool] * pages))
    return out[:, :R, :nb * page]

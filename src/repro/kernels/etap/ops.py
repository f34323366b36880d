"""jit'd public wrappers for the ETAP kernels: shape normalization (pad S to
a block/split multiple — masked via `length`), dtype checks, MLA-fused and
split-KV two-phase entry points.

Every entry point takes one :class:`repro.core.attn_spec.AttnSpec`
(``spec=``) wrapped by :func:`attn_spec.attn_entry`: the spec is
canonicalized BEFORE the jit cache — ``rescale=None`` resolves to the
process default mode (flipping the serve-level default can never serve a
stale trace) and fields the entry's trace ignores are projected to their
defaults (flipping an unused knob never retraces).  Legacy keyword calls
(``scale=``, ``block=``, ``rescale=``, ``n_splits=``, ...) still work
through the shim and emit ``DeprecationWarning``."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import attn_spec
from repro.kernels.etap.combine import combine_splits
from repro.kernels.etap.dsa import dsa_index_pallas
from repro.kernels.etap.etap import (etap_decode_mla_paged_pallas,
                                     etap_decode_mla_pallas,
                                     etap_decode_paged_pallas,
                                     etap_decode_pallas,
                                     etap_paged_partial_pallas,
                                     etap_partial_pallas,
                                     etap_prefill_mla_paged_pallas,
                                     etap_prefill_paged_pallas,
                                     etap_verify_mla_paged_pallas,
                                     etap_verify_paged_pallas)
from repro.kernels.etap.schedule import (paged_split_geometry, plan_splits,
                                         plan_splits_paged, split_geometry)


def _pad_seq(x, multiple: int):
    S = x.shape[1]
    pad = (-S) % multiple
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


@attn_spec.attn_entry(uses=("block", "interpret", "rescale"))
def etap_decode(q, k, v, length=None, *, spec):
    """ETAP decode attention. q: [BG,H,Dk]; k: [BG,S,Dk]; v: [BG,S,Dv];
    length: [BG] valid-prefix lengths (None = all S). Returns [BG,H,Dv]."""
    BG, _, _ = q.shape
    S = k.shape[1]
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)
    block = min(spec.block, S)
    k = _pad_seq(k, block)     # padded tail is masked out via `length`
    v = _pad_seq(v, block)
    return etap_decode_pallas(q, k, v, length, scale=spec.scale, block=block,
                              interpret=spec.interpret, rescale=spec.rescale)


@attn_spec.attn_entry(uses=("block", "interpret", "rescale"),
                      static_argnames=("dv",))
def etap_decode_mla(q, kv, dv: int, length=None, *, spec):
    """MLA-fused ETAP: one latent stream [BG,S,latent]; V = kv[..., :dv]."""
    BG = q.shape[0]
    S = kv.shape[1]
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)
    block = min(spec.block, S)
    kv = _pad_seq(kv, block)
    return etap_decode_mla_pallas(q, kv, dv, length, scale=spec.scale,
                                  block=block, interpret=spec.interpret,
                                  rescale=spec.rescale)


# ------------------------------------------------------ split-KV two-phase
def _partial(q, kv, v, length, *, scale, block, n_splits, interpret,
             fused_dv, rescale):
    """Pad S to a (n_splits · block) multiple and run the phase-1 kernel.
    n_splits is re-derived through the shared geometry, so a request for
    more splits than there are KV blocks degrades to fewer non-empty
    splits instead of launching zero-length grid rows."""
    block, n_splits, _, target = split_geometry(kv.shape[1], block, n_splits)
    kv = _pad_seq(kv, target)
    if v is not None:
        v = _pad_seq(v, target)
    return etap_partial_pallas(q, kv, v, length, scale=scale, block=block,
                               n_splits=n_splits, interpret=interpret,
                               fused_dv=fused_dv, rescale=rescale)


@attn_spec.attn_entry(uses=("block", "kv_splits", "interpret", "rescale"))
def etap_partial(q, k, v, length=None, *, spec):
    """Phase-1 split-KV stats. Returns (m, l, accT):
    [BG,n,H], [BG,n,H], [BG,n,Dv,H] (fp32).  spec.kv_splits None -> 2."""
    BG = q.shape[0]
    if length is None:
        length = jnp.full((BG,), k.shape[1], jnp.int32)
    n_splits = 2 if spec.kv_splits is None else int(spec.kv_splits)
    return _partial(q, k, v, length, scale=spec.scale, block=spec.block,
                    n_splits=n_splits, interpret=spec.interpret, fused_dv=0,
                    rescale=spec.rescale)


@attn_spec.attn_entry(uses=("block", "kv_splits", "interpret", "rescale"),
                      static_argnames=("dv",))
def etap_partial_mla(q, kv, dv: int, length=None, *, spec):
    """Phase-1 split-KV stats, MLA-fused (V = kv[..., :dv])."""
    BG = q.shape[0]
    if length is None:
        length = jnp.full((BG,), kv.shape[1], jnp.int32)
    n_splits = 2 if spec.kv_splits is None else int(spec.kv_splits)
    return _partial(q, kv, None, length, scale=spec.scale, block=spec.block,
                    n_splits=n_splits, interpret=spec.interpret, fused_dv=dv,
                    rescale=spec.rescale)


@attn_spec.attn_entry(uses=("block", "kv_splits", "interpret", "rescale"),
                      static_argnames=("combine",))
def etap_decode_splitkv(q, k, v, length=None, *, spec,
                        combine: str = "pallas"):
    """Two-phase split-KV ETAP decode. spec.kv_splits None/0 → auto
    (scheduler); 1 routes to the single-pass kernel (bit-identical — the
    combine weights degenerate to exp(0) = 1, so the two-phase path computes
    the same epilogue; routing just skips the stats round-trip)."""
    BG, H, _ = q.shape
    S = k.shape[1]
    n_splits = int(spec.kv_splits or 0)
    if not n_splits:
        n_splits = plan_splits(BG, S, H, v.shape[2],
                               block=spec.block).n_splits
    n_splits = split_geometry(S, spec.block, n_splits)[1]  # effective count
    if n_splits <= 1:
        return etap_decode(q, k, v, length, spec=spec)
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)
    m, l, accT = _partial(q, k, v, length, scale=spec.scale, block=spec.block,
                          n_splits=n_splits, interpret=spec.interpret,
                          fused_dv=0, rescale=spec.rescale)
    return combine_splits(m, l, accT, transposed=True, out_dtype=v.dtype,
                          combine=combine, interpret=spec.interpret,
                          rescale=spec.rescale)


# ------------------------------------------------------------------- paged
def _pad_table(table, multiple: int):
    """Pad the block table to a column multiple with null blocks (id 0);
    padded entries are masked via `lengths` exactly like the dense padded
    tail, so split geometry never repacks the pool."""
    nb = table.shape[1]
    pad = (-nb) % multiple
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    return table


@attn_spec.attn_entry(uses=("interpret", "rescale"))
def etap_decode_paged(q, k_pool, v_pool, table, lengths, *, spec,
                      k_sz=None, v_sz=None):
    """Paged ETAP decode. q: [B,H,Dk]; pools: [N,page,D*]; table:
    [B,max_blocks] int32; lengths: [B]. Returns [B,H,Dv].  Bit-identical
    to :func:`etap_decode` at block == page on the same logical rows.
    k_sz/v_sz: per-row (scale, zp) pools [N,page,2] when the pools hold
    int8/fp8 codes (in-register dequant, DESIGN.md §11)."""
    return etap_decode_paged_pallas(q, k_pool, v_pool, table, lengths,
                                    scale=spec.scale,
                                    interpret=spec.interpret,
                                    k_sz=k_sz, v_sz=v_sz,
                                    rescale=spec.rescale)


@attn_spec.attn_entry(uses=("interpret", "rescale"), static_argnames=("dv",))
def etap_decode_mla_paged(q, kv_pool, dv: int, table, lengths, *, spec,
                          kv_sz=None):
    """Paged MLA-fused ETAP: one latent pool, V = pool[..., :dv]."""
    return etap_decode_mla_paged_pallas(q, kv_pool, dv, table, lengths,
                                        scale=spec.scale,
                                        interpret=spec.interpret,
                                        kv_sz=kv_sz, rescale=spec.rescale)


@attn_spec.attn_entry(uses=("interpret", "rescale"))
def etap_prefill_paged(q, k_pool, v_pool, table, start, *, spec,
                       k_sz=None, v_sz=None):
    """Chunked paged ETAP prefill (separate-V). q: [B,Cq,H,Dk]; pools:
    [N,page,D*]; table: [B,max_blocks] int32; start: [B] tokens already in
    the pool before the chunk (whose rows must already be appended).
    Returns [B,Cq,H,Dv] — causal within the chunk, full over the pool."""
    return etap_prefill_paged_pallas(q, k_pool, v_pool, table, start,
                                     scale=spec.scale,
                                     interpret=spec.interpret,
                                     k_sz=k_sz, v_sz=v_sz,
                                     rescale=spec.rescale)


@attn_spec.attn_entry(uses=("interpret", "rescale"), static_argnames=("dv",))
def etap_prefill_mla_paged(q, kv_pool, dv: int, table, start, *, spec,
                           kv_sz=None, sel=None):
    """Chunked paged MLA-fused ETAP prefill: one latent pool, V = pool[..., :dv].
    sel: [B,Cq,max_blocks*page] bool, the rows each query row may attend
    besides the causal mask (DeepSeek Sparse Attention's top-k)."""
    return etap_prefill_mla_paged_pallas(q, kv_pool, dv, table, start,
                                         scale=spec.scale,
                                         interpret=spec.interpret,
                                         kv_sz=kv_sz, rescale=spec.rescale,
                                         sel=sel)


# ------------------------------------------- DeepSeek Sparse Attention
# Named entries, so the device trace tells the decode step's index kernel
# from the chunk's and the sparse decode from the dense one.
@attn_spec.attn_entry(uses=("interpret",))
def dsa_index_decode_paged(q, w, pool, table, limit, *, spec):
    """Index scores of one query row per sequence: q [B,1,heads,hd],
    w [B,1,heads] -> [B,1,max_blocks*page] fp32 (kernels/etap/dsa.py)."""
    return dsa_index_pallas(q, w, pool, table, limit,
                            interpret=spec.interpret)


@attn_spec.attn_entry(uses=("interpret",))
def dsa_index_prefill_paged(q, w, pool, table, limit, *, spec):
    """Index scores of a prefill chunk's rows: q [B,C,heads,hd],
    w [B,C,heads] -> [B,C,max_blocks*page] fp32."""
    return dsa_index_pallas(q, w, pool, table, limit,
                            interpret=spec.interpret)


@attn_spec.attn_entry(uses=("block", "interpret", "rescale"),
                      static_argnames=("dv",))
def dsa_sparse_decode(q, rows, dv: int, length, *, spec):
    """ETAP decode over each sequence's selected latent rows [B,k,latent]
    (the first ``length`` of them valid); V = rows[..., :dv]."""
    block = min(spec.block, rows.shape[1])
    rows = _pad_seq(rows, block)
    return etap_decode_mla_pallas(q, rows, dv, length, scale=spec.scale,
                                  block=block, interpret=spec.interpret,
                                  rescale=spec.rescale)


@attn_spec.attn_entry(uses=("interpret", "rescale"))
def etap_verify_paged(q, k_pool, v_pool, table, start, qpos, *, spec,
                      k_sz=None, v_sz=None):
    """Draft-verify attention over a paged cache (DESIGN.md §14): the
    chunked-prefill kernel with an EXPLICIT per-query position mask.
    q: [B,Cq,H,Dk] — the Cq drafted rows (already appended); qpos: [B,Cq]
    int32 absolute positions; start: [B] rows in the pool before the
    chunk.  Row c attends to pool positions <= qpos[b, c]."""
    return etap_verify_paged_pallas(q, k_pool, v_pool, table, start, qpos,
                                    scale=spec.scale,
                                    interpret=spec.interpret,
                                    k_sz=k_sz, v_sz=v_sz,
                                    rescale=spec.rescale)


@attn_spec.attn_entry(uses=("interpret", "rescale"), static_argnames=("dv",))
def etap_verify_mla_paged(q, kv_pool, dv: int, table, start, qpos, *, spec,
                          kv_sz=None):
    """Paged MLA-fused draft-verify: one latent pool, V = pool[..., :dv],
    explicit per-query positions (see :func:`etap_verify_paged`)."""
    return etap_verify_mla_paged_pallas(q, kv_pool, dv, table, start, qpos,
                                        scale=spec.scale,
                                        interpret=spec.interpret,
                                        kv_sz=kv_sz, rescale=spec.rescale)


def _paged_partial(q, k_pool, v_pool, table, lengths, *, scale, n_splits,
                   interpret, fused_dv, rescale, k_sz=None, v_sz=None):
    n_splits, npb, padded_nb = paged_split_geometry(table.shape[1], n_splits)
    table = _pad_table(table, padded_nb)
    return etap_paged_partial_pallas(q, k_pool, v_pool, table, lengths,
                                     scale=scale, n_splits=n_splits,
                                     interpret=interpret, fused_dv=fused_dv,
                                     k_sz=k_sz, v_sz=v_sz, rescale=rescale)


@attn_spec.attn_entry(uses=("kv_splits", "interpret", "rescale"),
                      static_argnames=("combine",))
def etap_decode_paged_splitkv(q, k_pool, v_pool, table, lengths, *, spec,
                              combine: str = "pallas", k_sz=None, v_sz=None):
    """Two-phase split-KV ETAP decode over a paged cache. spec.kv_splits
    None/0 → auto via the block-granular scheduler; 1 routes to the
    single-pass paged kernel (bit-identical, same argument as the dense
    path).  Requests for more splits than table columns degrade to the
    effective count of the shared geometry (no zero-length splits)."""
    B, H, _ = q.shape
    page = k_pool.shape[1]
    n_splits = int(spec.kv_splits or 0)
    if not n_splits:
        n_splits = plan_splits_paged(B, table.shape[1], page, H,
                                     v_pool.shape[2]).n_splits
    n_splits = paged_split_geometry(table.shape[1], n_splits)[0]
    if n_splits <= 1:
        return etap_decode_paged(q, k_pool, v_pool, table, lengths,
                                 spec=spec, k_sz=k_sz, v_sz=v_sz)
    m, l, accT = _paged_partial(q, k_pool, v_pool, table, lengths,
                                scale=spec.scale, n_splits=n_splits,
                                interpret=spec.interpret, fused_dv=0,
                                k_sz=k_sz, v_sz=v_sz, rescale=spec.rescale)
    out_dtype = q.dtype if k_sz is not None else v_pool.dtype
    return combine_splits(m, l, accT, transposed=True,
                          out_dtype=out_dtype, combine=combine,
                          interpret=spec.interpret, rescale=spec.rescale)


@attn_spec.attn_entry(uses=("kv_splits", "interpret", "rescale"),
                      static_argnames=("dv", "combine"))
def etap_decode_mla_paged_splitkv(q, kv_pool, dv: int, table, lengths, *,
                                  spec, combine: str = "pallas", kv_sz=None):
    """Two-phase split-KV over a paged MLA latent pool (V = pool[..., :dv])."""
    B, H, _ = q.shape
    page = kv_pool.shape[1]
    n_splits = int(spec.kv_splits or 0)
    if not n_splits:
        n_splits = plan_splits_paged(B, table.shape[1], page, H, dv).n_splits
    n_splits = paged_split_geometry(table.shape[1], n_splits)[0]
    if n_splits <= 1:
        return etap_decode_mla_paged(q, kv_pool, dv, table, lengths,
                                     spec=spec, kv_sz=kv_sz)
    m, l, accT = _paged_partial(q, kv_pool, None, table, lengths,
                                scale=spec.scale, n_splits=n_splits,
                                interpret=spec.interpret, fused_dv=dv,
                                k_sz=kv_sz, rescale=spec.rescale)
    out_dtype = q.dtype if kv_sz is not None else kv_pool.dtype
    return combine_splits(m, l, accT, transposed=True,
                          out_dtype=out_dtype, combine=combine,
                          interpret=spec.interpret, rescale=spec.rescale)


@attn_spec.attn_entry(uses=("block", "kv_splits", "interpret", "rescale"),
                      static_argnames=("dv", "combine"))
def etap_decode_mla_splitkv(q, kv, dv: int, length=None, *, spec,
                            combine: str = "pallas"):
    """Two-phase split-KV, MLA-fused single-latent-stream variant."""
    BG, H, _ = q.shape
    S = kv.shape[1]
    n_splits = int(spec.kv_splits or 0)
    if not n_splits:
        n_splits = plan_splits(BG, S, H, dv, block=spec.block).n_splits
    n_splits = split_geometry(S, spec.block, n_splits)[1]  # effective count
    if n_splits <= 1:
        return etap_decode_mla(q, kv, dv, length, spec=spec)
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)
    m, l, accT = _partial(q, kv, None, length, scale=spec.scale,
                          block=spec.block, n_splits=n_splits,
                          interpret=spec.interpret, fused_dv=dv,
                          rescale=spec.rescale)
    return combine_splits(m, l, accT, transposed=True, out_dtype=kv.dtype,
                          combine=combine, interpret=spec.interpret,
                          rescale=spec.rescale)

"""Multi-head Latent Attention (DeepSeek) with ETAP decode.

Training / prefill use the "naive" (decompressed) form. Decode uses the
*absorbed* form FlashMLA targets: the per-head up-projections W_uk / W_uv are
folded into the query and output, so attention runs over the shared 576-d
latent cache  c = [rmsnorm(c_kv) ; rope(k_r)]  — a single [B,S,576] stream
serving both K and V (V = c[..., :kv_lora_rank]).  This is the exact
16-heads-vs-huge-context GEMM the paper transposes with ETAP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import attn_spec
from repro.core.etap import (decode_attention, decode_attention_paged,
                             dsa_decode_paged, dsa_prefill_select,
                             prefill_attention_paged, seq_sharded_decode,
                             verify_attention_paged)
from repro.models import layers
from repro.models.attention import causal_attention
from repro.runtime import paged_cache


def init_mla(key, cfg, dtype):
    m = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 7)
    params = {
        "w_dq": layers.init_dense(ks[0], D, m.q_lora_rank, dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "w_uq": layers.init_dense(ks[1], m.q_lora_rank, H * m.qk_head_dim, dtype),
        # fused down-projection: [kv_lora | rope] columns
        "w_dkv": layers.init_dense(ks[2], D, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": layers.init_dense(ks[3], m.kv_lora_rank, H * m.qk_nope_head_dim, dtype),
        "w_uv": layers.init_dense(ks[4], m.kv_lora_rank, H * m.v_head_dim, dtype),
        "w_o": layers.init_dense(ks[5], H * m.v_head_dim, D, dtype),
    }
    if m.indexer is not None:
        params["idx"] = init_indexer(jax.random.fold_in(key, 7), cfg, dtype)
    return params


def init_indexer(key, cfg, dtype):
    """The lightning indexer's weights (DeepSeek Sparse Attention), drawn
    from a key folded off the block's MLA key so that every other weight
    draws as it does without an indexer."""
    m, ix, D = cfg.mla, cfg.mla.indexer, cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "w_q": layers.init_dense(ks[0], m.q_lora_rank,
                                 ix.n_heads * ix.head_dim, dtype),
        "w_k": layers.init_dense(ks[1], D, ix.head_dim, dtype),
        "k_scale": jnp.ones((ix.head_dim,), dtype),
        "k_bias": jnp.zeros((ix.head_dim,), dtype),
        "w_w": layers.init_dense(ks[2], D, ix.n_heads, dtype),
    }


def _q_latent(params, cfg, x):
    """x: [..., D] -> the normed q latent cq [..., q_lora_rank]."""
    return layers.rms_norm(layers.dense(x, params["w_dq"]), params["q_norm"],
                           cfg.norm_eps)


def _queries(params, cfg, x, positions, cq=None):
    """x: [..., D] -> (q_nope [..., H, nope], q_rope [..., H, rope])."""
    m, H = cfg.mla, cfg.num_heads
    if cq is None:
        cq = _q_latent(params, cfg, x)
    q = layers.dense(cq, params["w_uq"]).reshape(*x.shape[:-1], H, m.qk_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _rope_head(x, positions, cfg):
    """RoPE on the first qk_rope_head_dim columns of x [..., S, (heads,) d]."""
    r = cfg.mla.qk_rope_head_dim
    return jnp.concatenate(
        [layers.apply_rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]],
        axis=-1)


def _dense_f32(x, w):
    return jnp.einsum("...d,df->...f", x, w,
                      preferred_element_type=jnp.float32)


# The indexer's projections, LayerNorm and RoPE stay in fp32 and q and k
# are rounded to the pool's dtype once, at the end: the scores decide a
# top-k, and every extra rounding moves rows across its threshold.
def index_keys(params, cfg, x, positions):
    """The indexer's key rows: x [..., S, D] -> [..., S, head_dim] in x's
    dtype, one key shared by every index head: LayerNorm(x W_k), the
    first rope columns rotated."""
    p = params["idx"]
    k = layers.layer_norm(_dense_f32(x, p["w_k"]), p["k_scale"],
                          p["k_bias"], cfg.norm_eps)
    return _rope_head(k, positions, cfg).astype(x.dtype)


def index_queries(params, cfg, x, cq, positions):
    """The indexer's queries and head weights for rows x [..., S, D] with
    q latent cq [..., S, q_lora]: q [..., S, n_heads, head_dim] in cq's
    dtype (the first rope columns rotated) and fp32 weights
    w [..., S, n_heads], scaled by n_heads^-1/2 · head_dim^-1/2.  A past
    row s scores sum_h w[h] · ReLU(q[h] · k_s)."""
    ix = cfg.mla.indexer
    p = params["idx"]
    q = _dense_f32(cq, p["w_q"]).reshape(*cq.shape[:-1], ix.n_heads,
                                         ix.head_dim)
    w = _dense_f32(x, p["w_w"]) * (ix.n_heads ** -0.5 * ix.head_dim ** -0.5)
    return _rope_head(q, positions, cfg).astype(cq.dtype), w


def _latent(params, cfg, x, positions):
    """x: [..., D] -> latent cache rows [..., kv_lora+rope] (c in the paper)."""
    m = cfg.mla
    dkv = layers.dense(x, params["w_dkv"])
    c_kv = layers.rms_norm(dkv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = layers.apply_rope(dkv[..., m.kv_lora_rank:], positions, cfg.rope_theta)
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def mla_train(params, cfg, x, positions, *, return_cache: bool = False):
    """Naive (decompressed) MLA for training/prefill. x: [B,S,D] -> [B,S,D]."""
    m, H = cfg.mla, cfg.num_heads
    B, S, D = x.shape
    q_nope, q_rope = _queries(params, cfg, x, positions)
    c = _latent(params, cfg, x, positions)                    # [B,S,kv+rope]
    c_kv, k_rope = c[..., : m.kv_lora_rank], c[..., m.kv_lora_rank:]
    k_nope = layers.dense(c_kv, params["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = layers.dense(c_kv, params["w_uv"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = m.qk_head_dim ** -0.5
    o = causal_attention(q, k, v, scale=scale)                # kv heads == H
    out = layers.dense(o.reshape(B, S, H * m.v_head_dim), params["w_o"])
    if return_cache:
        return out, {"c": c}
    return out


def _absorbed_query(params, cfg, x, positions, cq=None, width=None):
    """Absorbed-form decode query:
    q_c[b,h] = q_nope[b,h] · W_uk[:,h]  (512-d), q = [q_c ; q_rope] (576-d).
    x: [B,D]; positions: [B,1]; cq: the q latent [B,1,q_lora] where the
    caller has it; width: a paged pool's row width, to which q is
    zero-extended (its zero lanes meet the pool's padded lanes, so the
    scores are exact).  Returns q: [B,H,width or latent]."""
    m, H = cfg.mla, cfg.num_heads
    q_nope, q_rope = _queries(params, cfg, x[:, None, :], positions, cq)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]               # [B,H,*]
    # absorb W_uk into the query: [B,H,nope] x [kv,H,nope] -> [B,H,kv]
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_c = jnp.einsum("bhd,chd->bhc", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32)).astype(x.dtype)
    q = jnp.concatenate([q_c, q_rope], axis=-1)               # [B,H,latent]
    return q if width is None else paged_cache.pad_lanes(q, width)


def _absorbed_out(params, cfg, o_lat, dtype):
    """Fold W_uv into the latent attention output and project:
    o[b,h] = o_latent[b,h] · W_uvᵀ → W_o. o_lat: [B,H,kv]. Returns [B,D]."""
    m, H = cfg.mla, cfg.num_heads
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhc,chd->bhd", o_lat.astype(jnp.float32),
                   w_uv.astype(jnp.float32)).astype(dtype)
    return layers.dense(o.reshape(o.shape[0], -1), params["w_o"])


def mla_decode(params, cfg, x, cache, pos, *, spec=None, **legacy):
    """Absorbed-form decode. x: [B,D]; cache: {"c": [B,Smax,latent]}.
    spec.kv_splits: split-KV count for the decode kernel (None = auto);
    the per-layer scale and cfg.use_kernels are folded into the spec here.

    scores   = q · cᵀ  — via ETAP as  c · qᵀ  with the context on M.
    o_latent = P · c[..., :512]; see :func:`_absorbed_query`/`_absorbed_out`.
    """
    spec = attn_spec.coerce(spec, legacy, where="mla_decode")
    m = cfg.mla
    B, D = x.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _absorbed_query(params, cfg, x, positions)
    c_t = _latent(params, cfg, x[:, None, :], positions)[:, 0]  # [B,latent]
    scale = m.qk_head_dim ** -0.5
    from repro.sharding.rules import current_mesh, seq_shardable
    mesh = current_mesh()
    seq_shard = seq_shardable(cache["c"].shape[1], mesh)
    if seq_shard:
        # latent cache is S-sharded over the model axis (no head dim to
        # shard — the paper's single-instance scenario); flash-decode-style
        # cross-shard softmax combine. See core.etap.seq_sharded_decode.
        o_lat, cache_c = seq_sharded_decode(
            q, cache["c"], c_t, pos, dv=m.kv_lora_rank, scale=scale)
    else:
        cache_c = jax.lax.dynamic_update_index_in_dim(cache["c"], c_t, pos, 1)
        length = jnp.full((B,), pos + 1, jnp.int32)
        # Single latent stream: K is the full 576 latent, V its first 512 cols.
        o_lat = decode_attention(
            q, cache_c, cache_c[..., : m.kv_lora_rank], length,
            spec=spec.replace(scale=scale,
                              use_kernels=cfg.use_kernels))    # [B,H,512]
    return _absorbed_out(params, cfg, o_lat, x.dtype), {"c": cache_c}


def mla_decode_paged(params, cfg, x, cache, table, lengths, *,
                     spec=None, **legacy):
    """Absorbed-form decode against a PAGED latent cache.

    x: [B,D]; cache: {"c": pool [num_blocks, page, W]}; table:
    [B,max_blocks]; lengths: [B] — each sequence's new token is written at
    its own position `lengths[b]` (continuous batching serves ragged
    lengths, so there is no shared scalar `pos`).  The pool stores each
    latent row lane-aligned, W = lane_width(latent) (640 for 576), its
    padded lanes 0; the query is zero-extended to W, so K is the whole
    stored row.  The pool is streamed once through the block table; V is
    its first kv_lora_rank columns (same one-stream argument as the dense
    MLA path).
    Returns (out [B,D], {"c": updated pool}, sel): sel the positions
    DeepSeek Sparse Attention selected, [B,k] (the first min(lengths + 1,
    k) of each row valid), None without an indexer."""
    spec = attn_spec.coerce(spec, legacy, where="mla_decode_paged")
    m = cfg.mla
    B, D = x.shape
    positions = lengths[:, None].astype(jnp.int32)            # [B,1]
    if m.indexer is not None:
        return _mla_decode_dsa(params, cfg, x, cache, table, lengths,
                               positions, spec)
    q = _absorbed_query(params, cfg, x, positions,
                        width=cache["c"].shape[-1])
    c_t = _latent(params, cfg, x[:, None, :], positions)[:, 0]  # [B,latent]
    inner = spec.replace(scale=m.qk_head_dim ** -0.5,
                         use_kernels=cfg.use_kernels)
    if "c_sz" in cache:        # quantized layout: codes + (scale, zp) pools
        pool, sz = paged_cache.append_rows_quant(
            cache["c"], cache["c_sz"], table, lengths, c_t)
        o_lat = decode_attention_paged(
            q, pool, None, table, lengths + 1, spec=inner,
            dv=m.kv_lora_rank, k_sz=sz)
        return (_absorbed_out(params, cfg, o_lat, x.dtype),
                {"c": pool, "c_sz": sz}, None)
    pool = paged_cache.append_rows(cache["c"], table, lengths, c_t)
    o_lat = decode_attention_paged(
        q, pool, None, table, lengths + 1, spec=inner,
        dv=m.kv_lora_rank)                                    # [B,H,512]
    return _absorbed_out(params, cfg, o_lat, x.dtype), {"c": pool}, None


def _mla_decode_dsa(params, cfg, x, cache, table, lengths, positions, spec):
    """DeepSeek Sparse Attention decode (DESIGN.md §17): append the new
    latent row and index key, score every row of each sequence with the
    lightning indexer, and run absorbed MLA over the top-k rows only."""
    m = cfg.mla
    cq = _q_latent(params, cfg, x[:, None, :])                # [B,1,q_lora]
    q = _absorbed_query(params, cfg, x, positions, cq,
                        width=cache["c"].shape[-1])
    xs = x[:, None, :]
    c_t = _latent(params, cfg, xs, positions)[:, 0]           # [B,latent]
    k_t = index_keys(params, cfg, xs, positions)[:, 0]        # [B,idx_dim]
    q_idx, w = index_queries(params, cfg, xs, cq, positions)
    pool = paged_cache.append_rows(cache["c"], table, lengths, c_t)
    kpool = paged_cache.append_rows(cache["k_idx"], table, lengths, k_t)
    o_lat, sel = dsa_decode_paged(
        q, pool, kpool, q_idx[:, 0], w[:, 0], table, lengths + 1,
        spec=spec.replace(scale=m.qk_head_dim ** -0.5,
                          use_kernels=cfg.use_kernels),
        dv=m.kv_lora_rank, topk=m.indexer.topk)               # [B,H,512]
    return (_absorbed_out(params, cfg, o_lat, x.dtype),
            {"c": pool, "k_idx": kpool}, sel)


def _mla_chunk(params, cfg, x, cache, table, lengths, positions, *, spec,
               qpos=None):
    """Shared body of chunked prefill and draft verification: append the
    chunk's latent rows through the table, then run absorbed-form attention
    over pool positions <= each query row's own horizon.  ``positions``
    [B,C] drives rope AND (via qpos) the causal mask; qpos None → the
    prefill entry (horizon = start + row index, implied by the kernel),
    else the explicit per-row horizon of the verify entry.  Under DeepSeek
    Sparse Attention (DESIGN.md §17) the chunk's index keys are appended
    too, and each row attends only its top-k causal rows: a mask on the
    paged prefill kernel, exact and dense in FLOPs.  Returns (out, new
    cache, sel): sel the chunk's last row's mask [B, max_blocks*page]
    under DeepSeek Sparse Attention, else None."""
    m, H = cfg.mla, cfg.num_heads
    B, C, D = x.shape
    cq = _q_latent(params, cfg, x)                            # [B,C,q_lora]
    q_nope, q_rope = _queries(params, cfg, x, positions, cq)  # [B,C,H,*]
    # absorb W_uk into the chunk queries: [B,C,H,nope] x [kv,H,nope]
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_c = jnp.einsum("bchd,khd->bchk", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32)).astype(x.dtype)
    q = paged_cache.pad_lanes(jnp.concatenate([q_c, q_rope], axis=-1),
                              cache["c"].shape[-1])           # [B,C,H,W]
    c_rows = _latent(params, cfg, x, positions)               # [B,C,latent]
    inner = spec.replace(scale=m.qk_head_dim ** -0.5,
                         use_kernels=cfg.use_kernels)
    if "c_sz" in cache:        # quantized layout: codes + (scale, zp) pools
        pool, sz = paged_cache.append_chunk_quant(
            cache["c"], cache["c_sz"], table, lengths, c_rows)
        kw = dict(spec=inner, dv=m.kv_lora_rank, k_sz=sz)
        new_cache = {"c": pool, "c_sz": sz}
    else:
        pool = paged_cache.append_chunk(cache["c"], table, lengths, c_rows)
        kw = dict(spec=inner, dv=m.kv_lora_rank)
        new_cache = {"c": pool}
    sel = None
    if m.indexer is not None:
        if qpos is not None:
            raise ValueError("DeepSeek Sparse Attention has no draft "
                             "verification")
        kpool = paged_cache.append_chunk(
            cache["k_idx"], table, lengths,
            index_keys(params, cfg, x, positions))
        q_idx, w = index_queries(params, cfg, x, cq, positions)
        kw["sel"] = sel = dsa_prefill_select(
            q_idx, w, kpool, table, lengths, spec=inner,
            topk=m.indexer.topk)
        new_cache["k_idx"] = kpool
    if qpos is None:
        o_lat = prefill_attention_paged(q, pool, None, table, lengths, **kw)
    else:
        o_lat = verify_attention_paged(q, pool, None, table, lengths, qpos,
                                       **kw)                  # [B,C,H,kv]
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bchk,khd->bchd", o_lat.astype(jnp.float32),
                   w_uv.astype(jnp.float32)).astype(x.dtype)
    out = layers.dense(o.reshape(B, C, H * m.v_head_dim), params["w_o"])
    return out, new_cache, (None if sel is None else sel[:, -1])


def mla_prefill_chunk(params, cfg, x, cache, table, lengths, *,
                      spec=None, **legacy):
    """Absorbed-form CHUNKED prefill against a paged latent cache
    (DESIGN.md §9).

    x: [B,C,D] — C prompt tokens per sequence at absolute positions
    lengths[b] + c; cache: {"c": pool}; table: [B,max_blocks]; lengths: [B]
    tokens already written (the chunk start).  The chunk's latent rows are
    appended into the pool FIRST, then attention runs over pool positions
    <= each query's own position — causal inside the chunk, full over the
    previously-written context.  Mathematically this is the single-shot
    naive prefill: q·k = [q_nope·W_uk ; q_rope]·[c_kv ; k_rope] and
    o = P·(W_uv c_kv) = (P·c_kv)·W_uv, so scores and outputs agree with
    mla_train to float noise while streaming the 576-wide latent once.
    Returns (out [B,C,D], {"c": updated pool}, sel): sel the last chunk
    row's DeepSeek Sparse Attention mask, None without an indexer."""
    spec = attn_spec.coerce(spec, legacy, where="mla_prefill_chunk")
    C = x.shape[1]
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    return _mla_chunk(params, cfg, x, cache, table, lengths, positions,
                      spec=spec)


def mla_verify_chunk(params, cfg, x, cache, table, lengths, qpos, *,
                     spec=None, **legacy):
    """Absorbed-form DRAFT VERIFICATION against the paged latent cache
    (DESIGN.md §14): score k draft rows in one chunked-prefill-shaped pass.

    x: [B,k,D] — the draft tokens' embeddings; qpos: [B,k] each draft
    row's absolute position (a linear chain is lengths[:, None] +
    arange(k), which makes this bitwise identical to
    :func:`mla_prefill_chunk`).  The draft latent rows are appended into
    the pool at lengths — the in-cache half of in-cache verification; the
    scheduler rewinds rejected rows afterwards with BlockPool.truncate,
    never a pool rewrite.  Returns (out [B,k,D], updated cache)."""
    spec = attn_spec.coerce(spec, legacy, where="mla_verify_chunk")
    qpos = qpos.astype(jnp.int32)
    out, cache, _ = _mla_chunk(params, cfg, x, cache, table, lengths, qpos,
                               spec=spec, qpos=qpos)
    return out, cache


def init_mla_cache(cfg, batch: int, max_len: int, dtype):
    return {"c": jnp.zeros((batch, max_len, cfg.mla.latent_dim), dtype)}


def init_mla_cache_paged(cfg, layout, dtype, kv_dtype: str = "fp"):
    """Paged latent pool (block 0 = reserved null block, see
    runtime/paged_cache.py), its rows lane_width(latent) wide: the TPU's
    default layout of such a pool is row-major, the layout the kernels
    read, so no program relays it (DESIGN.md §8).  kv_dtype "int8"/"fp8":
    the pool stores codes and a parallel per-row (scale, zp) pool rides
    under "c_sz" (DESIGN.md §11); scale 1 / zp 0 makes the all-zero init
    round-trip exactly."""
    shape = (layout.num_blocks, layout.block_size,
             paged_cache.lane_width(cfg.mla.latent_dim))
    qdt = paged_cache.quant_dtype(kv_dtype)
    ix = cfg.mla.indexer
    if ix is not None:
        if qdt is not None:
            raise ValueError("DeepSeek Sparse Attention keeps its latent and "
                             f"index pools in {jnp.dtype(dtype).name}; "
                             f"kv_dtype {kv_dtype!r} is not supported")
        # the indexer's keys: a second paged leaf under the same table
        return {"c": jnp.zeros(shape, dtype),
                "k_idx": jnp.zeros(shape[:2] + (ix.head_dim,), dtype)}
    if qdt is None:
        return {"c": jnp.zeros(shape, dtype)}
    sz0 = jnp.concatenate(
        [jnp.ones(shape[:2] + (1,), jnp.float32),        # scale
         jnp.zeros(shape[:2] + (1,), jnp.float32)], -1)  # zero-point
    return {"c": jnp.zeros(shape, qdt), "c_sz": sz0}


def mla_prefill_cache(params, cfg, x, positions):
    """Latent cache rows for a whole prompt (used by prefill)."""
    return _latent(params, cfg, x, positions)

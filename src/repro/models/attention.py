"""Attention layers: training/prefill (blockwise causal, local-window) and
decode (via the ETAP core).

Sharding notes (DESIGN.md §5): train/prefill attention keeps tensors in the
[B,S,H,*] head-major layout with KV expanded to H heads, so the head dim can
ride the `model` mesh axis whenever divisible (best-effort `constrain`).
Per-chunk jax.checkpoint makes the f32 score blocks transient in the
backward pass (flash-style recompute) instead of stacked residuals.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import attn_spec
from repro.core.etap import (decode_attention, gqa_decode_xla, gqa_to_grouped,
                             seq_sharded_gqa_decode)
from repro.models import layers
from repro.runtime import paged_cache
from repro.sharding.rules import (BATCH, constrain, current_mesh,
                                  seq_shardable)

NEG_INF = -1e30


def _score_constraint(s):
    """Scores [B,H,q,S]: shard heads over `model` when divisible, else fall
    back to sharding the q-position dim (e.g. llava's 56 heads on TP16)."""
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return s
    if s.shape[1] % mesh.shape["model"] == 0:
        return constrain(s, P(BATCH, "model", None, None))
    return constrain(s, P(BATCH, None, "model", None))


def _expand_kv(k, H: int):
    """[B,S,K,hd] -> [B,S,H,hd] by group broadcast (keeps head-dim sharding)."""
    B, S, K, hd = k.shape
    G = H // K
    if G == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, K, G, hd))
    return k.reshape(B, S, H, hd)


# ------------------------------------------------------------- train/prefill
def causal_attention(q, k, v, *, scale: float, q_block: int = 512):
    """Blockwise causal attention (chunked over queries; masked full-KV per
    chunk).  q: [B,S,H,D]; k,v: [B,S,K,D*] with H = K*G.  Returns [B,S,H,Dv].

    The XLA path eats the masked upper-triangle FLOPs; the Pallas prefill
    kernel (kernels/flash_prefill) skips those blocks on TPU — see DESIGN.md.
    """
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    q_block = min(q_block, S)
    assert S % q_block == 0
    nq = S // q_block

    spec = P(BATCH, None, "model", None)
    q = constrain(q, spec)
    kf = constrain(_expand_kv(k, H), spec)        # bf16; f32 only in the MXU
    vf = constrain(_expand_kv(v, H), spec)
    qc = jnp.swapaxes(q.reshape(B, nq, q_block, H, D), 0, 1)
    kpos = jnp.arange(S, dtype=jnp.int32)

    @jax.checkpoint
    def chunk(i, qi):                     # qi: [B, q_block, H, D]
        s = jnp.einsum("bqhd,bshd->bhqs", qi, kf,
                       preferred_element_type=jnp.float32) * scale
        s = _score_constraint(s)
        qpos = i * q_block + jnp.arange(q_block, dtype=jnp.int32)
        mask = qpos[:, None] >= kpos[None, :]                 # [q_block, S]
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", p, vf,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(lambda xs: chunk(xs[0], xs[1]), (jnp.arange(nq), qc))
    out = jnp.swapaxes(out, 0, 1).reshape(B, S, H, Dv)
    return constrain(out.astype(v.dtype), spec)


def local_attention(q, k, v, *, window: int, scale: float):
    """Sliding-window causal attention, chunk = window: query chunk i attends
    kv chunks {i-1, i} under the band mask. O(S·2w) compute/memory."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    w = min(window, S)
    assert S % w == 0, f"S={S} % window={w} != 0"
    nc = S // w

    spec = P(BATCH, None, "model", None)
    q = constrain(q, spec)
    kh = constrain(_expand_kv(k, H), spec)
    vh = constrain(_expand_kv(v, H), spec)

    qc = jnp.swapaxes(q.reshape(B, nc, w, H, D), 0, 1)        # [nc,B,w,H,D]
    kc = jnp.swapaxes(kh.reshape(B, nc, w, H, D), 0, 1)
    vc = jnp.swapaxes(vh.reshape(B, nc, w, H, Dv), 0, 1)
    # previous chunk (zeros for chunk 0; masked out by the band anyway)
    kprev = jnp.pad(kc, ((1, 0), (0, 0), (0, 0), (0, 0), (0, 0)))[:-1]
    vprev = jnp.pad(vc, ((1, 0), (0, 0), (0, 0), (0, 0), (0, 0)))[:-1]

    qpos = jnp.arange(w, dtype=jnp.int32)[:, None] + w        # within-pair coords
    kpos = jnp.arange(2 * w, dtype=jnp.int32)[None, :]
    band = (qpos >= kpos) & (qpos - kpos < w)                 # causal ∧ window

    @jax.checkpoint
    def chunk(args):
        i, qi, ki, vi, kp, vp = args
        k2 = jnp.concatenate([kp, ki], axis=1)                # [B,2w,H,D]
        v2 = jnp.concatenate([vp, vi], axis=1)
        s = jnp.einsum("bqhd,bshd->bhqs", qi, k2,
                       preferred_element_type=jnp.float32) * scale
        s = _score_constraint(s)
        valid = band & ~((i == 0) & (kpos < w))               # no prev for chunk 0
        s = jnp.where(valid[None, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", p, v2,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(chunk, (jnp.arange(nc), qc, kc, vc, kprev, vprev))
    out = jnp.swapaxes(out, 0, 1).reshape(B, S, H, Dv)
    return constrain(out.astype(v.dtype), spec)


# ------------------------------------------------------------------- decode
def gqa_decode(q, k_cache, v_cache, length, *, spec=None, **legacy):
    """One-token decode against a [B,S,K,D] cache. q: [B,H,D] -> [B,H,Dv].
    `spec.mode` selects ETAP (paper) vs standard (baseline) pipelines.
    The XLA path streams the cache in its native layout (no reshuffle copy);
    the Pallas path (tests/benchmarks) uses the grouped [BG,...] form.
    spec.kv_splits: split-KV count (None = auto-scheduled on the kernel
    path).  An EXPLICIT kv_splits > 1 on the XLA etap path is honoured
    through the grouped form — that costs the cache reshuffle copy, so it
    is opt-in rather than auto there."""
    spec = attn_spec.coerce(spec, legacy, where="gqa_decode")
    B, H, D = q.shape
    K = k_cache.shape[2]
    n_splits = spec.kv_splits
    want_xla_split = (not spec.use_kernels and spec.mode == "etap"
                      and n_splits is not None and n_splits > 1)
    if spec.use_kernels or want_xla_split:
        qg, kg, vg, restore = gqa_to_grouped(q, k_cache, v_cache)
        lg = jnp.repeat(length, K) if length.ndim else jnp.full((B * K,), length)
        o = decode_attention(qg, kg, vg, lg, spec=spec)
        return restore(o)
    q4 = q.reshape(B, K, H // K, D)
    return gqa_decode_xla(q4, k_cache, v_cache, length, spec=spec)


# --------------------------------------------------------- attention module
def init_attention(key, cfg, dtype):
    H, Kv, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "w_q": layers.init_dense(ks[0], D, H * hd, dtype),
        "w_k": layers.init_dense(ks[1], D, Kv * hd, dtype),
        "w_v": layers.init_dense(ks[2], D, Kv * hd, dtype),
        "w_o": layers.init_dense(ks[3], H * hd, D, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(params, cfg, x, positions):
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lead = x.shape[:-1]
    q = layers.dense(x, params["w_q"]).reshape(*lead, H, hd)
    k = layers.dense(x, params["w_k"]).reshape(*lead, Kv, hd)
    v = layers.dense(x, params["w_v"]).reshape(*lead, Kv, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_train(params, cfg, x, positions, *, return_cache: bool = False):
    """x: [B,S,D] -> [B,S,D]. Full or local causal attention per config."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    scale = cfg.resolved_head_dim ** -0.5
    if cfg.attention_kind == "local":
        o = local_attention(q, k, v, window=cfg.window_size, scale=scale)
    else:
        o = causal_attention(q, k, v, scale=scale)
    out = layers.dense(o.reshape(*x.shape[:-1], -1), params["w_o"])
    if return_cache:
        return out, {"k": k, "v": v}
    return out


def attention_decode(params, cfg, x, cache, pos, *, spec=None, **legacy):
    """x: [B,D] one token; cache: {"k","v"}: [B,S,K,hd] (ring buffer of size
    window for local attention). Returns (out [B,D], new cache).
    spec.kv_splits: split-KV count for the kernel decode path (None = auto);
    the per-layer scale and cfg.use_kernels are folded into the spec here."""
    spec = attn_spec.coerce(spec, legacy, where="attention_decode")
    B, D = x.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(params, cfg, x[:, None, :], positions)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                        # [B,H,hd],[B,K,hd]
    Smax = cache["k"].shape[1]
    K = k.shape[1]
    scale = cfg.resolved_head_dim ** -0.5
    spec = attn_spec.resolve_backend(
        spec.replace(scale=scale, use_kernels=cfg.use_kernels))
    mesh = current_mesh()
    seq_shard = (cfg.attention_kind == "full" and not spec.use_kernels
                 and seq_shardable(Smax, mesh))
    if seq_shard:
        # big full-attention cache: S-sharded over `model` (shard_map partial
        # softmax + tiny stats exchange) — same scheme as MLA decode.
        q4 = q.reshape(B, K, cfg.num_heads // K, cfg.resolved_head_dim)
        o, kc, vc = seq_sharded_gqa_decode(q4, cache["k"], cache["v"], k, v,
                                           pos, scale=scale)
    else:
        slot = pos % Smax if cfg.attention_kind == "local" else pos
        kc = jax.lax.dynamic_update_index_in_dim(cache["k"], k, slot, 1)
        vc = jax.lax.dynamic_update_index_in_dim(cache["v"], v, slot, 1)
        length = jnp.minimum(pos + 1, Smax)
        o = gqa_decode(q, kc, vc, jnp.full((B,), length, jnp.int32),
                       spec=spec)
    out = layers.dense(o.reshape(B, -1), params["w_o"])
    return out, {"k": kc, "v": vc}


def _append_paged_kv(cache, table, lengths, k, v):
    """Append one row (or a [B,C,...] chunk) of K/V into the paged GQA
    cache, quantizing on write when the cache carries (scale, zp) pools
    (DESIGN.md §11).  Returns the updated cache dict."""
    chunked = k.ndim == 4                          # [B,C,K,hd] vs [B,K,hd]
    if "k_sz" in cache:
        app = (paged_cache.append_chunk_quant if chunked
               else paged_cache.append_rows_quant)
        kc, k_sz = app(cache["k"], cache["k_sz"], table, lengths, k)
        vc, v_sz = app(cache["v"], cache["v_sz"], table, lengths, v)
        return {"k": kc, "v": vc, "k_sz": k_sz, "v_sz": v_sz}
    app = paged_cache.append_chunk if chunked else paged_cache.append_rows
    return {"k": app(cache["k"], table, lengths, k),
            "v": app(cache["v"], table, lengths, v)}


def _gather_paged_kv(cache, table):
    """Dense [B,S,K,hd] views of the paged GQA cache, dequantized when the
    pools hold codes (the GQA paged path is gather-based — see
    attention_decode_paged; MLA streams its pool in place instead)."""
    kd = paged_cache.gather_blocks(cache["k"], table)
    vd = paged_cache.gather_blocks(cache["v"], table)
    if "k_sz" in cache:
        kd = paged_cache.dequantize_rows(
            kd, paged_cache.gather_blocks(cache["k_sz"], table))
        vd = paged_cache.dequantize_rows(
            vd, paged_cache.gather_blocks(cache["v_sz"], table))
    return kd, vd


def attention_decode_paged(params, cfg, x, cache, table, lengths, *,
                           spec=None, **legacy):
    """One-token GQA decode against a PAGED cache: {"k","v"} pools of shape
    [num_blocks, page, K, hd], a shared block table and per-sequence
    lengths (ragged — each new token lands at its own `lengths[b]`).

    The new KV row is appended through the table; attention then gathers
    the pool into the native dense [B,S,K,hd] layout and reuses the
    existing GQA paths — correctness-first: the GQA pool carries a kv-head
    axis the grouped paged kernels don't stride over (yet), so only MLA
    (the paper's serving path) streams its pool in place.  Local-window
    attention keeps its dense ring buffer (a window never pages)."""
    spec = attn_spec.coerce(spec, legacy, where="attention_decode_paged")
    assert cfg.attention_kind == "full", \
        "paged cache supports full attention (local windows stay dense)"
    B, D = x.shape
    positions = lengths[:, None].astype(jnp.int32)
    q, k, v = _project_qkv(params, cfg, x[:, None, :], positions)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                       # [B,H,hd],[B,K,hd]
    new_cache = _append_paged_kv(cache, table, lengths, k, v)
    kd, vd = _gather_paged_kv(new_cache, table)               # [B,S,K,hd]
    if "k_sz" in cache:
        q = q.astype(jnp.float32)         # match the dequantized fp32 rows
    o = gqa_decode(q, kd, vd, lengths + 1,
                   spec=spec.replace(scale=cfg.resolved_head_dim ** -0.5,
                                     use_kernels=cfg.use_kernels,
                                     block=cache["k"].shape[1]))
    # back to the model dtype: under a quantized layout the dequantized
    # rows (and hence gqa_decode's output) are fp32 — without the cast
    # every decode step's residual stream would silently promote
    out = layers.dense(o.reshape(B, -1).astype(x.dtype), params["w_o"])
    return out, new_cache


def _attention_chunk(params, cfg, x, cache, table, lengths, positions):
    """Shared body of chunked prefill and draft verification over the paged
    GQA cache: append the chunk's K/V rows, gather, run the masked
    chunk-vs-context product.  ``positions`` [B,C] drives rope AND the
    per-row causal horizon (key position p live for row c iff
    p <= positions[b, c]) — prefill passes start + row index, verification
    passes the explicit draft-row horizons (identical on linear chains)."""
    B, C, D = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)  # [B,C,H,hd],[B,C,K,hd]
    new_cache = _append_paged_kv(cache, table, lengths, k, v)
    kd, vd = _gather_paged_kv(new_cache, table)               # [B,S,K,hd]
    H = cfg.num_heads
    S = kd.shape[1]
    kh = _expand_kv(kd, H)
    vh = _expand_kv(vd, H)
    if "k_sz" in cache:
        q = q.astype(jnp.float32)         # match the dequantized fp32 rows
    s = jnp.einsum("bchd,bshd->bhcs", q, kh,
                   preferred_element_type=jnp.float32) * cfg.resolved_head_dim ** -0.5
    kpos = jnp.arange(S, dtype=jnp.int32)
    valid = kpos[None, None, :] <= positions[:, :, None]      # [B,C,S]
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhcs,bshv->bchv", p, vh,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    out = layers.dense(o.reshape(B, C, -1), params["w_o"])
    return out, new_cache


def attention_prefill_chunk(params, cfg, x, cache, table, lengths, *,
                            spec=None, **legacy):
    """CHUNKED prefill of C prompt tokens against a PAGED GQA cache.

    x: [B,C,D]; cache: {"k","v"} pools [num_blocks, page, K, hd]; table:
    [B,max_blocks]; lengths: [B] tokens already written (the chunk start).
    The chunk's K/V rows are appended through the table first; attention
    then gathers the pool into the native dense [B,S,K,hd] layout and runs
    a causally-masked chunk-vs-context product — same correctness-first
    gather route as :func:`attention_decode_paged` (the GQA pool carries a
    kv-head axis the paged kernels don't stride over; MLA, the paper's
    serving path, streams its pool in place via core.etap).  The spec is
    accepted for entry-point parity; this dense-mask route has no knobs."""
    assert cfg.attention_kind == "full", \
        "paged cache supports full attention (local windows stay dense)"
    attn_spec.coerce(spec, legacy, where="attention_prefill_chunk")
    C = x.shape[1]
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    return _attention_chunk(params, cfg, x, cache, table, lengths, positions)


def attention_verify_chunk(params, cfg, x, cache, table, lengths, qpos, *,
                           spec=None, **legacy):
    """DRAFT VERIFICATION over the paged GQA cache (DESIGN.md §14): score k
    draft rows in one chunked-prefill-shaped pass.  qpos: [B,k] each draft
    row's absolute position; a linear chain (lengths[:, None] + arange(k))
    makes this bitwise identical to :func:`attention_prefill_chunk`.
    Rejected rows are rewound by the scheduler via BlockPool.truncate."""
    assert cfg.attention_kind == "full", \
        "paged cache supports full attention (local windows stay dense)"
    attn_spec.coerce(spec, legacy, where="attention_verify_chunk")
    return _attention_chunk(params, cfg, x, cache, table, lengths,
                            qpos.astype(jnp.int32))


def init_attention_cache(cfg, batch: int, max_len: int, dtype):
    Kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    n = min(max_len, cfg.window_size) if cfg.attention_kind == "local" else max_len
    return {"k": jnp.zeros((batch, n, Kv, hd), dtype),
            "v": jnp.zeros((batch, n, Kv, hd), dtype)}


def init_attention_cache_paged(cfg, layout, dtype, kv_dtype: str = "fp"):
    Kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (layout.num_blocks, layout.block_size, Kv, hd)
    qdt = paged_cache.quant_dtype(kv_dtype)
    if qdt is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    # per-row (scale, zp) PER KV HEAD: the quantization granule is the
    # head's hd-vector (DESIGN.md §11); scale 1 round-trips the zero init
    sz0 = jnp.concatenate(
        [jnp.ones(shape[:3] + (1,), jnp.float32),
         jnp.zeros(shape[:3] + (1,), jnp.float32)], -1)
    return {"k": jnp.zeros(shape, qdt), "v": jnp.zeros(shape, qdt),
            "k_sz": sz0, "v_sz": sz0}

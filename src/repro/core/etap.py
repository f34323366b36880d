"""Efficient Transpose Attention Pipeline (ETAP) — the paper's contribution.

Decode attention computes, per (batch, kv-group):
    standard:  S  = Q Kᵀ,  P = softmax_rows(S),  O = P V          (thin M = heads)
    ETAP:      Sᵀ = K Qᵀ,  Pᵀ = softmax_cols(Sᵀ), Oᵀ = Vᵀ Pᵀ,  O = (Oᵀ)ᵀ
with the online-softmax recurrence carried per *column* of the transposed block
(paper Algorithm 1).  The KV context length rides the M-dimension of every GEMM
in the hot loop, so the thin head dimension never pads the systolic array's
M side, and the score/probability tiles keep S on sublanes end-to-end (see
DESIGN.md §2 for the TPU adaptation of the WGMMA argument).

This module is the *XLA* implementation (lax.scan over KV blocks) used by the
dry-run and as a mid-level reference; ``repro.kernels.etap`` is the Pallas TPU
kernel with the same math, and ``repro.kernels.etap.ref`` is the direct oracle.

Shapes (grouped-query form — MLA is the special case group_size=H, kv "heads"=1):
    q:  [BG, H, Dk]     BG = batch * kv_heads, H = q heads per kv head
    k:  [BG, S, Dk]
    v:  [BG, S, Dv]
    length: [BG] valid cache length per row (mask positions >= length)
returns O: [BG, H, Dv]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import attn_spec
from repro.kernels import softmax_state
from repro.sharding.rules import current_mesh

NEG_INF = softmax_state.NEG_INF


def _blocks(s: int, block: int) -> int:
    assert s % block == 0, f"S={s} not divisible by block={block}"
    return s // block


def etap_decode_xla(q, k, v, length=None, *, scale: float, block: int = 512,
                    rescale: str | None = None):
    """ETAP transposed decode attention, online softmax over KV blocks.

    Blocks are taken with lax.dynamic_slice inside a fori_loop (not scan xs),
    so the KV cache is streamed in place — no [nb, ...] transpose copy of the
    whole cache per decode step (that copy would double the memory roofline
    term of the paper's core workload)."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    block = min(block, S)
    nb = _blocks(S, block)
    mode = softmax_state.resolve(rescale)
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)

    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)            # [BG, Dk, H]

    def step(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        # Sᵀ = K·Qᵀ : [BG, block, H] — KV block length on the M dimension.
        sT = jnp.einsum("bkd,bdh->bkh", kj, qT.astype(k.dtype),
                        preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)  # [block]
        valid = pos[None, :] < length[:, None]                # [BG, block]
        sT = jnp.where(valid[:, :, None], sT, NEG_INF)
        # column-wise (per-head) stats; Oᵀ += Vᵀ·Pᵀ over the long KV axis.
        return softmax_state.update(
            carry, sT,
            lambda pT: jnp.einsum("bkv,bkh->bvh", vj, pT.astype(v.dtype),
                                  preferred_element_type=jnp.float32),
            axis=1, mode=mode, expand=lambda c: c[:, None, :])

    state = jax.lax.fori_loop(
        0, nb, step, softmax_state.init((BG, H), (BG, Dv, H)))
    oT = softmax_state.finalize(state, expand=lambda l: l[:, None, :])
    return jnp.swapaxes(oT, 1, 2).astype(v.dtype)             # final O = (Oᵀ)ᵀ


def standard_decode_xla(q, k, v, length=None, *, scale: float, block: int = 512,
                        rescale: str | None = None):
    """Baseline (FlashMLA-without-ETAP): untransposed flash decode. Same
    signature/semantics as :func:`etap_decode_xla`; the thin head dim rides M."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    block = min(block, S)
    nb = _blocks(S, block)
    mode = softmax_state.resolve(rescale)
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)

    qf = q.astype(jnp.float32)

    def step(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        s = jnp.einsum("bhd,bkd->bhk", qf.astype(k.dtype), kj,
                       preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]                # [BG, block]
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        return softmax_state.update(
            carry, s,
            lambda p: jnp.einsum("bhk,bkv->bhv", p.astype(v.dtype), vj,
                                 preferred_element_type=jnp.float32),
            axis=2, mode=mode, expand=lambda c: c[:, :, None])

    state = jax.lax.fori_loop(
        0, nb, step, softmax_state.init((BG, H), (BG, H, Dv)))
    return softmax_state.finalize(
        state, expand=lambda l: l[:, :, None]).astype(v.dtype)


def etap_partial_xla(q, k, v, length, *, scale: float, block: int = 512,
                     vary_axis=None, rescale: str | None = None):
    """ETAP loop WITHOUT the epilogue: returns raw (m, l, accT) softmax
    statistics — the combinable form used by sequence-sharded decode.
    vary_axis: shard_map manual axis name(s) to mark the carry varying over
    (required when called inside shard_map)."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    block = min(block, S)
    nb = _blocks(S, block)
    mode = softmax_state.resolve(rescale)

    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)

    def step(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        sT = jnp.einsum("bkd,bdh->bkh", kj, qT.astype(k.dtype),
                        preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]
        sT = jnp.where(valid[:, :, None], sT, NEG_INF)
        return softmax_state.update(
            carry, sT,
            lambda pT: jnp.einsum("bkv,bkh->bvh", vj, pT.astype(v.dtype),
                                  preferred_element_type=jnp.float32),
            axis=1, mode=mode, expand=lambda c: c[:, None, :])

    init = softmax_state.init((BG, H), (BG, Dv, H))
    if vary_axis is not None:
        init = jax.tree.map(
            lambda a: jax.lax.pcast(a, vary_axis, to="varying"), init)
    return jax.lax.fori_loop(0, nb, step, init)


def combine_partials(m, l, accT, *, rescale: str | None = None):
    """Merge per-shard (m, l, accT) stats (leading shard axis) into O.
    m,l: [n,BG,H]; accT: [n,BG,Dv,H] -> [BG,H,Dv].  The stat-domain merge
    (and its fp32-on-entry upcast — half-precision exp/sum here would erase
    the split-invariance the combine owes the single-pass path, DESIGN.md
    §6) is :func:`softmax_state.merge_splits`, shared with the Pallas
    combine kernel.  ``rescale`` must match the partials' producer."""
    _, l_g, acc_g = softmax_state.merge_splits(
        m, l, accT, axis=0, mode=softmax_state.resolve(rescale),
        expand=lambda w: w[:, :, None, :])
    oT = acc_g / l_g[:, None, :]                              # [BG,Dv,H]
    return jnp.swapaxes(oT, 1, 2)


def etap_decode_splitkv_xla(q, k, v, length=None, *, scale: float,
                            block: int = 512, n_splits: int = 2,
                            rescale: str | None = None):
    """Two-phase split-KV ETAP decode in pure XLA (DESIGN.md §3).

    The KV context is cut into n_splits contiguous segments; each segment's
    (m, l, accT) partial stats come from a vmapped :func:`etap_partial_xla`
    (XLA parallelizes across segments — the same shape the Pallas phase-1
    kernel gives the TPU grid), merged by :func:`combine_partials`. A fully
    masked segment carries m = NEG_INF and drops out of the merge with
    weight exp(NEG_INF - m*) = 0."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    mode = softmax_state.resolve(rescale)
    if length is None:
        length = jnp.full((BG,), S, jnp.int32)
    if n_splits <= 1:
        return etap_decode_xla(q, k, v, length, scale=scale, block=block,
                               rescale=mode)
    from repro.kernels.etap.schedule import split_geometry
    # effective count: short contexts degrade to fewer non-empty splits
    block, n_splits, npb, padded_s = split_geometry(S, block, n_splits)
    if n_splits <= 1:
        return etap_decode_xla(q, k, v, length, scale=scale, block=block,
                               rescale=mode)
    seg = npb * block
    pad = padded_s - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    ks = jnp.moveaxis(k.reshape(BG, n_splits, seg, Dk), 1, 0)  # [n,BG,seg,Dk]
    vs = jnp.moveaxis(v.reshape(BG, n_splits, seg, Dv), 1, 0)
    starts = jnp.arange(n_splits, dtype=jnp.int32)[:, None] * seg
    seg_len = jnp.clip(length[None, :] - starts, 0, seg)       # [n,BG]
    m, l, accT = jax.vmap(
        lambda kk, vv, ll: etap_partial_xla(q, kk, vv, ll, scale=scale,
                                            block=block,
                                            rescale=mode))(ks, vs, seg_len)
    return combine_partials(m, l, accT, rescale=mode).astype(v.dtype)


def seq_sharded_decode(q, cache, new_row, pos, *, dv: int, scale: float,
                       axis: str = "model", block: int = 512,
                       rescale: str | None = None):
    """Sequence-sharded MLA decode (shard_map over `axis`).

    The MLA latent cache [B, S, L] has NO head dimension, so tensor
    parallelism cannot shard it — instead S is sharded over the model axis;
    each shard (1) writes the new latent row if it owns position `pos`,
    (2) runs the ETAP partial loop over its local S/n slice, and (3) shards
    exchange the tiny (m, l, accT) stats (flash-decode-style cross-device
    softmax combine). q: [B,H,L]; cache: [B,S,L] S-sharded; new_row: [B,L].
    Returns (O [B,H,dv], updated cache)."""
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    mode = softmax_state.resolve(rescale)

    # shard ids ride in as an axis-sharded operand instead of
    # jax.lax.axis_index: the latter lowers to partition-id, which SPMD
    # can't place inside a partially-auto manual region on older JAX.
    shard_ids = jnp.arange(mesh.shape[axis], dtype=jnp.int32)

    def local(q, cache, new_row, pos, sid):
        idx = sid[0]
        S_local = cache.shape[1]
        start = idx * S_local
        slot = jnp.clip(pos - start, 0, S_local - 1)
        owns = (pos >= start) & (pos < start + S_local)
        # single-row conditional write: non-owners rewrite their old row —
        # O(row) traffic, never an O(cache) select copy (§Perf D4)
        old = jax.lax.dynamic_slice_in_dim(cache, slot, 1, axis=1)
        row = jnp.where(owns, new_row[:, None, :], old)
        cache = jax.lax.dynamic_update_slice_in_dim(cache, row, slot, axis=1)
        length = jnp.clip(pos + 1 - start, 0, S_local)
        B = q.shape[0]
        m, l, accT = etap_partial_xla(
            q, cache, cache[..., :dv],
            jnp.full((B,), length, jnp.int32), scale=scale, block=block,
            vary_axis=(axis,), rescale=mode)
        # combine via weighted psum: one all-reduce of [B,dv,H] instead of
        # an n-fold all-gather (§Perf iteration D3 — 8x less wire traffic);
        # the weights come from THE merge definition (softmax_state), the
        # Σ is the all-reduce.
        m_g = jax.lax.pmax(m, axis)                           # [B,H]
        w = softmax_state.merge_weights(m, m_g, mode=mode)
        l_g = jax.lax.psum(l * w, axis)
        acc_g = jax.lax.psum(accT * w[:, None, :], axis)      # [B,dv,H]
        oT = acc_g / l_g[:, None, :]
        return jnp.swapaxes(oT, 1, 2).astype(cache.dtype), cache

    # manual ONLY over the model axis: batch (pod/data) sharding of q/cache
    # keeps propagating automatically outside the manual region.
    return jax.shard_map(
        local, mesh=mesh, axis_names={axis},
        in_specs=(P(), P(None, axis, None), P(), P(), P(axis)),
        out_specs=(P(), P(None, axis, None)),
        check_vma=False,
    )(q, cache, new_row, pos, shard_ids)


def decode_attention(q, k, v, length=None, *, spec=None, **legacy):
    """Unified decode attention entry point, driven by one
    :class:`repro.core.attn_spec.AttnSpec`.

    spec.mode: "etap" (the paper) or "standard" (FlashMLA-like baseline).
    spec.use_kernels: dispatch to the Pallas implementations (None: on a
    TPU backend only, compiled; off a TPU they run in interpret mode —
    see attn_spec.resolve_backend).
    spec.kv_splits: KV-split count for the two-phase split-KV pipeline.
    None → auto via kernels.etap.schedule (resolves to 1 at short contexts /
    large batches, i.e. exactly the old single-pass behaviour) on both the
    kernel and XLA "etap" paths; 1 → force single-pass. The "standard" XLA
    loop streams serially regardless — it is the deliberately unsplit
    baseline.
    spec.rescale: softmax-state rescale mode, None → the process default
    (``--rescale`` / REPRO_RESCALE) — resolved here, before any jit cache.
    Legacy keywords (scale=..., mode=..., n_splits=...) shim through
    :func:`attn_spec.coerce` with a DeprecationWarning.
    """
    spec = attn_spec.coerce(spec, legacy, where="decode_attention")
    if spec.use_kernels:
        from repro.kernels.etap import ops as etap_ops
        from repro.kernels.flash_decode import ops as fd_ops
        if spec.mode == "etap":
            return etap_ops.etap_decode_splitkv(q, k, v, length, spec=spec)
        return fd_ops.flash_decode_splitkv(q, k, v, length, spec=spec)
    scale, block = spec.scale, spec.block
    rescale = softmax_state.resolve(spec.rescale)
    n_splits = spec.kv_splits
    if spec.mode == "etap":
        if n_splits is None:
            from repro.kernels.etap.schedule import plan_splits
            n_splits = plan_splits(q.shape[0], k.shape[1], q.shape[1],
                                   v.shape[2], block=block).n_splits
        if n_splits > 1:
            return etap_decode_splitkv_xla(q, k, v, length, scale=scale,
                                           block=block,
                                           n_splits=int(n_splits),
                                           rescale=rescale)
    fn = etap_decode_xla if spec.mode == "etap" else standard_decode_xla
    return fn(q, k, v, length, scale=scale, block=block, rescale=rescale)


# ------------------------------------------------------------------- paged
def _gather_kv(k_pool, v_pool, table, dv: int, k_sz=None, v_sz=None):
    """Materialize the dense (k, v) view of a paged cache: the fallback
    route for paths without a native paged kernel.  v_pool None → MLA-fused
    (V = first `dv` gathered columns).  k_sz/v_sz: per-row (scale, zp)
    pools for quantized code pools (DESIGN.md §11) — the gathered codes
    are dequantized densely here, the XLA twin of the kernels' in-register
    expand (same affine: runtime.paged_cache.dequantize_rows)."""
    from repro.runtime.paged_cache import dequantize_rows, gather_blocks
    k = gather_blocks(k_pool, table)
    if k_sz is not None:
        k = dequantize_rows(k, gather_blocks(k_sz, table))
    if v_pool is not None:
        v = gather_blocks(v_pool, table)
        if v_sz is not None:
            v = dequantize_rows(v, gather_blocks(v_sz, table))
    else:
        v = k[..., :dv]
    return k, v


def etap_decode_paged_xla(q, k_pool, v_pool, table, lengths, *,
                          scale: float, dv: int = 0, k_sz=None, v_sz=None,
                          rescale: str | None = None):
    """Paged ETAP decode in pure XLA: gather the pool rows through the
    block table into the dense layout, then run the blockwise loop with
    block == page — so at block-aligned lengths it is bit-identical to the
    paged Pallas kernel AND to the dense path at equal block size.  XLA
    materializes the gather (one cache-sized copy); the Pallas paged
    kernels avoid it by dereferencing the table inside the grid.
    With v_pool None, V = gathered k_pool[..., :dv] (MLA-fused).
    k_sz/v_sz: (scale, zp) pools for quantized code pools."""
    k, v = _gather_kv(k_pool, v_pool, table, dv, k_sz, v_sz)
    if k_sz is not None:
        q = q.astype(jnp.float32)          # match the dequantized fp32 rows
    return etap_decode_xla(q, k, v, lengths, scale=scale,
                           block=k_pool.shape[1], rescale=rescale)


def decode_attention_paged(q, k_pool, v_pool, table, lengths, *,
                           spec=None, dv: int = 0, k_sz=None, v_sz=None,
                           **legacy):
    """Paged decode attention entry point (the `cache_layout="paged"`
    analogue of :func:`decode_attention`), driven by one AttnSpec.

    q: [B,H,Dk]; pools: [N,page,D*]; table: [B,max_blocks]; lengths: [B].
    v_pool None → MLA-fused (V = first `dv` pool columns, one HBM stream).
    k_sz/v_sz: (scale, zp) pools when the pools hold int8/fp8 codes — the
    kernel path dequants in registers, the XLA path after the gather.
    spec.kv_splits: None = auto via the block-granular paged scheduler; the
    "standard" baseline runs on the gathered dense layout (it exists for
    comparison, not serving)."""
    spec = attn_spec.coerce(spec, legacy, where="decode_attention_paged")
    if spec.use_kernels and spec.mode == "etap":
        from repro.kernels.etap import ops as etap_ops
        if v_pool is None:
            return etap_ops.etap_decode_mla_paged_splitkv(
                q, k_pool, dv, table, lengths, spec=spec, kv_sz=k_sz)
        return etap_ops.etap_decode_paged_splitkv(
            q, k_pool, v_pool, table, lengths, spec=spec,
            k_sz=k_sz, v_sz=v_sz)
    scale = spec.scale
    rescale = softmax_state.resolve(spec.rescale)
    n_splits = spec.kv_splits
    if spec.mode == "etap":
        page = k_pool.shape[1]
        if n_splits is None:
            from repro.kernels.etap.schedule import plan_splits_paged
            n_splits = plan_splits_paged(
                q.shape[0], table.shape[1], page, q.shape[1],
                v_pool.shape[2] if v_pool is not None else dv).n_splits
        if n_splits > 1:
            k, v = _gather_kv(k_pool, v_pool, table, dv, k_sz, v_sz)
            return etap_decode_splitkv_xla(q, k, v, lengths, scale=scale,
                                           block=page,
                                           n_splits=int(n_splits),
                                           rescale=rescale)
        return etap_decode_paged_xla(q, k_pool, v_pool, table, lengths,
                                     scale=scale, dv=dv, k_sz=k_sz,
                                     v_sz=v_sz, rescale=rescale)
    k, v = _gather_kv(k_pool, v_pool, table, dv, k_sz, v_sz)
    if spec.use_kernels:
        from repro.kernels.flash_decode import ops as fd_ops
        return fd_ops.flash_decode_splitkv(
            q, k, v, lengths, spec=spec.replace(block=k_pool.shape[1]))
    return standard_decode_xla(q, k, v, lengths, scale=scale,
                               block=k_pool.shape[1], rescale=rescale)


def etap_prefill_xla(q, k, v, start, *, scale: float, block: int = 512,
                     rescale: str | None = None, sel=None):
    """Chunked ETAP prefill, online softmax over KV blocks (the XLA twin of
    the paged Pallas prefill kernel — DESIGN.md §9).

    q: [B, Cq, H, Dk] chunk queries at absolute positions start[b] + c;
    k: [B, S, Dk]; v: [B, S, Dv] (the chunk's own rows already written into
    k/v by the caller); start: [B].  The Cq*H query tile rides the N side of
    every GEMM while KV blocks stay on M, with a causal mask per column:
    key position p is live for chunk row c iff p <= start + c.
    Implemented as the linear-chain special case of :func:`etap_verify_xla`
    (qpos = start + row index) — the two are bitwise identical there.
    sel: [B, Cq, S] bool, the rows each query row may attend besides the
    causal mask (DeepSeek Sparse Attention's top-k, DESIGN.md §17).
    Returns [B, Cq, H, Dv]."""
    Cq = q.shape[1]
    qpos = start[:, None] + jnp.arange(Cq, dtype=jnp.int32)[None, :]
    return etap_verify_xla(q, k, v, qpos, scale=scale, block=block,
                           rescale=rescale, sel=sel)


def etap_verify_xla(q, k, v, qpos, *, scale: float, block: int = 512,
                    rescale: str | None = None, sel=None):
    """Draft-verify ETAP attention: the chunked-prefill loop with an
    EXPLICIT per-query-row causal horizon (DESIGN.md §14).

    q: [B, Cq, H, Dk] — the Cq draft rows under verification; qpos: [B, Cq]
    absolute key position row c may attend up to (inclusive; its own pool
    row included).  For a linear draft chain qpos = start[:, None] +
    arange(Cq), which makes this function bit-identical to
    :func:`etap_prefill_xla` — verification IS a chunked prefill.  An
    explicit vector rather than start + row index is the tree hook:
    sibling draft rows share a start but not a mask.
    Returns [B, Cq, H, Dv]."""
    B, Cq, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    CH = Cq * H
    block = min(block, S)
    nb = _blocks(S, block)
    mode = softmax_state.resolve(rescale)

    qT = jnp.swapaxes(q.reshape(B, CH, Dk), 1, 2).astype(jnp.float32)
    # column c*H + h of the transposed score tile is query row c
    qpos = jnp.repeat(qpos.astype(jnp.int32), H, axis=1)       # [B, CH]
    if sel is not None:
        sel = jnp.repeat(sel, H, axis=1)                       # [B, CH, S]

    def step(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        sT = jnp.einsum("bkd,bdh->bkh", kj, qT.astype(k.dtype),
                        preferred_element_type=jnp.float32) * scale
        kpos = j * block + jnp.arange(block, dtype=jnp.int32)  # [block]
        valid = kpos[None, :, None] <= qpos[:, None, :]        # [B,block,CH]
        if sel is not None:
            valid = valid & jnp.swapaxes(jax.lax.dynamic_slice_in_dim(
                sel, j * block, block, axis=2), 1, 2)
        sT = jnp.where(valid, sT, NEG_INF)
        return softmax_state.update(
            carry, sT,
            lambda pT: jnp.einsum("bkv,bkh->bvh", vj, pT.astype(v.dtype),
                                  preferred_element_type=jnp.float32),
            axis=1, mode=mode, expand=lambda c: c[:, None, :])

    state = jax.lax.fori_loop(
        0, nb, step, softmax_state.init((B, CH), (B, Dv, CH)))
    oT = softmax_state.finalize(state, expand=lambda l: l[:, None, :])
    return jnp.swapaxes(oT, 1, 2).reshape(B, Cq, H, Dv).astype(v.dtype)


def prefill_attention_paged(q, k_pool, v_pool, table, start, *, spec=None,
                            dv: int = 0, k_sz=None, v_sz=None, sel=None,
                            **legacy):
    """Chunked paged prefill attention entry point (the prefill analogue of
    :func:`decode_attention_paged`).

    q: [B,Cq,H,Dk]; pools: [N,page,D*]; table: [B,max_blocks]; start: [B]
    tokens in the pool before the chunk — the chunk's latent/KV rows must
    already be appended (runtime.paged_cache.append_chunk), so the kernels
    stream ONE pool source for both the past context and the live chunk.
    `start` is indifferent to HOW the preceding rows got into the pool:
    written by this request's earlier chunks, or mapped wholesale from a
    prefix-cache hit (DESIGN.md §10) — a prefill that resumes at a nonzero
    offset over donor-computed blocks is the same computation as one that
    resumes over its own, which is why prefix skipping needs no kernel
    changes.  v_pool None → MLA-fused (V = first `dv` pool columns).
    `spec.mode` is accepted for parity with decode but ignored; both modes
    share the transposed loop here — prefill tiles are never thin on M.
    sel: [B,Cq,max_blocks*page] bool top-k mask of DeepSeek Sparse
    Attention (MLA-fused bf16 pools only), applied on top of the causal
    mask: the work stays dense, the softmax sees the selected rows only."""
    spec = attn_spec.coerce(spec, legacy, where="prefill_attention_paged")
    if spec.use_kernels:
        from repro.kernels.etap import ops as etap_ops
        if v_pool is None:
            return etap_ops.etap_prefill_mla_paged(
                q, k_pool, dv, table, start, spec=spec, kv_sz=k_sz, sel=sel)
        return etap_ops.etap_prefill_paged(
            q, k_pool, v_pool, table, start, spec=spec,
            k_sz=k_sz, v_sz=v_sz)
    k, v = _gather_kv(k_pool, v_pool, table, dv, k_sz, v_sz)
    if k_sz is not None:
        q = q.astype(jnp.float32)          # match the dequantized fp32 rows
    return etap_prefill_xla(q, k, v, start, scale=spec.scale,
                            block=k_pool.shape[1],
                            rescale=softmax_state.resolve(spec.rescale),
                            sel=sel)


def verify_attention_paged(q, k_pool, v_pool, table, start, qpos, *,
                           spec=None, dv: int = 0, k_sz=None, v_sz=None,
                           **legacy):
    """Speculative-decode verification attention over the paged pool
    (DESIGN.md §14) — the scoring half of draft-then-verify.

    Shaped exactly like :func:`prefill_attention_paged`: the k draft rows
    must already be appended to the pool (append_chunk / append_chunk_quant)
    and `start` [B] is the pre-chunk length, so ONE pool stream covers the
    committed context and the live draft rows.  The only difference is the
    causal mask: the explicit per-row horizon `qpos` [B, Cq] replaces
    start + row index.  A linear chain (qpos = start[:, None] + arange(Cq))
    is bitwise identical to the prefill entry — verification IS a chunked
    prefill — while tree-shaped drafts feed sibling rows with equal start
    but disjoint horizons.  v_pool None → MLA-fused (V = first `dv` pool
    columns); k_sz/v_sz → quantized code pools, dequantized in registers on
    the kernel path and after the gather on the XLA path."""
    spec = attn_spec.coerce(spec, legacy, where="verify_attention_paged")
    if spec.use_kernels:
        from repro.kernels.etap import ops as etap_ops
        if v_pool is None:
            return etap_ops.etap_verify_mla_paged(
                q, k_pool, dv, table, start, qpos, spec=spec, kv_sz=k_sz)
        return etap_ops.etap_verify_paged(
            q, k_pool, v_pool, table, start, qpos, spec=spec,
            k_sz=k_sz, v_sz=v_sz)
    k, v = _gather_kv(k_pool, v_pool, table, dv, k_sz, v_sz)
    if k_sz is not None:
        q = q.astype(jnp.float32)          # match the dequantized fp32 rows
    return etap_verify_xla(q, k, v, qpos, scale=spec.scale,
                           block=k_pool.shape[1],
                           rescale=softmax_state.resolve(spec.rescale))


# ----------------------------------------- DeepSeek Sparse Attention
def index_scores_xla(q, w, k_pool, table, limit):
    """XLA twin of the index-score kernel (kernels/etap/dsa.py), also its
    oracle.  q: [B,R,heads,hd]; w: [B,R,heads] fp32; k_pool: [N,page,hd];
    table: [B,max_blocks]; limit: [B] rows scored.  Returns
    I = sum_h w_h · ReLU(q_h · k) as [B,R,max_blocks*page] fp32, -inf at
    rows >= limit."""
    from repro.runtime.paged_cache import gather_blocks
    k = gather_blocks(k_pool, table)                           # [B,L,hd]
    s = jnp.einsum("brhd,bld->brhl", q.astype(k.dtype), k,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("brhl,brh->brl", jnp.maximum(s, 0.0),
                   w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    pos = jnp.arange(s.shape[-1], dtype=jnp.int32)
    return jnp.where(pos[None, None, :] < limit[:, None, None], s, -jnp.inf)


def index_scores_paged(q, w, k_pool, table, limit, *, spec, decode: bool):
    """Index scores through the kernel (named by ``decode``: the decode
    step's or a prefill chunk's) or its XLA twin.  A row all of whose
    ReLUs are zero scores zero: -0.0 is made +0.0, so that such ties
    order by position alone."""
    if spec.use_kernels:
        from repro.kernels.etap import ops as etap_ops
        fn = (etap_ops.dsa_index_decode_paged if decode
              else etap_ops.dsa_index_prefill_paged)
        s = fn(q, w, k_pool, table, limit, spec=spec)
    else:
        s = index_scores_xla(q, w, k_pool, table, limit)
    return jnp.where(s == 0, 0.0, s)


def _ordered_bits(x):
    """fp32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _largest_below(pred, bits: int, shape):
    """The largest unsigned t < 2**bits with ``pred(t)`` true, for a
    predicate that holds from 0 up to some t and not above: one bit at a
    time from the top."""
    def step(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1),
                                  jnp.uint32(bits - 1) - i.astype(jnp.uint32))
        return jnp.where(pred(cand), cand, t)
    return jax.lax.fori_loop(0, bits, step, jnp.zeros(shape, jnp.uint32))


def top_k_mask(scores, k: int):
    """Mask of exactly the k entries along the last axis that
    ``jax.lax.top_k(scores, k)`` picks: the largest, ties to the lower
    index.  No sort: the k-th largest value is found bit by bit (32
    counting passes over the row), then, among the entries equal to it,
    the last position to keep (a pass per bit of the row length)."""
    u = _ordered_bits(scores)
    rows = scores.shape[:-1]
    kth = _largest_below(
        lambda t: jnp.sum(u >= t[..., None], axis=-1) >= k, 32, rows)
    above = u > kth[..., None]
    tie = u == kth[..., None]
    need = k - jnp.sum(above, axis=-1)                    # >= 1 tied entries
    pos = jnp.arange(scores.shape[-1], dtype=jnp.uint32)
    last = _largest_below(
        lambda p: jnp.sum(tie & (pos < p[..., None]), axis=-1) < need,
        max(1, int(scores.shape[-1]).bit_length()), rows)
    return above | (tie & (pos <= last[..., None]))


@functools.partial(jax.jit, static_argnames=("topk",))
def dsa_select(scores, pool, table, lengths, *, topk: int):
    """The selection of a sparse decode step: each sequence's top-k rows by
    index score (exact, ``jax.lax.top_k``), gathered through the block
    table.  scores: [B,L] (-inf past each length); pool: [N,page,latent];
    lengths: [B] rows per sequence.  Returns (rows [B,k,latent], valid
    [B], idx [B,k]) with k = min(topk, L): the first min(lengths, k) rows
    are the selected ones, best first, and idx their positions."""
    k = min(topk, scores.shape[-1])
    _, idx = jax.lax.top_k(scores, k)                          # [B,k]
    page = pool.shape[1]
    blk = jnp.take_along_axis(table, idx // page, axis=1)
    return (pool[blk, idx % page],
            jnp.minimum(lengths, k).astype(jnp.int32), idx)


def dsa_decode_paged(q, pool, k_pool, q_idx, w, table, lengths, *, spec,
                     dv: int, topk: int):
    """DeepSeek Sparse Attention decode (DESIGN.md §17): the lightning
    indexer scores every row of each sequence against its paged index
    keys, the top-k rows are gathered, and ETAP decode runs over them
    alone — no pass over the whole latent pool.

    q: [B,H,latent] absorbed queries; pool: [N,page,latent]; k_pool:
    [N,page,hd] index keys; q_idx: [B,heads,hd]; w: [B,heads]; lengths:
    [B] rows per sequence (the new token's included).  Returns [B,H,dv]."""
    spec = attn_spec.coerce(spec, {}, where="dsa_decode_paged")
    scores = index_scores_paged(q_idx[:, None], w[:, None], k_pool, table,
                                lengths, spec=spec, decode=True)[:, 0]
    rows, valid, idx = dsa_select(scores, pool, table, lengths, topk=topk)
    if spec.use_kernels:
        from repro.kernels.etap import ops as etap_ops
        return etap_ops.dsa_sparse_decode(q, rows, dv, valid, spec=spec), idx
    block = min(spec.block, rows.shape[1])
    pad = (-rows.shape[1]) % block
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    return etap_decode_xla(q, rows, rows[..., :dv], valid, scale=spec.scale,
                           block=block,
                           rescale=softmax_state.resolve(spec.rescale)), idx


def dsa_prefill_select(q_idx, w, k_pool, table, start, *, spec, topk: int):
    """Per-row top-k masks of a prefill chunk: q_idx [B,C,heads,hd], w
    [B,C,heads] at positions start + c.  Row c keeps the min(topk, causal
    rows) causal rows with the largest index scores, ties to the earlier
    row, as the decode step's top-k picks them.
    Returns [B,C,max_blocks*page] bool."""
    spec = attn_spec.coerce(spec, {}, where="dsa_prefill_select")
    C = q_idx.shape[1]
    scores = index_scores_paged(q_idx, w, k_pool, table, start + C,
                                spec=spec, decode=False)       # [B,C,L]
    qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    causal = (jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, None, :]
              <= qpos[:, :, None])
    scores = jnp.where(causal, scores, -jnp.inf)
    return causal & top_k_mask(scores, min(topk, scores.shape[-1]))


def gqa_partial_xla(q, k, v, length, *, scale: float, block: int = 512,
                    vary_axis=None, rescale: str | None = None):
    """ETAP partial stats for GQA in the native [B,S,K,hd] cache layout.
    q: [B,K,G,hd]. Returns (m, l, accT): [B,K,G], [B,K,G], [B,K,Dv,G]."""
    B, K, G, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[3]
    block = min(block, S)
    nb = _blocks(S, block)
    mode = softmax_state.resolve(rescale)
    qf = q.astype(jnp.float32)

    def step(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        sT = jnp.einsum("bskd,bkgd->bksg", kj, qf.astype(k.dtype),
                        preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]
        sT = jnp.where(valid[:, None, :, None], sT, NEG_INF)
        return softmax_state.update(
            carry, sT,
            lambda pT: jnp.einsum("bskv,bksg->bkvg", vj, pT.astype(v.dtype),
                                  preferred_element_type=jnp.float32),
            axis=2, mode=mode, expand=lambda c: c[:, :, None, :])

    init = softmax_state.init((B, K, G), (B, K, Dv, G))
    if vary_axis is not None:
        init = jax.tree.map(
            lambda a: jax.lax.pcast(a, vary_axis, to="varying"), init)
    return jax.lax.fori_loop(0, nb, step, init)


def seq_sharded_gqa_decode(q, k_cache, v_cache, new_k, new_v, pos, *,
                           scale: float, axis: str = "model",
                           block: int = 512, rescale: str | None = None):
    """Sequence-sharded GQA decode (shard_map over `axis`) — the generic-
    attention analogue of :func:`seq_sharded_decode`: each shard owns an
    S/n slice of the [B,S,K,hd] cache, writes the new KV row if `pos` falls
    in its range, runs the ETAP partial loop locally, and shards exchange
    only the (m, l, accT) stats. q: [B,K,G,hd]; new_k/new_v: [B,K,hd].
    Returns (O [B,K*G,Dv], new k_cache, new v_cache)."""
    from jax.sharding import PartitionSpec as P
    mesh = current_mesh()
    mode = softmax_state.resolve(rescale)
    B, K, G, Dk = q.shape
    Dv = v_cache.shape[3]

    shard_ids = jnp.arange(mesh.shape[axis], dtype=jnp.int32)  # see above

    def local(q, kc, vc, nk, nv, pos, sid):
        idx = sid[0]
        S_local = kc.shape[1]
        start = idx * S_local
        slot = jnp.clip(pos - start, 0, S_local - 1)
        owns = (pos >= start) & (pos < start + S_local)
        # single-row conditional writes (see seq_sharded_decode — §Perf D4)
        def write(c, new):
            old = jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
            row = jnp.where(owns, new[:, None], old)
            return jax.lax.dynamic_update_slice_in_dim(c, row, slot, axis=1)
        kc = write(kc, nk)
        vc = write(vc, nv)
        length = jnp.full((B,), jnp.clip(pos + 1 - start, 0, S_local),
                          jnp.int32)
        m, l, accT = gqa_partial_xla(q, kc, vc, length, scale=scale,
                                     block=block, vary_axis=(axis,),
                                     rescale=mode)
        # weighted-psum combine (one all-reduce, no n-fold gather — §Perf D3)
        m_g = jax.lax.pmax(m, axis)                    # [B,K,G]
        w = softmax_state.merge_weights(m, m_g, mode=mode)
        l_g = jax.lax.psum(l * w, axis)
        acc_g = jax.lax.psum(accT * w[:, :, None, :], axis)
        o = jnp.swapaxes(acc_g / l_g[:, :, None, :], 2, 3)   # [B,K,G,Dv]
        return o.reshape(B, K * G, Dv).astype(v_cache.dtype), kc, vc

    cspec = P(None, axis, None, None)
    return jax.shard_map(
        local, mesh=mesh, axis_names={axis},
        in_specs=(P(), cspec, cspec, P(), P(), P(), P(axis)),
        out_specs=(P(), cspec, cspec),
        check_vma=False,
    )(q, k_cache, v_cache, new_k, new_v, pos, shard_ids)


def gqa_decode_xla(q, k, v, length, *, spec=None, **legacy):
    """GQA decode attention operating NATIVELY on the [B,S,K,hd] cache layout
    (no transpose/copy of the multi-GiB cache — it is streamed in place with
    dynamic_slice). q: [B,K,G,hd]; k,v: [B,S,K,hd*]; length: [B].
    Returns [B, K*G, Dv]. spec.mode "etap" keeps the KV block on the long
    GEMM dim with per-(k,g)-column softmax stats; "standard" is the thin-M
    baseline.  Legacy keywords shim through attn_spec.coerce."""
    spec = attn_spec.coerce(spec, legacy, where="gqa_decode_xla")
    scale, mode = spec.scale, spec.mode
    B, K, G, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[3]
    block = min(spec.block, S)
    nb = _blocks(S, block)
    rs = softmax_state.resolve(spec.rescale)
    qf = q.astype(jnp.float32)

    def step_etap(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        # Sᵀ: KV block on the long dim, per-(k,g) column statistics
        sT = jnp.einsum("bskd,bkgd->bksg", kj, qf.astype(k.dtype),
                        preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]    # [B, block]
        sT = jnp.where(valid[:, None, :, None], sT, NEG_INF)
        return softmax_state.update(
            carry, sT,                            # stats [B,K,G]
            lambda pT: jnp.einsum("bskv,bksg->bkvg", vj, pT.astype(v.dtype),
                                  preferred_element_type=jnp.float32),
            axis=2, mode=rs, expand=lambda c: c[:, :, None, :])

    def step_std(j, carry):
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        s = jnp.einsum("bkgd,bskd->bkgs", qf.astype(k.dtype), kj,
                       preferred_element_type=jnp.float32) * scale
        pos = j * block + jnp.arange(block, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        return softmax_state.update(
            carry, s,                             # acc [B,K,G,Dv]
            lambda p: jnp.einsum("bkgs,bskv->bkgv", p.astype(v.dtype), vj,
                                 preferred_element_type=jnp.float32),
            axis=3, mode=rs, expand=lambda c: c[..., None])

    if mode == "etap":
        state = jax.lax.fori_loop(
            0, nb, step_etap, softmax_state.init((B, K, G), (B, K, Dv, G)))
        oT = softmax_state.finalize(state, expand=lambda l: l[:, :, None, :])
        o = jnp.swapaxes(oT, 2, 3)                            # [B,K,G,Dv]
    else:
        state = jax.lax.fori_loop(
            0, nb, step_std, softmax_state.init((B, K, G), (B, K, G, Dv)))
        o = softmax_state.finalize(state, expand=lambda l: l[..., None])
    return o.reshape(B, K * G, Dv).astype(v.dtype)


def gqa_to_grouped(q, k, v):
    """[B,H,D],[B,S,K,D],[B,S,K,Dv] -> grouped (BG=B*K) form + a restorer."""
    B, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D).reshape(B * K, G, D)
    kg = jnp.swapaxes(k, 1, 2).reshape(B * K, k.shape[1], k.shape[3])
    vg = jnp.swapaxes(v, 1, 2).reshape(B * K, v.shape[1], v.shape[3])

    def restore(o):                                           # [B*K, G, Dv]
        return o.reshape(B, K, G, o.shape[-1]).reshape(B, H, o.shape[-1])
    return qg, kg, vg, restore

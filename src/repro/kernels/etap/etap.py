"""ETAP decode-attention Pallas TPU kernel (paper Algorithm 1, TPU-adapted).

Per (batch-group, KV-block) grid step the kernel computes the *transposed*
attention update:

    Sᵀ_j = K_j Qᵀ            [B_kv, H]   (KV block length on the GEMM M dim)
    m, ℓ  : per-COLUMN online-softmax stats            [1, H]
    Accᵀ += Vᵀ_j Pᵀ_j         [Dv, H]    (contraction over the long KV axis)
    epilogue: O = (Accᵀ / ℓ)ᵀ  [H, Dv]   (the single final transpose)

The HBM→VMEM producer pipeline of the paper's warpgroup1 is Pallas grid
pipelining (serial KV grid dimension, double-buffered by Mosaic); see
DESIGN.md §2. The MLA-fused variant streams the 576-wide latent cache once
and reuses its first Dv columns as V — one HBM stream for both GEMMs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import softmax_state

NEG_INF = softmax_state.NEG_INF


def _dequant(blk, sz_ref):
    """Expand a quantized KV block in registers: codes [block, D] +
    per-row (scale, zp) [block, 2] -> fp32 rows.  Delegates to THE dequant
    definition (runtime.paged_cache.dequantize_rows — the loaded blk/sz
    are plain jnp values inside the Pallas body, so the runtime affine
    traces directly): kernel, XLA gather twin, and oracle literally share
    one function and cannot drift.  sz_ref None is the fp passthrough."""
    if sz_ref is None:
        return blk
    from repro.runtime.paged_cache import dequantize_rows
    return dequantize_rows(blk, sz_ref[0].astype(jnp.float32))


def _etap_body(length_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *, scale: float, block: int,
               nb: int, fused_dv: int, rescale: str,
               k_sz_ref=None, v_sz_ref=None):
    """Shared kernel body. With fused_dv > 0, v_ref is None and V is the
    first fused_dv columns of the K (latent) block.  With k_sz_ref /
    v_sz_ref set, the K/V blocks arrive as int8/fp8 codes and are
    dequantized in registers before the dot (DESIGN.md §11); the softmax
    statistics and the accumulator are fp32 either way.  The online-softmax
    state lives in the (m, l, acc) scratch refs and is advanced exclusively
    through :mod:`repro.kernels.softmax_state` (``rescale`` selects the
    mul/amla recurrence)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    k_blk = _dequant(k_ref[0], k_sz_ref)               # [block, Dk]
    q = q_ref[0]                                       # [H, Dk]
    if k_sz_ref is not None:
        q = q.astype(jnp.float32)                      # match dequanted K
    # Sᵀ = K·Qᵀ — context block on M, heads on N (no M padding waste).
    sT = jax.lax.dot_general(
        k_blk, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [block, H]

    length = length_ref[pl.program_id(0)]
    pos = j * block + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
    sT = jnp.where(pos < length, sT, NEG_INF)

    v_blk = k_blk[:, :fused_dv] if fused_dv else _dequant(v_ref[0], v_sz_ref)
    # Accᵀ += Vᵀ·Pᵀ — contraction over the KV block.
    m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
        (m_ref[...], l_ref[...], acc_ref[...]), sT,
        lambda p: jax.lax.dot_general(
            v_blk, p, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),       # [Dv, H]
        axis=0, mode=rescale)

    @pl.when(j == nb - 1)
    def _epilogue():
        o_ref[0] = softmax_state.finalize(
            (None, l_ref[...], acc_ref[...])).T.astype(o_ref.dtype)


def _body_fused(length_ref, q_ref, k_ref, o_ref, acc, m, l, **kw):
    _etap_body(length_ref, q_ref, k_ref, None, o_ref, acc, m, l, **kw)


# The paged bodies are the SAME math: the block table only changes *which*
# pool block the BlockSpec index map DMAs in per grid step (scalar-prefetch
# gather — see _paged_call); logical positions / masking are untouched, so
# paged output is bit-identical to the dense kernel at equal block size.
def _paged_body(length_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                acc, m, l, **kw):
    _etap_body(length_ref, q_ref, k_ref, v_ref, o_ref, acc, m, l, **kw)


def _paged_body_fused(length_ref, table_ref, q_ref, k_ref, o_ref,
                      acc, m, l, **kw):
    _etap_body(length_ref, q_ref, k_ref, None, o_ref, acc, m, l, **kw)


# Quantized paged bodies: the sz pool rides as one more gathered operand
# (same table deref as its code pool), dequant happens in _etap_body.
def _paged_body_quant(length_ref, table_ref, q_ref, k_ref, k_sz_ref,
                      v_ref, v_sz_ref, o_ref, acc, m, l, **kw):
    _etap_body(length_ref, q_ref, k_ref, v_ref, o_ref, acc, m, l,
               k_sz_ref=k_sz_ref, v_sz_ref=v_sz_ref, **kw)


def _paged_body_quant_fused(length_ref, table_ref, q_ref, k_ref, k_sz_ref,
                            o_ref, acc, m, l, **kw):
    _etap_body(length_ref, q_ref, k_ref, None, o_ref, acc, m, l,
               k_sz_ref=k_sz_ref, **kw)


def _call(q, k, v, length, *, scale, block, interpret, fused_dv, rescale):
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = fused_dv or v.shape[2]
    block = min(block, S)
    assert S % block == 0, (S, block)
    nb = S // block

    in_specs = [
        pl.BlockSpec((1, H, Dk), lambda b, j, *_: (b, 0, 0)),      # q
        pl.BlockSpec((1, block, Dk), lambda b, j, *_: (b, j, 0)),  # k (or latent)
    ]
    operands = [q, k]
    if not fused_dv:
        in_specs.append(pl.BlockSpec((1, block, Dv), lambda b, j, *_: (b, j, 0)))
        operands.append(v)

    kw = dict(scale=scale, block=block, nb=nb, fused_dv=fused_dv,
              rescale=softmax_state.resolve(rescale))
    body = functools.partial(_body_fused if fused_dv else _etap_body, **kw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BG, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, Dv), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Dv, H), jnp.float32),                  # Accᵀ
            pltpu.VMEM((1, H), jnp.float32),                   # m
            pltpu.VMEM((1, H), jnp.float32),                   # ℓ
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BG, H, Dv), (v if v is not None else k).dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), *operands)


def etap_decode_pallas(q, k, v, length, *, scale: float, block: int = 512,
                       interpret: bool = True, rescale: str | None = None):
    """Generic (separate-V) ETAP decode kernel."""
    return _call(q, k, v, length, scale=scale, block=block,
                 interpret=interpret, fused_dv=0, rescale=rescale)


def etap_decode_mla_pallas(q, kv, dv: int, length, *, scale: float,
                           block: int = 512, interpret: bool = True,
                           rescale: str | None = None):
    """MLA-fused ETAP: single latent stream, V = kv[..., :dv]."""
    return _call(q, kv, None, length, scale=scale, block=block,
                 interpret=interpret, fused_dv=dv, rescale=rescale)


# ----------------------------------------------------------- paged variants
def _pool_spec(page, D):
    """BlockSpec gathering pool block ``table[b, j]`` per grid step."""
    return pl.BlockSpec((1, page, D), lambda b, j, lens, tab: (tab[b, j], 0, 0))


def _paged_call(q, pool, v_pool, table, lengths, *, scale, interpret,
                fused_dv, rescale, k_sz=None, v_sz=None):
    """Paged single-pass ETAP: KV lives in a block pool [N, page, D]; the
    block table [B, max_blocks] rides in as a scalar-prefetch operand and
    the K/V BlockSpec index maps dereference it, so each grid step DMAs
    pool block ``table[b, j]`` — the gather happens inside the grid, never
    as a materialized dense copy.  k_sz/v_sz: per-row (scale, zp) pools
    [N, page, 2] for quantized code pools (DESIGN.md §11) — they gather
    through the same table and are expanded in registers."""
    B, H, Dk = q.shape
    page = pool.shape[1]
    nb = table.shape[1]
    Dv = fused_dv or v_pool.shape[2]
    quant = k_sz is not None

    in_specs = [
        pl.BlockSpec((1, H, Dk), lambda b, j, *_: (b, 0, 0)),            # q
        _pool_spec(page, Dk),                                            # pool
    ]
    operands = [q, pool]
    if quant:
        in_specs.append(_pool_spec(page, 2))
        operands.append(k_sz)
    if not fused_dv:
        in_specs.append(_pool_spec(page, Dv))
        operands.append(v_pool)
        if quant:
            in_specs.append(_pool_spec(page, 2))
            operands.append(v_sz)

    kw = dict(scale=scale, block=page, nb=nb, fused_dv=fused_dv,
              rescale=softmax_state.resolve(rescale))
    if quant:
        body = functools.partial(
            _paged_body_quant_fused if fused_dv else _paged_body_quant, **kw)
    else:
        body = functools.partial(
            _paged_body_fused if fused_dv else _paged_body, **kw)

    out_dtype = (q.dtype if quant
                 else (v_pool if v_pool is not None else pool).dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, Dv), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Dv, H), jnp.float32),                  # Accᵀ
            pltpu.VMEM((1, H), jnp.float32),                   # m
            pltpu.VMEM((1, H), jnp.float32),                   # ℓ
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.astype(jnp.int32), *operands)


def etap_decode_paged_pallas(q, k_pool, v_pool, table, lengths, *,
                             scale: float, interpret: bool = True,
                             k_sz=None, v_sz=None,
                             rescale: str | None = None):
    """Paged (separate-V) ETAP decode kernel. q: [B,H,Dk]; pools
    [N,page,D*]; table: [B,max_blocks]; lengths: [B]. Returns [B,H,Dv].
    k_sz/v_sz: (scale, zp) pools when k_pool/v_pool hold int8/fp8 codes."""
    return _paged_call(q, k_pool, v_pool, table, lengths, scale=scale,
                       interpret=interpret, fused_dv=0, rescale=rescale,
                       k_sz=k_sz, v_sz=v_sz)


def etap_decode_mla_paged_pallas(q, kv_pool, dv: int, table, lengths, *,
                                 scale: float, interpret: bool = True,
                                 kv_sz=None, rescale: str | None = None):
    """Paged MLA-fused ETAP: single latent pool, V = pool[..., :dv].
    kv_sz: (scale, zp) pool when kv_pool holds int8/fp8 codes — V is
    sliced AFTER the affine, so one sz pair serves both operands."""
    return _paged_call(q, kv_pool, None, table, lengths, scale=scale,
                       interpret=interpret, fused_dv=dv, rescale=rescale,
                       k_sz=kv_sz)


# ---------------------------------------------------------- chunked prefill
def _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, scale: float, page: int,
                       nb: int, heads: int, cols: int, fused_dv: int,
                       rescale: str, k_sz_ref=None, v_sz_ref=None,
                       qpos_ref=None, sel_ref=None):
    """Chunked paged ETAP prefill (DESIGN.md §9): the decode body with the
    single query row widened to a [Cq, H] tile, flattened to CH = Cq*H
    online-softmax columns.  The KV walk streams the sequence's pool blocks
    (chunk rows included — the caller appends the chunk before attending),
    and the mask is CAUSAL per column: key position j*page+r is live for
    column c iff  r_pos <= start + c // H  (query c//H is the chunk-local
    row, start the tokens already in the pool).  Blocks past the chunk end
    are fully masked and drop out with weight exp(-inf - m) = 0; block 0 of
    the walk always holds position 0, so no column is ever all-masked.

    The grid is (b, column tile t, KV block j): each step holds ``cols`` of
    the CH columns (see :func:`_col_tiling`).  Columns never interact —
    every one carries its own (m, ℓ, Accᵀ) column — so tiling them is the
    same math, whatever the tile.

    ``qpos_ref`` is the VERIFY generalization (DESIGN.md §14): an explicit
    per-column absolute query position [1, cols] replaces the derived
    ``start + c // H`` — the draft-verification mask where each scored
    chunk row attends to exactly the pool rows at or before its own
    position, independent of how the chunk maps onto the pool tail.

    ``sel_ref`` (DeepSeek Sparse Attention, DESIGN.md §17): the tile's
    query rows' top-k masks over this KV block, [cols // heads, page]; a
    0/1 matmul against the row-of-column indicator spreads each row's mask
    over its ``heads`` columns."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    k_blk = _dequant(k_ref[0], k_sz_ref)               # [page, Dk]
    q = q_ref[0]                                       # [cols, Dk]
    if k_sz_ref is not None:
        q = q.astype(jnp.float32)                      # match dequanted K
    # Sᵀ = K·Qᵀ — pool block rows on M, the Cq*H query tile on N.
    sT = jax.lax.dot_general(
        k_blk, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [page, cols]

    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
    if qpos_ref is None:
        start = start_ref[pl.program_id(0)]
        col = pl.program_id(1) * cols + jax.lax.broadcasted_iota(
            jnp.int32, sT.shape, 1)
        qpos = start + col // heads
    else:
        qpos = qpos_ref[0]                             # [1, cols] per-column
    live = kpos <= qpos                                # causal chunk-vs-pool
    if sel_ref is not None:
        shape = (cols // heads, cols)
        spread = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) // heads
                  == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        picked = jax.lax.dot_general(
            sel_ref[0, 0], spread.astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [page, cols]
        live = live & (picked > 0.5)
    sT = jnp.where(live, sT, NEG_INF)

    v_blk = k_blk[:, :fused_dv] if fused_dv else _dequant(v_ref[0], v_sz_ref)
    m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
        (m_ref[...], l_ref[...], acc_ref[...]), sT,
        lambda p: jax.lax.dot_general(
            v_blk, p, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),       # [Dv, cols]
        axis=0, mode=rescale)

    @pl.when(j == nb - 1)
    def _epilogue():
        o_ref[0] = softmax_state.finalize(
            (None, l_ref[...], acc_ref[...])).T.astype(o_ref.dtype)


def _prefill_body_fused(start_ref, table_ref, q_ref, k_ref, o_ref,
                        acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, None, o_ref,
                       acc, m, l, **kw)


def _prefill_body_sel(start_ref, table_ref, q_ref, sel_ref, k_ref, o_ref,
                      acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, None, o_ref,
                       acc, m, l, sel_ref=sel_ref, **kw)


def _prefill_body_quant(start_ref, table_ref, q_ref, k_ref, k_sz_ref,
                        v_ref, v_sz_ref, o_ref, acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                       acc, m, l, k_sz_ref=k_sz_ref, v_sz_ref=v_sz_ref, **kw)


def _prefill_body_quant_fused(start_ref, table_ref, q_ref, k_ref, k_sz_ref,
                              o_ref, acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, None, o_ref,
                       acc, m, l, k_sz_ref=k_sz_ref, **kw)


# Verify bodies (DESIGN.md §14): the prefill bodies with the per-column
# query-position operand riding directly after q — same math, explicit mask.
def _verify_body(start_ref, table_ref, q_ref, qpos_ref, k_ref, v_ref, o_ref,
                 acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                       acc, m, l, qpos_ref=qpos_ref, **kw)


def _verify_body_fused(start_ref, table_ref, q_ref, qpos_ref, k_ref, o_ref,
                       acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, None, o_ref,
                       acc, m, l, qpos_ref=qpos_ref, **kw)


def _verify_body_quant(start_ref, table_ref, q_ref, qpos_ref, k_ref,
                       k_sz_ref, v_ref, v_sz_ref, o_ref, acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                       acc, m, l, qpos_ref=qpos_ref, k_sz_ref=k_sz_ref,
                       v_sz_ref=v_sz_ref, **kw)


def _verify_body_quant_fused(start_ref, table_ref, q_ref, qpos_ref, k_ref,
                             k_sz_ref, o_ref, acc, m, l, **kw):
    _etap_prefill_body(start_ref, table_ref, q_ref, k_ref, None, o_ref,
                       acc, m, l, qpos_ref=qpos_ref, k_sz_ref=k_sz_ref, **kw)


# Widest slice of the Cq*H query columns one prefill grid step holds.  The
# Accᵀ scratch and the q/o blocks all grow with it: at latent 576 / Dv 512
# in bf16, 4096 columns overflow v5e's scoped VMEM and 2048 fit.  A
# multiple of 128: Mosaic needs that of the qpos row's block when it is
# narrower than the array.
PREFILL_COL_TILE = 2048


def _col_tiling(CH: int):
    """(cols per grid step, CH padded to a whole number of tiles)."""
    if CH <= PREFILL_COL_TILE:
        return CH, CH
    return PREFILL_COL_TILE, -(-CH // PREFILL_COL_TILE) * PREFILL_COL_TILE


def _sel_operand(sel, rows: int, page: int):
    """[B, Cq, nb*page] bool top-k mask -> the kernel's [B, nb, rows, page]
    fp32 planes (rows >= Cq, padded with live rows): one (rows-of-the-tile,
    page) block per grid step."""
    B, Cq, S = sel.shape
    m = sel.astype(jnp.float32)
    if rows != Cq:
        m = jnp.pad(m, ((0, 0), (0, rows - Cq), (0, 0)), constant_values=1.0)
    return m.reshape(B, rows, S // page, page).transpose(0, 2, 1, 3)


def _prefill_call(q, pool, v_pool, table, start, *, heads, scale, interpret,
                  fused_dv, rescale, k_sz=None, v_sz=None, qpos=None,
                  sel=None):
    B, CH, Dk = q.shape
    page = pool.shape[1]
    nb = table.shape[1]
    Dv = fused_dv or v_pool.shape[2]
    quant = k_sz is not None
    cols, CHp = _col_tiling(CH)
    if CHp != CH:
        # pad columns are zero queries (horizon 0 when explicit — key 0 is
        # always live, so none is all-masked) and are sliced off below
        q = jnp.pad(q, ((0, 0), (0, CHp - CH), (0, 0)))
        if qpos is not None:
            qpos = jnp.pad(qpos, ((0, 0), (0, CHp - CH)))

    def pool_spec(D):
        return pl.BlockSpec((1, page, D),
                            lambda b, t, j, lens, tab: (tab[b, j], 0, 0))

    in_specs = [
        pl.BlockSpec((1, cols, Dk), lambda b, t, j, *_: (b, t, 0)),      # q
    ]
    operands = [q]
    if qpos is not None:
        # per-column absolute query positions as a [B, 1, CHp] int32 plane:
        # a (1, cols) row per step is a VMEM vector compare (no SMEM vector
        # indexing), and the unit axis keeps the block's last two dims
        # legal for Mosaic at any batch
        in_specs.append(pl.BlockSpec((1, 1, cols),
                                     lambda b, t, j, *_: (b, 0, t)))
        operands.append(qpos.astype(jnp.int32)[:, None, :])
    if sel is not None:
        # DeepSeek Sparse Attention: fused bf16 pools, no explicit qpos
        assert fused_dv and not quant and qpos is None and cols % heads == 0
        in_specs.append(pl.BlockSpec((1, 1, cols // heads, page),
                                     lambda b, t, j, *_: (b, j, t, 0)))
        operands.append(_sel_operand(sel, CHp // heads, page))
    in_specs.append(pool_spec(Dk))                                       # pool
    operands.append(pool)
    if quant:
        in_specs.append(pool_spec(2))
        operands.append(k_sz)
    if not fused_dv:
        in_specs.append(pool_spec(Dv))
        operands.append(v_pool)
        if quant:
            in_specs.append(pool_spec(2))
            operands.append(v_sz)

    kw = dict(scale=scale, page=page, nb=nb, heads=heads, cols=cols,
              fused_dv=fused_dv, rescale=softmax_state.resolve(rescale))
    if sel is not None:
        body = functools.partial(_prefill_body_sel, **kw)
    elif qpos is not None:
        body = functools.partial(
            (_verify_body_quant_fused if fused_dv else _verify_body_quant)
            if quant else
            (_verify_body_fused if fused_dv else _verify_body), **kw)
    elif quant:
        body = functools.partial(
            _prefill_body_quant_fused if fused_dv else _prefill_body_quant,
            **kw)
    else:
        body = functools.partial(
            _prefill_body_fused if fused_dv else _etap_prefill_body, **kw)

    out_dtype = (q.dtype if quant
                 else (v_pool if v_pool is not None else pool).dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, CHp // cols, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, cols, Dv), lambda b, t, j, *_: (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((Dv, cols), jnp.float32),               # Accᵀ
            pltpu.VMEM((1, cols), jnp.float32),                # m
            pltpu.VMEM((1, cols), jnp.float32),                # ℓ
        ],
    )
    o = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, CHp, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(start.astype(jnp.int32), table.astype(jnp.int32), *operands)
    return o[:, :CH]


def etap_prefill_paged_pallas(q, k_pool, v_pool, table, start, *,
                              scale: float, interpret: bool = True,
                              k_sz=None, v_sz=None,
                              rescale: str | None = None):
    """Paged (separate-V) chunked ETAP prefill. q: [B,Cq,H,Dk]; pools
    [N,page,D*]; table [B,max_blocks]; start [B] = tokens already in the
    pool BEFORE this chunk (the chunk's own rows must already be appended).
    Returns [B,Cq,H,Dv].  k_sz/v_sz: (scale, zp) pools for quantized
    code pools."""
    B, Cq, H, Dk = q.shape
    o = _prefill_call(q.reshape(B, Cq * H, Dk), k_pool, v_pool, table, start,
                      heads=H, scale=scale, interpret=interpret, fused_dv=0,
                      rescale=rescale, k_sz=k_sz, v_sz=v_sz)
    return o.reshape(B, Cq, H, o.shape[-1])


def etap_prefill_mla_paged_pallas(q, kv_pool, dv: int, table, start, *,
                                  scale: float, interpret: bool = True,
                                  kv_sz=None, rescale: str | None = None,
                                  sel=None):
    """Paged MLA-fused chunked prefill: single latent pool, V = pool[..., :dv].
    sel: [B,Cq,max_blocks*page] bool top-k mask (DeepSeek Sparse Attention)
    applied on top of the causal one."""
    B, Cq, H, Dk = q.shape
    o = _prefill_call(q.reshape(B, Cq * H, Dk), kv_pool, None, table, start,
                      heads=H, scale=scale, interpret=interpret, fused_dv=dv,
                      rescale=rescale, k_sz=kv_sz, sel=sel)
    return o.reshape(B, Cq, H, dv)


# -------------------------------------------------- draft verification
def _expand_qpos(qpos, H):
    """[B, Cq] absolute query positions -> the [B, Cq*H] per-column row the
    kernel compares against (column c*H + h belongs to query row c)."""
    return jnp.repeat(qpos.astype(jnp.int32), H, axis=1)


def etap_verify_paged_pallas(q, k_pool, v_pool, table, start, qpos, *,
                             scale: float, interpret: bool = True,
                             k_sz=None, v_sz=None,
                             rescale: str | None = None):
    """Paged (separate-V) draft-verify attention (DESIGN.md §14): the
    chunked-prefill kernel with an EXPLICIT per-query position operand.
    q: [B,Cq,H,Dk] — the Cq drafted rows (already appended to the pool);
    qpos: [B,Cq] int32 absolute positions — row c attends to pool rows at
    positions <= qpos[b, c].  A linear draft chain with
    ``qpos = start + arange(Cq)`` is bit-identical to the prefill kernel;
    the explicit operand is what tree-shaped position layouts plug into."""
    B, Cq, H, Dk = q.shape
    o = _prefill_call(q.reshape(B, Cq * H, Dk), k_pool, v_pool, table, start,
                      heads=H, scale=scale, interpret=interpret, fused_dv=0,
                      rescale=rescale, k_sz=k_sz, v_sz=v_sz,
                      qpos=_expand_qpos(qpos, H))
    return o.reshape(B, Cq, H, o.shape[-1])


def etap_verify_mla_paged_pallas(q, kv_pool, dv: int, table, start, qpos, *,
                                 scale: float, interpret: bool = True,
                                 kv_sz=None, rescale: str | None = None):
    """Paged MLA-fused draft-verify: single latent pool, V = pool[..., :dv],
    explicit per-query positions (see :func:`etap_verify_paged_pallas`)."""
    B, Cq, H, Dk = q.shape
    o = _prefill_call(q.reshape(B, Cq * H, Dk), kv_pool, None, table, start,
                      heads=H, scale=scale, interpret=interpret, fused_dv=dv,
                      rescale=rescale, k_sz=kv_sz, qpos=_expand_qpos(qpos, H))
    return o.reshape(B, Cq, H, dv)


# ------------------------------------------------------- split-KV (phase 1)
def _partial_out_specs(H, Dv):
    """Phase-1 output blocks, one (b, split) pair per grid step.  m and ℓ
    carry a unit axis, [B, n_splits, 1, H] blocked (1, 1, 1, H): Mosaic
    needs a block's last two dims to be (8, 128)-divisible or to equal the
    array's, and a (1, H) row of an [n_splits, H] plane is neither.  The
    wrappers drop the unit axis, so callers still see [B, n_splits, H]."""
    return [
        pl.BlockSpec((1, 1, 1, H), lambda b, s, j, *_: (b, s, 0, 0)),    # m
        pl.BlockSpec((1, 1, 1, H), lambda b, s, j, *_: (b, s, 0, 0)),    # ℓ
        pl.BlockSpec((1, 1, Dv, H), lambda b, s, j, *_: (b, s, 0, 0)),   # Accᵀ
    ]


def _etap_partial_body(length_ref, q_ref, k_ref, v_ref,
                       m_out_ref, l_out_ref, acc_out_ref,
                       acc_ref, m_ref, l_ref, *, scale: float, block: int,
                       npb: int, fused_dv: int, rescale: str,
                       k_sz_ref=None, v_sz_ref=None):
    """Split-KV partial: same transposed update as :func:`_etap_body`, on a
    3-D ``(BG, n_splits, nb_per_split)`` grid.  Each (b, split) pair owns a
    contiguous KV segment and emits raw ``(m, ℓ, Accᵀ)`` stats instead of O —
    the combine kernel (phase 2, ``combine.py``) merges them in the stat
    domain, so splits are fully independent and can run on different cores."""
    s = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    k_blk = _dequant(k_ref[0], k_sz_ref)               # [block, Dk]
    q = q_ref[0]                                       # [H, Dk]
    if k_sz_ref is not None:
        q = q.astype(jnp.float32)                      # match dequanted K
    sT = jax.lax.dot_general(
        k_blk, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [block, H]

    length = length_ref[pl.program_id(0)]
    pos = (s * npb + j) * block + jax.lax.broadcasted_iota(
        jnp.int32, sT.shape, 0)
    sT = jnp.where(pos < length, sT, NEG_INF)

    v_blk = k_blk[:, :fused_dv] if fused_dv else _dequant(v_ref[0], v_sz_ref)
    m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
        (m_ref[...], l_ref[...], acc_ref[...]), sT,
        lambda p: jax.lax.dot_general(
            v_blk, p, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),       # [Dv, H]
        axis=0, mode=rescale)

    @pl.when(j == npb - 1)
    def _emit():
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]
        acc_out_ref[0, 0] = acc_ref[...]


def _partial_body_fused(length_ref, q_ref, k_ref, m_out, l_out, acc_out,
                        acc, m, l, **kw):
    _etap_partial_body(length_ref, q_ref, k_ref, None, m_out, l_out, acc_out,
                       acc, m, l, **kw)


def etap_partial_pallas(q, k, v, length, *, scale: float, block: int,
                        n_splits: int, interpret: bool = True,
                        fused_dv: int = 0, rescale: str | None = None):
    """Phase-1 split-KV ETAP kernel.

    q: [BG,H,Dk]; k: [BG,S,Dk] with S == n_splits * nb_per_split * block
    (callers pad — the tail is masked via `length`).  Returns fp32 partial
    stats (m, l, accT): [BG,n_splits,H], [BG,n_splits,H], [BG,n_splits,Dv,H].
    With fused_dv > 0, v is ignored and V = k[..., :fused_dv] (MLA latent)."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = fused_dv or v.shape[2]
    assert S % (n_splits * block) == 0, (S, n_splits, block)
    npb = S // (n_splits * block)

    in_specs = [
        pl.BlockSpec((1, H, Dk), lambda b, s, j, *_: (b, 0, 0)),       # q
        pl.BlockSpec((1, block, Dk),
                     lambda b, s, j, *_, npb=npb: (b, s * npb + j, 0)),  # k
    ]
    operands = [q, k]
    if not fused_dv:
        in_specs.append(pl.BlockSpec(
            (1, block, Dv), lambda b, s, j, *_, npb=npb: (b, s * npb + j, 0)))
        operands.append(v)

    kw = dict(scale=scale, block=block, npb=npb, fused_dv=fused_dv,
              rescale=softmax_state.resolve(rescale))
    body = functools.partial(
        _partial_body_fused if fused_dv else _etap_partial_body, **kw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BG, n_splits, npb),
        in_specs=in_specs,
        out_specs=_partial_out_specs(H, Dv),
        scratch_shapes=[
            pltpu.VMEM((Dv, H), jnp.float32),                  # Accᵀ
            pltpu.VMEM((1, H), jnp.float32),                   # m
            pltpu.VMEM((1, H), jnp.float32),                   # ℓ
        ],
    )
    m, l, accT = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BG, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((BG, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((BG, n_splits, Dv, H), jnp.float32),
        ],
        # splits are independent work items — only the within-split KV walk
        # is a sequential accumulation.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), *operands)
    return m[:, :, 0], l[:, :, 0], accT


def _paged_partial_body(length_ref, table_ref, q_ref, k_ref, v_ref,
                        m_out, l_out, acc_out, acc, m, l, **kw):
    _etap_partial_body(length_ref, q_ref, k_ref, v_ref, m_out, l_out,
                       acc_out, acc, m, l, **kw)


def _paged_partial_body_fused(length_ref, table_ref, q_ref, k_ref,
                              m_out, l_out, acc_out, acc, m, l, **kw):
    _etap_partial_body(length_ref, q_ref, k_ref, None, m_out, l_out,
                       acc_out, acc, m, l, **kw)


def _paged_partial_body_quant(length_ref, table_ref, q_ref, k_ref, k_sz_ref,
                              v_ref, v_sz_ref, m_out, l_out, acc_out,
                              acc, m, l, **kw):
    _etap_partial_body(length_ref, q_ref, k_ref, v_ref, m_out, l_out,
                       acc_out, acc, m, l, k_sz_ref=k_sz_ref,
                       v_sz_ref=v_sz_ref, **kw)


def _paged_partial_body_quant_fused(length_ref, table_ref, q_ref, k_ref,
                                    k_sz_ref, m_out, l_out, acc_out,
                                    acc, m, l, **kw):
    _etap_partial_body(length_ref, q_ref, k_ref, None, m_out, l_out,
                       acc_out, acc, m, l, k_sz_ref=k_sz_ref, **kw)


def etap_paged_partial_pallas(q, k_pool, v_pool, table, lengths, *,
                              scale: float, n_splits: int,
                              interpret: bool = True, fused_dv: int = 0,
                              k_sz=None, v_sz=None,
                              rescale: str | None = None):
    """Phase-1 split-KV over a PAGED cache: same (b, split, block-walk) grid
    as :func:`etap_partial_pallas`, but each grid step's KV block is pool
    block ``table[b, s*npb + j]`` (scalar-prefetch gather).  Splits are cut
    at page granularity — callers pad the table to an ``n_splits * npb``
    width with null blocks (masked via `lengths`), so ``n_splits`` composes
    with paging with no repacking.  Returns fp32 (m, l, accT) stats.
    k_sz/v_sz: (scale, zp) pools for quantized code pools — the partial
    stats stay fp32 regardless of the storage layout."""
    B, H, Dk = q.shape
    page = k_pool.shape[1]
    nb = table.shape[1]
    Dv = fused_dv or v_pool.shape[2]
    assert nb % n_splits == 0, (nb, n_splits)
    npb = nb // n_splits
    quant = k_sz is not None

    def split_pool_spec(D):
        return pl.BlockSpec(
            (1, page, D),
            lambda b, s, j, lens, tab, npb=npb: (tab[b, s * npb + j], 0, 0))

    in_specs = [
        pl.BlockSpec((1, H, Dk), lambda b, s, j, *_: (b, 0, 0)),         # q
        split_pool_spec(Dk),                                             # pool
    ]
    operands = [q, k_pool]
    if quant:
        in_specs.append(split_pool_spec(2))
        operands.append(k_sz)
    if not fused_dv:
        in_specs.append(split_pool_spec(Dv))
        operands.append(v_pool)
        if quant:
            in_specs.append(split_pool_spec(2))
            operands.append(v_sz)

    kw = dict(scale=scale, block=page, npb=npb, fused_dv=fused_dv,
              rescale=softmax_state.resolve(rescale))
    if quant:
        body = functools.partial(
            _paged_partial_body_quant_fused if fused_dv
            else _paged_partial_body_quant, **kw)
    else:
        body = functools.partial(
            _paged_partial_body_fused if fused_dv else _paged_partial_body,
            **kw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_splits, npb),
        in_specs=in_specs,
        out_specs=_partial_out_specs(H, Dv),
        scratch_shapes=[
            pltpu.VMEM((Dv, H), jnp.float32),                  # Accᵀ
            pltpu.VMEM((1, H), jnp.float32),                   # m
            pltpu.VMEM((1, H), jnp.float32),                   # ℓ
        ],
    )
    m, l, accT = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, n_splits, Dv, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.astype(jnp.int32), *operands)
    return m[:, :, 0], l[:, :, 0], accT

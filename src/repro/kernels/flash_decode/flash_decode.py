"""Baseline flash decode-attention kernel — the *untransposed* pipeline
(FlashMLA-without-ETAP). Identical tiling/pipelining to the ETAP kernel so
the two differ ONLY in computation orientation:

    S_j = Q Kᵀ_j     [H, B_kv]    (thin head dim on the GEMM M dimension)
    m, ℓ : per-ROW online stats   [H, 1]
    Acc += P_j V_j   [H, Dv]

This is the comparison target for the paper's Figure-1 claim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import softmax_state

NEG_INF = softmax_state.NEG_INF


def _body(length_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
          *, scale: float, block: int, nb: int, rescale: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    q = q_ref[0]                                        # [H, Dk]
    k_blk = k_ref[0]                                    # [block, Dk]
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [H, block]

    length = length_ref[pl.program_id(0)]
    pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    v_blk = v_ref[0]
    m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
        (m_ref[...], l_ref[...], acc_ref[...]), s,
        lambda p: jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),        # [H, Dv]
        axis=1, mode=rescale)

    @pl.when(j == nb - 1)
    def _epilogue():
        o_ref[0] = softmax_state.finalize(
            (None, l_ref[...], acc_ref[...])).astype(o_ref.dtype)


def flash_decode_pallas(q, k, v, length, *, scale: float, block: int = 512,
                        interpret: bool = True, rescale: str | None = None):
    """q: [BG,H,Dk]; k: [BG,S,Dk]; v: [BG,S,Dv]; length: [BG]. -> [BG,H,Dv]."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    block = min(block, S)
    assert S % block == 0
    nb = S // block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BG, nb),
        in_specs=[
            pl.BlockSpec((1, H, Dk), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, block, Dk), lambda b, j, *_: (b, j, 0)),
            pl.BlockSpec((1, block, Dv), lambda b, j, *_: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dv), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dv), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_body, scale=scale, block=block, nb=nb,
                          rescale=softmax_state.resolve(rescale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BG, H, Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), q, k, v)


# ------------------------------------------------------- split-KV (phase 1)
def _partial_body(length_ref, q_ref, k_ref, v_ref,
                  m_out_ref, l_out_ref, acc_out_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, block: int,
                  npb: int, rescale: str):
    """Split-KV partial for the untransposed baseline: 3-D
    ``(BG, n_splits, nb_per_split)`` grid emitting per-split (m, ℓ, Acc)
    stats in the standard [H, ·] orientation (merged by
    ``kernels.etap.combine`` with transposed=False)."""
    s = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    q = q_ref[0]                                        # [H, Dk]
    k_blk = k_ref[0]                                    # [block, Dk]
    sc = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [H, block]

    length = length_ref[pl.program_id(0)]
    pos = (s * npb + j) * block + jax.lax.broadcasted_iota(
        jnp.int32, sc.shape, 1)
    sc = jnp.where(pos < length, sc, NEG_INF)

    v_blk = v_ref[0]
    m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
        (m_ref[...], l_ref[...], acc_ref[...]), sc,
        lambda p: jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),        # [H, Dv]
        axis=1, mode=rescale)

    @pl.when(j == npb - 1)
    def _emit():
        m_out_ref[0, 0] = m_ref[...].T                  # [1, H]
        l_out_ref[0, 0] = l_ref[...].T
        acc_out_ref[0, 0] = acc_ref[...]


def flash_decode_partial_pallas(q, k, v, length, *, scale: float, block: int,
                                n_splits: int, interpret: bool = True,
                                rescale: str | None = None):
    """Phase-1 stats for the baseline kernel. S == n·npb·block (pre-padded).
    Returns (m, l, acc): [BG,n,H], [BG,n,H], [BG,n,H,Dv] (fp32)."""
    BG, H, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    assert S % (n_splits * block) == 0, (S, n_splits, block)
    npb = S // (n_splits * block)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BG, n_splits, npb),
        in_specs=[
            pl.BlockSpec((1, H, Dk), lambda b, s, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, block, Dk),
                         lambda b, s, j, *_, npb=npb: (b, s * npb + j, 0)),
            pl.BlockSpec((1, block, Dv),
                         lambda b, s, j, *_, npb=npb: (b, s * npb + j, 0)),
        ],
        # m/ℓ carry a unit axis so their (1, H) block rows equal the
        # array's last two dims (Mosaic's tiling rule); dropped below
        out_specs=[
            pl.BlockSpec((1, 1, 1, H), lambda b, s, j, *_: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, H), lambda b, s, j, *_: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, H, Dv), lambda b, s, j, *_: (b, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, Dv), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_partial_body, scale=scale, block=block, npb=npb,
                          rescale=softmax_state.resolve(rescale)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BG, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((BG, n_splits, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((BG, n_splits, H, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), q, k, v)
    return m[:, :, 0], l[:, :, 0], acc

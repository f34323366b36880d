"""Blockwise causal flash-attention prefill kernel (beyond-paper).

The XLA train/prefill path (models.attention.causal_attention) pays masked
upper-triangle FLOPs; this kernel skips fully-masked KV blocks via pl.when
AND pins the index_map to min(i, j) so skipped steps do not stream KV from
HBM. GQA is handled by mapping the kv-head block index to bh // group.

Grid: (B*H, S/Bq, S/Bkv) — kv fastest (serial, online softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import softmax_state

NEG_INF = softmax_state.NEG_INF


def _body(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
          *, scale: float, bq: int, bkv: int, nkv: int, rescale: str):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        softmax_state.init_refs(m_ref, l_ref, acc_ref)

    # causal block skip in POSITION terms (bq and bkv may differ: kv block j
    # is needed iff its first row j·bkv precedes the q block's last row)
    @pl.when(j * bkv <= i * bq + bq - 1)
    def _compute():
        q = q_ref[0]                                   # [bq, D]
        k = k_ref[0]                                   # [bkv, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # in-block causal mask (only the diagonal block is partially masked,
        # but the branchless form costs nothing on the VPU)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

        v_blk = v_ref[0]
        m_ref[...], l_ref[...], acc_ref[...] = softmax_state.update(
            (m_ref[...], l_ref[...], acc_ref[...]), s,
            lambda p: jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
            axis=1, mode=rescale)

    @pl.when(j == nkv - 1)
    def _epilogue():
        o_ref[0] = softmax_state.finalize(
            (None, l_ref[...], acc_ref[...])).astype(o_ref.dtype)


def flash_prefill_pallas(q, k, v, *, scale: float, bq: int = 256,
                         bkv: int = 256, interpret: bool = True,
                         rescale: str | None = None):
    """q: [B,S,H,D]; k,v: [B,S,K,D*] (GQA) -> [B,S,H,Dv]."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    Dv = v.shape[-1]
    bq, bkv = min(bq, S), min(bkv, S)
    assert S % bq == 0 and S % bkv == 0
    nq, nkv = S // bq, S // bkv

    qh = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    kh = jnp.swapaxes(k, 1, 2).reshape(B * K, S, D)
    vh = jnp.swapaxes(v, 1, 2).reshape(B * K, S, Dv)

    out = pl.pallas_call(
        functools.partial(_body, scale=scale, bq=bq, bkv=bkv, nkv=nkv,
                          rescale=softmax_state.resolve(rescale)),
        grid=(B * H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
            # skipped steps re-point at the last needed kv block: no extra
            # HBM traffic (last needed j for q block i = (i·bq+bq-1)//bkv)
            pl.BlockSpec((1, bkv, D),
                         lambda bh, i, j, G=G: (
                             bh // G,
                             jnp.minimum(j, (i * bq + bq - 1) // bkv), 0)),
            pl.BlockSpec((1, bkv, Dv),
                         lambda bh, i, j, G=G: (
                             bh // G,
                             jnp.minimum(j, (i * bq + bq - 1) // bkv), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.swapaxes(out.reshape(B, H, S, Dv), 1, 2)

"""Split-KV phase 2: merge per-split (m, ℓ, Acc) partial stats into O.

The merge math — global max, per-split weights, weighted ℓ/Acc sums — is
:func:`repro.kernels.softmax_state.merge_splits`, the ONE stat-domain merge
definition shared with the sequence-sharded XLA combine in
``core/etap.py`` (they were two hand-synced copies before DESIGN.md §13).
``rescale`` must match the mode the partials were produced under: the stats
live in that mode's domain (natural-log max vs power-of-two bias).

A fully-masked split carries (m = -1e30, ℓ = 0, Acc = garbage·0-weight);
its weight underflows to exactly 0, so it drops out of the merge without a
branch.  With a single split the weights are exp(0) = 1 (amla: 2^0 = 1)
and the merge reduces bitwise to the single-pass epilogue ``(Acc / ℓ)ᵀ`` —
split-KV with n_splits=1 is bit-compatible with the one-phase kernels.

fp32 end-to-end until the final epilogue cast (DESIGN.md §6/§11): the
merge weights are exponentials of stat DIFFERENCES — computing them in a
half dtype collapses nearby splits' weights and loses the paper's RMSE
edge.  The upcast guard lives inside ``merge_splits`` itself (the PR 5
bf16-stat bug can't be reintroduced from a call site); only o_ref.dtype
may be narrow.

Two backends: a Pallas kernel (one grid step per batch-group row) and an
XLA fallback tracing the same merge under plain jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import softmax_state


def _combine_body(m_ref, l_ref, acc_ref, o_ref, *, transposed: bool,
                  rescale: str):
    if transposed:                                     # ETAP: acc [n, Dv, H]
        _, l_g, acc_g = softmax_state.merge_splits(
            m_ref[0], l_ref[0], acc_ref[0], axis=0, mode=rescale,
            expand=lambda w: w[:, None, :])
        o_ref[0] = (acc_g / l_g).T.astype(o_ref.dtype)     # [H, Dv]
    else:                                              # standard: [n, H, Dv]
        _, l_g, acc_g = softmax_state.merge_splits(
            m_ref[0], l_ref[0], acc_ref[0], axis=0, mode=rescale,
            expand=lambda w: w[:, :, None])
        o_ref[0] = (acc_g / l_g[:, None]).astype(o_ref.dtype)


def combine_splits_pallas(m, l, acc, *, transposed: bool, out_dtype,
                          interpret: bool = True,
                          rescale: str | None = None):
    """m, l: [BG,n,H]; acc: [BG,n,Dv,H] (transposed) or [BG,n,H,Dv].
    Returns O: [BG,H,Dv]."""
    BG, n, H = m.shape
    Dv = acc.shape[2] if transposed else acc.shape[3]
    acc_blk = (1, n, Dv, H) if transposed else (1, n, H, Dv)
    return pl.pallas_call(
        functools.partial(_combine_body, transposed=transposed,
                          rescale=softmax_state.resolve(rescale)),
        grid=(BG,),
        in_specs=[
            pl.BlockSpec((1, n, H), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, n, H), lambda b: (b, 0, 0)),
            pl.BlockSpec(acc_blk, lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dv), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BG, H, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(m, l, acc)


def combine_splits_xla(m, l, acc, *, transposed: bool, out_dtype,
                       rescale: str | None = None):
    """XLA fallback (identical math; used when the combine kernel is not
    worth a launch, e.g. under vmap or on non-TPU backends)."""
    mode = softmax_state.resolve(rescale)
    if transposed:                                     # acc [BG,n,Dv,H]
        _, l_g, acc_g = softmax_state.merge_splits(
            m, l, acc, axis=1, mode=mode,
            expand=lambda w: w[:, :, None, :])
        return jnp.moveaxis(acc_g / l_g[:, None, :], 1, 2).astype(out_dtype)
    _, l_g, acc_g = softmax_state.merge_splits(       # acc [BG,n,H,Dv]
        m, l, acc, axis=1, mode=mode,
        expand=lambda w: w[..., None])
    return (acc_g / l_g[..., None]).astype(out_dtype)


def combine_splits(m, l, acc, *, transposed: bool, out_dtype,
                   combine: str = "pallas", interpret: bool = True,
                   rescale: str | None = None):
    """Dispatch phase-2 merge: combine = "pallas" | "xla"."""
    if combine == "xla":
        return combine_splits_xla(m, l, acc, transposed=transposed,
                                  out_dtype=out_dtype, rescale=rescale)
    return combine_splits_pallas(m, l, acc, transposed=transposed,
                                 out_dtype=out_dtype, interpret=interpret,
                                 rescale=rescale)

"""Pallas TPU selective-scan (Mamba-1) forward kernel.

The XLA associative-scan path moves O(log L) full-size state temporaries
through HBM (EXPERIMENTS.md §Perf-M); the CUDA mamba kernel keeps the
recurrence state in SRAM. The TPU-native translation: grid over
(batch, d_inner blocks, sequence chunks) with the chunk dimension serial —
the [d_blk, N] state lives in a VMEM scratch across chunk steps, dA/dBx/C
stream through VMEM once, y is written once. HBM traffic = one read of the
inputs + one write of y (the paper-style "state never leaves fast memory"
property, adapted from SRAM/warp terms to VMEM/grid terms).

    h_t = dA_t ⊙ h_{t-1} + dBx_t          dA, dBx: [B, L, D, N]
    y_t = Σ_n C_{t,n} · h_{t,d,n}         C: [B, L, N] → y: [B, L, D]

The in-chunk loop is a jax.lax.fori_loop over time INSIDE the kernel body —
steps are [d_blk, N] VPU ops with no HBM round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(dA_ref, dBx_ref, c_ref, y_ref, hout_ref, h_ref, *, chunk: int,
          nchunks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dA = dA_ref[0]          # [chunk, d_blk, N]
    dBx = dBx_ref[0]
    c = c_ref[0]            # [chunk, N]

    def step(t, h):
        h = dA[t] * h + dBx[t]                          # [d_blk, N]
        y_ref[0, t, :] = jnp.sum(h * c[t][None, :], axis=1)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])

    @pl.when(j == nchunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


def selective_scan_pallas(dA, dBx, c, *, chunk: int = 256,
                          d_block: int = 256, interpret: bool = True):
    """dA, dBx: [B, L, D, N] f32; c: [B, L, N] f32 -> y: [B, L, D] f32.
    L % chunk == 0 and D % d_block == 0 required (ops.py pads)."""
    B, L, D, N = dA.shape
    chunk = min(chunk, L)
    d_block = min(d_block, D)
    assert L % chunk == 0 and D % d_block == 0
    grid = (B, D // d_block, L // chunk)

    return pl.pallas_call(
        functools.partial(_body, chunk=chunk, nchunks=L // chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block, N),
                         lambda b, d, j: (b, j, d, 0)),
            pl.BlockSpec((1, chunk, d_block, N),
                         lambda b, d, j: (b, j, d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, d_block, N), lambda b, d, j: (b, d, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, L, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dA, dBx, c)

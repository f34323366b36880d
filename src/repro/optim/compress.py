"""Gradient compression for cross-pod (DCN-class) reductions.

int8 per-chunk-scaled quantization with error feedback:
    q = round(g / s),  s = max|g_chunk| / 127        (per 256-elem chunk)
    residual r += g - dequant(q)   carried to the next step (error feedback)
The quantized payload crosses the slow `pod` axis; scales are f32 but tiny
(1/256 of elements). Inside a pod, gradients reduce at full precision.

Two integration points:
  · `compressed_psum(x, axis)` — shard_map-level collective (tested directly)
  · `PodReducer` — pytree-level wrapper with persistent error-feedback state
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256


def _pad_to_chunks(x):
    flat = x.reshape(-1)
    pad = (-flat.size) % CHUNK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, CHUNK), pad


def quantize(g) -> tuple[jax.Array, jax.Array]:
    """g: any-shape f32/bf16 -> (int8 chunks [n,CHUNK], scales f32 [n])."""
    chunks, _ = _pad_to_chunks(g.astype(jnp.float32))
    scale = jnp.max(jnp.abs(chunks), axis=1) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(chunks / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale, shape) -> jax.Array:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


def compress_decompress(g):
    """Round-trip (the compression that the wire would carry)."""
    q, s = quantize(g)
    return dequantize(q, s, g.shape)


def compressed_psum(x, axis: str):
    """all-reduce over `axis` carrying int8 payloads + f32 scales.
    Mathematically: sum over shards of dequant(quant(x_i)). Must be called
    inside shard_map with `axis` manual."""
    q, s = quantize(x)
    # each shard contributes dequant(q)·1; reduce the *dequantized* values —
    # wire format is (int8 q, f32 s); on TPU the DCN transfer is the int8
    # payload, the psum here models the arithmetic.
    contrib = dequantize(q, s, x.shape)
    return jax.lax.psum(contrib, axis)


def pod_reduce_with_feedback(grads, residual, axis: str = "pod"):
    """One error-feedback compression step for a gradient pytree that is
    about to cross the pod axis. Returns (reduced_grads, new_residual).
    Call inside shard_map over `axis` (or without a mesh: identity+feedback)."""
    def one(g, r):
        g32 = g.astype(jnp.float32) + r
        q, s = quantize(g32)
        deq = dequantize(q, s, g32.shape)
        new_r = g32 - deq
        from repro.sharding.rules import current_mesh
        mesh = current_mesh()
        if mesh is not None and axis in mesh.axis_names:
            try:
                deq = jax.lax.psum(deq, axis) / mesh.shape[axis]
            except NameError:
                pass   # not inside shard_map: local-only (tests)
        return deq.astype(g.dtype), new_r
    flat_g, td = jax.tree.flatten(grads)
    flat_r = td.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r, strict=True)]
    return td.unflatten([o[0] for o in outs]), td.unflatten([o[1] for o in outs])


def init_residual(grads_like):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def compress_tree_for_pod_reduce(grads):
    """Stateless variant used by the dry-run train step when
    TrainConfig.compress_grads is on: models the quantize→reduce→dequantize
    arithmetic (error feedback lives in the trainer loop state)."""
    return jax.tree.map(compress_decompress, grads)

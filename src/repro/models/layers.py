"""Shared neural layers: norms, rope, MLP, embedding. Pure functions over
param pytrees (dicts); init_* builds params, apply is the function itself."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-6):
    """LayerNorm with a scale and a bias, computed in fp32."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def init_dense(key, d_in: int, d_out: int, dtype) -> jax.Array:
    std = d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def dense(x, w):
    return jnp.einsum("...d,df->...f", x, w)


# ---------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, positions, theta: float):
    """positions: [...]; returns (sin, cos) of shape positions.shape + (head_dim/2,)."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., seq, heads, head_dim] or [..., seq, head_dim]; positions broadcastable
    to x's seq axis. Rotates pairs (x[..:half], x[..half:]) -- neox style."""
    half = x.shape[-1] // 2
    sin, cos = rope_frequencies(x.shape[-1], positions, theta)
    if x.ndim == sin.ndim + 1:        # [..., seq, heads, dim] vs sin [..., seq, half]
        sin, cos = sin[..., None, :], cos[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MLP (GLU)
def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(k1, d_model, d_ff, dtype),
        "w_up": init_dense(k2, d_model, d_ff, dtype),
        "w_down": init_dense(k3, d_ff, d_model, dtype),
    }


def mlp(params, x):
    h = jax.nn.silu(dense(x, params["w_gate"])) * dense(x, params["w_up"])
    return dense(h, params["w_down"])


# ---------------------------------------------------------------- Embedding
def init_embed(key, vocab: int, d_model: int, dtype):
    return (jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02).astype(dtype)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(table, x):
    """Tied unembedding; logits in f32 for a stable softmax/CE."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      table.astype(jnp.float32))

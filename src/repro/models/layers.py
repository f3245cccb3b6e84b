"""Shared layers: norms, rotary embeddings (incl. M-RoPE), initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps: float = 1e-5):
    """RMSNorm with fp32 statistics but bf16 application.

    The squared-mean reduces in fp32 (fused, never materialized); the scale
    is applied in the stream dtype. This keeps the residual stream and its
    cotangents bf16 end-to-end — materializing the fp32 upcast costs two
    full activation tensors of HBM traffic per layer (llama3 train_4k:
    -344 GB/step/chip)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * gamma + beta).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x (..., S, H, Dh); positions (..., S) int32. Pairs (even, odd) lanes."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (...,S,dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_mrope(x, positions_3d, sections=(2, 1, 1), theta: float = 1e4):
    """Qwen2-VL M-RoPE: the rotary spectrum is split into (t, h, w) sections
    (ratios ``sections``), each rotated by its own position stream.

    x (..., S, H, Dh); positions_3d (3, ..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    total = sum(sections)
    bounds = []
    start = 0
    for s in sections:
        size = half * s // total
        bounds.append((start, start + size))
        start = start + size
    bounds[-1] = (bounds[-1][0], half)                  # absorb rounding

    freqs = rope_freqs(dh, theta)                       # (half,)
    # Build per-frequency position source by section.
    ang_parts = []
    for (lo, hi), pos in zip(bounds, positions_3d):
        ang_parts.append(pos[..., None].astype(jnp.float32) * freqs[lo:hi])
    ang = jnp.concatenate(ang_parts, axis=-1)           # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * (fan_in ** -0.5)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return jax.random.normal(key, (vocab, d), dtype) * (d ** -0.5)

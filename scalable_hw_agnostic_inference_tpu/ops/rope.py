"""Rotary position embeddings (Llama/Mistral-style half-rotation).

Pure functions over ``[B, T, H, D]`` tensors; positions are explicit so the
same code serves prefill (positions ``0..T``) and paged decode (arbitrary
per-token positions from the block table) without recompilation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def llama3_scaled_inv_freq(inv_freq: jax.Array, scaling) -> jax.Array:
    """HF ``rope_type="llama3"`` frequency remap (Llama-3.1+/mllama).

    ``scaling`` = (factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings). Long wavelengths divide by ``factor``,
    short ones pass through, the band between interpolates smoothly.
    """
    import math

    factor, low, high, orig = scaling
    low_wavelen = orig / low
    high_wavelen = orig / high
    wavelen = 2.0 * math.pi / inv_freq
    scaled = inv_freq / factor
    smooth = (orig / wavelen - low) / (high - low)
    mid = (1 - smooth) * scaled + smooth * inv_freq
    out = jnp.where(wavelen > low_wavelen, scaled, inv_freq)
    is_mid = jnp.logical_and(wavelen <= low_wavelen, wavelen >= high_wavelen)
    return jnp.where(is_mid, mid, out)


def rope_angles(positions: jax.Array, dim: int, theta: float = 10000.0,
                scaling=None):
    """cos/sin tables for ``positions`` → each ``[..., dim/2]`` (fp32).

    ``scaling``: optional llama3 rope-scaling tuple (see
    :func:`llama3_scaled_inv_freq`).
    """
    if dim % 2:
        raise ValueError(f"rope dim must be even, got {dim}")
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is not None:
        inv_freq = llama3_scaled_inv_freq(inv_freq, scaling)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., dim/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               scaling=None) -> jax.Array:
    """Rotate ``x`` ``[B, T, H, D]`` by per-token ``positions`` ``[B, T]``.

    Half-rotation convention (HF Llama): the first D/2 lanes pair with the
    last D/2 lanes.
    """
    B, T, H, D = x.shape
    cos, sin = rope_angles(positions, D, theta, scaling)  # [B, T, D/2]
    cos = cos[:, :, None, :]  # [B, T, 1, D/2]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float = 10000.0) -> jax.Array:
    """Rotate ``x`` ``[B, T, H, D]`` by ``positions`` ``[B, T]`` with the
    pairs on NEIGHBOURING lanes: lanes ``(2i, 2i+1)`` turn by
    ``positions * theta ** (-2i / D)`` and stay where they are (HF's
    ``rope_interleave``, DeepSeek-V3's layout). A dot product of two
    vectors rotated this way is the one HF computes after its
    de-interleaving permutation, which it applies to both sides alike."""
    B, T, H, D = x.shape
    cos, sin = rope_angles(positions, D, theta)           # [B, T, D/2]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(B, T, H, D // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(B, T, H, D).astype(x.dtype)

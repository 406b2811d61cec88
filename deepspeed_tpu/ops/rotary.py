"""Rotary position embeddings.

Reference counterpart: ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``
(432 LoC CUDA). On TPU this is pure VPU elementwise work that XLA fuses into
the surrounding projections, so the jnp form IS the fast path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10000.0):
    """Precompute cos/sin tables [T, Dh/2] in fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [T, Dh/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary_pos_emb(x: jax.Array, cos: jax.Array, sin: jax.Array,
                         position_offset=0) -> jax.Array:
    """x: [B, T, H, Dh]; cos/sin: [T_max, Dh/2] tables.

    Pairs (x[2i], x[2i+1]) rotated by position angle — the interleaved GPT-NeoX
    convention used by LLaMA.

    ``position_offset`` may be a per-slot ``[B]`` vector (continuous
    batching): each batch row is then rotated at its own position.
    """
    b, t, h, dh = x.shape
    if not isinstance(position_offset, int) and jnp.ndim(position_offset) == 1:
        pos = position_offset[:, None] + jnp.arange(t)[None, :]  # [B, T]
        c = cos[pos][:, :, None, :]  # [B, T, 1, Dh/2]
        s = sin[pos][:, :, None, :]
    else:
        if isinstance(position_offset, int) and position_offset == 0:
            c = jax.lax.dynamic_slice_in_dim(cos, 0, t, axis=0)
            s = jax.lax.dynamic_slice_in_dim(sin, 0, t, axis=0)
        else:
            c = jax.lax.dynamic_slice_in_dim(cos, position_offset, t, axis=0)
            s = jax.lax.dynamic_slice_in_dim(sin, position_offset, t, axis=0)
        c = c[None, :, None, :]  # [1, T, 1, Dh/2]
        s = s[None, :, None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(b, t, h, dh)
    return out.astype(x.dtype)


def apply_rotary_half(x: jax.Array, positions: jax.Array, theta: float
                      ) -> jax.Array:
    """RoPE in the rotate-half convention (HF LLaMA's ``rotate_half``: the
    halves ``x[..., :Dh/2]`` and ``x[..., Dh/2:]`` are the pairs) over the
    whole head dimension, angles computed and not tabulated: ``x [B, T, H,
    Dh]`` at ``positions`` (``[T]``, or ``[B, T]`` under continuous
    batching), in float32."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    return apply_rotary_half_freqs(x, positions, inv)


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's ``[dim / 2]`` inverse frequencies (DeepSeek's ``deepseek_yarn``):
    a pair that turns more than ``beta_fast`` times over the ``original_max``
    trained positions keeps its frequency ``f_i = theta ** (-2 i / dim)``, one
    that turns less than ``beta_slow`` times is slowed by ``factor``, and a
    linear ramp blends the pairs between.

        dim(n) = dim ln(original_max / (2 pi n)) / (2 ln theta)
        low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), in [0, dim/2 - 1]
        r_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i (1 - r_i) + f_i / factor r_i"""
    half = dim // 2

    def turns_at(n):
        return dim * math.log(original_max / (2 * math.pi * n)) / \
            (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), half - 1)
    span = (high - low) or 0.001
    i = jnp.arange(half, dtype=jnp.float32)
    f = theta ** (-2.0 * i / dim)
    r = jnp.clip((i - low) / span, 0.0, 1.0)
    return f * (1.0 - r) + f / factor * r


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where ``factor <= 1``): with
    ``mscale_all_dim`` it multiplies the softmax scale twice over."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rotary_half_freqs(x: jax.Array, positions: jax.Array,
                            inv_freq: jax.Array) -> jax.Array:
    """:func:`apply_rotary_half` at given inverse frequencies ``[Dh / 2]``
    (:func:`yarn_inv_freq`): ``x [B, T, H, Dh]`` at ``positions`` (``[T]`` or
    ``[B, T]``), rotate-half, in float32."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # [.., T, Dh/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang[None, :, None] if ang.ndim == 2 else ang[:, :, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(ang) + turned * jnp.sin(ang)).astype(x.dtype)


def apply_rotary_pairs_freqs(x: jax.Array, positions: jax.Array,
                             inv_freq: jax.Array) -> jax.Array:
    """Rotation in the INTERLEAVED convention (``rope_interleave``): the
    neighbours ``(x[2i], x[2i + 1])`` are pair ``i``, turned by ``positions *
    inv_freq[i]`` where they lie. ``x [B, T, H, Dh]`` at ``positions`` (``[T]``
    or ``[B, T]``), ``inv_freq [Dh / 2]`` (:func:`yarn_inv_freq`), in
    float32. A score of two vectors rotated so is the score of the same two
    rotated by halves after a de-interleave: the convention names which lanes
    of a checkpoint's projection are partners."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # [.., T, Dh/2]
    ang = ang[None, :, None] if ang.ndim == 2 else ang[:, :, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)

"""Rotary position embeddings (RoPE).

Parity role: the reference applies rotary inside ``TP_Attn``
(``layers/nvidia/tp_attn.py:120-160``) with precomputed cos/sin caches.
Here it's a pure function over positions — XLA fuses the trig + rotate
into the surrounding kernels, so no cache tensor is materialized.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float = 1e6) -> jax.Array:
    """Inverse frequencies [head_dim/2] (Qwen3 default theta=1e6)."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_freqs(
    head_dim: int, theta: float, factor: float, beta_fast: float = 32.0,
    beta_slow: float = 1.0, original_max: int = 4096,
) -> jax.Array:
    """YaRN inverse frequencies [head_dim/2] (the DeepSeek-V3 rotary):
    per frequency a blend of ``theta^(-2i/d)`` and that over ``factor``.
    Dims that turn more than ``beta_fast`` times over the original
    length keep their frequency, those that turn fewer than
    ``beta_slow`` times are interpolated, with a linear ramp between
    the two correction dims."""
    extra = theta ** -(np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def correction_dim(turns):
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(extra / factor * ramp + extra * (1 - ramp),
                       jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term, ``0.1 m ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(
    x: jax.Array,          # [..., S, head_dim] or [..., head_dim]
    positions: jax.Array,  # [..., S] or [...] int32 absolute positions
    theta: float = 1e6,
    inv_freq: jax.Array | None = None,  # [head_dim/2]: theta is unused
) -> jax.Array:
    """Rotate-half RoPE (HF convention: first/second half pairing)."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., hd/2]
    cos = jnp.cos(ang)
    sin = jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)

"""Pieces of the modern decoder block that more than one model family
can use: RMSNorm, the rotary position table (plain or YaRN-scaled) with
its rotation of interleaved pairs or of the two halves, and the SwiGLU
feed-forward.

``TransformerLM`` (GPT-2 block: LayerNorm, learned positions, GELU) uses
none of them; ``latent_moe.LatentMoELM`` uses all three.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps: float):
    """``x / rms(x) * gain`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


class RMSNorm(nn.Module):
    """Root-mean-square norm with a learned gain (no bias, no mean).
    Computed in float32; the result is cast to ``dtype``."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, gain, self.eps).astype(self.dtype)


class SwiGLU(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down``, no biases.  A ``limit``
    above 0 clamps as gpt-oss does: ``x W_gate`` at most ``limit``,
    ``x W_up`` within ``+-limit``."""

    width: int
    dtype: jnp.dtype
    limit: float = 0.0

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        if not self.limit:
            h = nn.silu(dense(self.width, "gate")(x)) \
                * dense(self.width, "up")(x)
        else:
            gate = jnp.minimum(dense(self.width, "gate")(x), self.limit)
            up = jnp.clip(dense(self.width, "up")(x), -self.limit,
                          self.limit)
            h = nn.silu(gate) * up
        return dense(d, "down")(h)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 m ln(s) + 1`` for ``s > 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_inv_freq(dim: int, theta: float, *, factor: float = 1.0,
                    beta_fast: float = 32.0, beta_slow: float = 1.0,
                    original_max_len: Optional[int] = None):
    """Inverse frequencies ``[dim / 2]`` of a rotary embedding over
    ``dim`` channels.  ``factor > 1`` blends, per channel, the plain
    frequency with the one interpolated by ``factor`` (YaRN, "NTK by
    parts": channels that turn more than ``beta_fast`` times inside
    ``original_max_len`` keep theirs, those that turn fewer than
    ``beta_slow`` times are interpolated, a linear ramp between)."""
    half = dim // 2
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    if factor <= 1.0:
        return plain

    def correction_dim(turns):
        return dim * math.log(original_max_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return plain / factor * (1.0 - keep) + plain * keep


def rotary_angles(positions, inv_freq):
    """``(cos, sin)`` ``[..., dim / 2]`` in float32 at ``positions``."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary_interleaved(x, cos, sin):
    """Rotate the channel pairs ``(x[2i], x[2i + 1])`` of the last axis by
    the angle of pair ``i``.  ``cos``/``sin`` broadcast against
    ``x[..., ::2]``.  Float32 inside, ``x.dtype`` out."""
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rotary_half(x, cos, sin):
    """Rotate the channel pairs ``(x[i], x[i + D / 2])`` of the last axis
    by the angle of pair ``i`` (the "rotate half" convention).  ``cos``/
    ``sin`` broadcast against ``x[..., :D / 2]``.  Float32 inside,
    ``x.dtype`` out."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)

"""Rotary position embedding with YaRN's frequencies, as the DeepSeek-V2/V3
family of latent attention applies it to the "rope" dims of a query and of
the one key all heads share.

Pair ``i`` of a ``d``-wide vector is dims ``(i, i + d / 2)`` (the halves;
the family's code reaches the same pairs after de-interleaving its
weights' columns, and with query and key paired alike the scores are the
same function of the weights). Angles, cosines and sines are float32.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """(dim / 2,) float32 inverse frequencies: ``theta^(-2i/dim)`` for the
    pairs that turn more than ``beta_fast`` times over ``original_max``
    positions, that over ``factor`` for those that turn fewer than
    ``beta_slow`` times, and a linear ramp between the two pair indices."""
    half = dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)

    def pair_turning(turns):  # the pair index that turns `turns` times
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (freq * (1.0 - ramp) + freq / factor * ramp).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotate(x, positions, inv_freq, gain: float = 1.0):
    """``x`` (B, S, ..., d) with each pair turned by its token's
    ``position * inv_freq``; ``positions`` (S,). ``gain`` rides on the
    cosines and sines (YaRN's ``mscale / mscale_all_dim``). In ``x``'s
    dtype."""
    half = x.shape[-1] // 2
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos = gain * jnp.cos(angle).reshape(shape)
    sin = gain * jnp.sin(angle).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)

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


def yarn_rotation(dim: int, r) -> tuple:
    """A config's ``rope_scaling`` group with its ``theta`` as what latent
    attention needs of it: YaRN's inverse frequencies for ``dim`` "rope"
    dims, the gain on the cosines and sines (``mscale / mscale_all_dim``),
    and the factor ``mscale_all_dim^2`` on the softmax scale."""
    m_all = yarn_mscale(r["factor"], r["mscale_all_dim"])
    return (yarn_inv_freq(dim, r["theta"], r["factor"],
                          r["original_max_position_embeddings"],
                          r["beta_fast"], r["beta_slow"]),
            yarn_mscale(r["factor"], r["mscale"]) / m_all, m_all ** 2)


def tables(positions, inv_freq, gain: float = 1.0):
    """``(cos, sin)``, each (S, d / 2) float32: ``gain`` times the cosine
    and sine of ``position * inv_freq``; ``positions`` (S,). ``gain`` is
    YaRN's ``mscale / mscale_all_dim``."""
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    return gain * jnp.cos(angle), gain * jnp.sin(angle)


def rotate(x, positions, inv_freq, gain: float = 1.0):
    """``x`` (B, S, ..., d) with each pair turned by its token's
    ``position * inv_freq``; ``positions`` (S,). ``gain`` rides on the
    cosines and sines (:func:`tables`). In ``x``'s dtype."""
    half = x.shape[-1] // 2
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = (t.reshape(shape) for t in tables(positions, inv_freq, gain))
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)

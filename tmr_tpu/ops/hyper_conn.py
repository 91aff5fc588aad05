"""Manifold-constrained hyper-connections (arXiv 2512.24880 over arXiv
2409.19606): the residual path as ``n`` streams, a sub-layer reading one
mix of them and writing back through a doubly stochastic mix.

For every token, with ``X`` its (n, C) streams and ``F`` the sub-layer::

    v      = flatten(X) / rms(flatten(X))            (no learned weight)
    H_pre  = sigmoid(a_pre  v phi_pre  + b_pre)      (n,)
    H_post = 2 sigmoid(a_post v phi_post + b_post)   (n,)
    H_res  = SinkhornKnopp(clip(a_res mat(v phi_res) + b_res))   (n, n)
    X'     = H_res X + H_post^T F(norm(H_pre X))

**Layout.** The streams lead and the tokens trail: ``x`` is (n, ..., C) and
the coefficients come back as (n, ...), (n, ...) and (n, n, ...). On the
chip the tokens then lie along the lanes, the n x n arithmetic of the
Sinkhorn sweeps is element-wise adds and divides of whole vectors of tokens
(a sum over a 4-long axis as three adds, which XLA fuses; it does not fuse
a reduction), and no array has a 4-long minor axis to be padded to a tile.

All of the coefficient arithmetic is float32; the mixes accumulate in
float32 and the streams are stored in the compute type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def hc_formulation(n: int, width: int, dtype) -> str:
    """What a hyper-connected sub-layer traces with (counter
    ``trunk.hc.<formulation>``). One answer today: the XLA passes of this
    file; a kernel that keeps a token's streams on the chip between the
    coefficients and the mixes gets its name and its gate here."""
    return "xla"


def _sum_short(m, axis: int):
    """The sum over a short leading axis as adds of its slices."""
    return sum(lax.index_in_dim(m, j, axis, keepdims=True)
               for j in range(m.shape[axis]))


def sinkhorn(logits, iters: int, eps: float):
    """``logits`` (n, n, ...) -> ``exp`` of them with, ``iters`` times,
    every row divided by (its sum + eps), then every column by (its sum +
    eps): rows and columns then sum to 1."""
    def sweep(_, m):
        m = m / (_sum_short(m, 1) + eps)
        return m / (_sum_short(m, 0) + eps)

    return lax.fori_loop(0, iters, sweep, jnp.exp(logits))


def coefficients(x, phi, alpha, b_pre, b_post, b_res, iters: int, eps: float,
                 clamp, norm_eps: float):
    """``x`` (n, ..., C) -> ``H_pre`` (n, ...), ``H_post`` (n, ...),
    ``H_res`` (n, n, ...), float32. ``phi`` (n C, 2 n + n^2), columns
    [pre | post | res]; ``alpha`` (3,); ``clamp`` (low, high) of ``h_res``.

    ``v phi`` is taken as ``(sum_j X_j phi_j) / rms``: the norm has no
    weight, so it is one scalar a token and the streams are read once, not
    normalised and written first. With ``x`` and the leaf both in bfloat16
    one MXU pass accumulated in float32 is exact; float32 operands take
    ``highest``."""
    f32 = jnp.float32
    n, c = x.shape[0], x.shape[-1]
    tokens = x.shape[1:-1]
    rows = x.reshape(n, -1, c)
    both = jnp.promote_types(x.dtype, phi.dtype)
    w = phi.reshape(n, c, -1).astype(both)
    u = sum(jnp.matmul(rows[j].astype(both), w[j], precision=HI,
                       preferred_element_type=f32)
            for j in range(n))  # (T, 2n + n^2)
    mean_sq = sum(jnp.mean(jnp.square(rows[j].astype(f32)), -1)
                  for j in range(n)) / n
    u = (u * lax.rsqrt(mean_sq + norm_eps)[:, None]).T  # tokens on the lanes
    alpha = alpha.astype(f32)
    col = lambda t: t.astype(f32).reshape(t.shape + (1,))
    h_pre = jax.nn.sigmoid(alpha[0] * u[:n] + col(b_pre))
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * u[n:2 * n] + col(b_post))
    h_res = alpha[2] * u[2 * n:].reshape(n, n, -1) + col(b_res)
    h_res = sinkhorn(jnp.clip(h_res, clamp[0], clamp[1]), iters, eps)
    return (h_pre.reshape((n,) + tokens), h_post.reshape((n,) + tokens),
            h_res.reshape((n, n) + tokens))


def pre_mix(x, h_pre, dtype):
    """``sum_j H_pre[j] X_j``: (n, ..., C) -> (..., C) in ``dtype``."""
    f32 = jnp.float32
    return sum(h_pre[j][..., None] * x[j].astype(f32)
               for j in range(x.shape[0])).astype(dtype)


def post_mix(x, y, h_post, h_res):
    """Stream ``i`` of the result: ``sum_j H_res[i, j] X_j + H_post[i] y``;
    (n, ..., C) in ``x``'s dtype.

    The result is stored, and the barrier says so: every stream of a
    sub-layer reads all n of the one before, element-wise, and left to fuse
    through them XLA's CPU compiler duplicates the chain n-fold a sub-layer
    in bfloat16 (six sub-layers compiled in 108 s, twelve not in ten
    minutes; with the barrier 2.6 s). On the chip the streams have three
    readers and are stored anyway."""
    f32 = jnp.float32
    n = x.shape[0]
    x32, y32 = [x[j].astype(f32) for j in range(n)], y.astype(f32)
    return lax.optimization_barrier(jnp.stack([
        (sum(h_res[i, j][..., None] * x32[j] for j in range(n))
         + h_post[i][..., None] * y32).astype(x.dtype) for i in range(n)]))

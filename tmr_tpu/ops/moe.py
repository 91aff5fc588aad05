"""Sparse expert layer: a router over all the experts of the model (sigmoid
scores with a selection bias, :func:`route`, or a softmax over the chosen
experts' logits, :func:`route_softmax_topk`), and the part of the layer's
result that the experts *held here* give.

A layer is told which experts it holds (``expert_offset`` and the leading
size of its expert weights): under expert parallelism every chip routes over
all experts and computes its own experts' part for the tokens routed to
them; the parts add up to the whole layer (``tests/test_lm_trunk.py``). No
token is dropped and there is no capacity factor: the pairs are sorted by
expert and the products are grouped (``jax.lax.ragged_dot``, or on a TPU the
Pallas grouped matrix product), so the buffer holds every pair even when
every token picks experts held here. On one chip there is no exchange, and
nothing here stands in for one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def route(x, kernel, bias, top_k: int, scale: float):
    """The router, in float32: ``x`` (T, D), ``kernel`` (D, E), ``bias``
    (E,) the selection bias. Scores ``s = sigmoid(x W)``; the ``top_k``
    experts with the largest ``s + bias`` are chosen; the weights come from
    ``s`` alone, renormalised over the chosen and scaled. Returns expert ids
    (T, k) int32 and weights (T, k) float32."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.matmul(x.astype(f32), kernel.astype(f32),
                                  precision=HI))
    _, idx = lax.top_k(s + bias.astype(f32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), weights


def route_softmax_topk(x, kernel, top_k: int):
    """The other router, in float32: ``logits = x W``; the ``top_k`` experts
    with the largest logits are chosen and weighed by a softmax over the
    chosen logits alone. No selection bias, no scale. Returns as
    :func:`route`."""
    f32 = jnp.float32
    logits = jnp.matmul(x.astype(f32), kernel.astype(f32), precision=HI)
    chosen, idx = lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)


def dispatch(x, idx, experts_held: int, expert_offset: int = 0):
    """Sort the token-expert pairs by expert. Returns the pairs' inputs
    ``xs`` (T * k, D) with the pairs of experts held here first, in expert
    order, ``group_sizes`` (experts_held,) int32, ``here`` (T, k) bool and
    ``slot`` (T, k) int32, the row of ``xs`` each pair went to."""
    t, k = idx.shape
    local = idx - expert_offset
    here = (local >= 0) & (local < experts_held)
    key = jnp.where(here, local, experts_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    group_sizes = jnp.bincount(key, length=experts_held + 1)[:experts_held]
    return (x[order // k], group_sizes.astype(jnp.int32), here,
            slot.reshape(t, k))


def _gmm_tiles(m: int, k: int, n: int):
    """(tm, tk, tn) for the Pallas grouped product, or None where the sizes
    do not tile: 512 rows of pairs, and the widest multiples of 128 that
    divide ``k`` and ``n`` up to 1152 and 1024 (so that the tiles and the
    float32 accumulator stay within the kernel's fast memory)."""
    def widest(size, cap):
        fits = [t for t in range(128, cap + 1, 128) if size % t == 0]
        return max(fits) if fits else None

    tk, tn = widest(k, 1152), widest(n, 1024)
    if m % 512 or tk is None or tn is None:
        return None
    return 512, tk, tn


def grouped_formulation(rows: int, d: int, width: int, dtype) -> str:
    """What the grouped products trace with, by what can be observed: on a
    TPU in bfloat16 at sizes that tile, the Pallas grouped matrix product
    (``gmm``: kept for attribution, not speed. It costs what XLA's own
    rewrite of ``ragged_dot`` costs, and keeps the layer's scope path on the
    device trace, which that rewrite drops; ``chip_smoke.py`` holds the two
    equal on the chip); else ``lax.ragged_dot``."""
    tiles = _gmm_tiles(rows, d, width) and _gmm_tiles(rows, width, d)
    if (jax.default_backend() == "tpu" and dtype == jnp.bfloat16 and tiles):
        return "gmm"
    return "ragged_dot"


def grouped_ffn(xs, group_sizes, gate, up, down, dtype,
                formulation: str = "ragged_dot"):
    """``down(silu(gate x) * up x)`` of every row by its group's expert:
    ``gate``, ``up`` (Eh, D, I), ``down`` (Eh, I, D). Rows past the groups'
    total are not computed."""
    if formulation == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        dot = lambda a, w: gmm(
            a, w.astype(dtype), group_sizes, preferred_element_type=dtype,
            tiling=_gmm_tiles(a.shape[0], w.shape[1], w.shape[2]))
    else:
        dot = lambda a, w: lax.ragged_dot(a, w.astype(dtype), group_sizes,
                                          preferred_element_type=dtype)
    xs = xs.astype(dtype)
    return dot(jax.nn.silu(dot(xs, gate)) * dot(xs, up), down)


def combine(ys, weights, here, slot):
    """Each token's weighted sum over its pairs held here: ``ys`` (T * k, D)
    in sorted order -> (T, D) float32."""
    pairs = ys[slot.reshape(-1)].reshape(slot.shape + ys.shape[-1:])
    # rows past the groups' total hold whatever the product left there
    return jnp.where(here[..., None],
                     pairs.astype(jnp.float32) * weights[..., None],
                     0.0).sum(1)

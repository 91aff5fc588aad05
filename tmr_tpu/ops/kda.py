"""Gated delta-rule linear attention with channel-wise decay (KDA,
arXiv 2510.26692, section 3), and the short causal depthwise convolution
that feeds it.

Per head, with the state ``S`` (d_k x d_v), ``S_0 = 0``, decay
``a_t = exp(g_t)`` in (0, 1] one value a channel, ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_recurrent` is that recurrence, one token a step: the definition.
:func:`kda_chunked` is what a program runs. Inside a chunk of ``C`` tokens,
with ``G_t`` the running sum of ``g`` from the chunk's start and
``u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t)``::

    (I + A) U = beta (V - (K * exp(G)) S_0)      A_ti = beta_t sum_c k_tc k_ic exp(G_tc - G_ic), i < t
    O         = (Q * exp(G)) S_0 + A' U          A'_ti = sum_c q_tc k_ic exp(G_tc - G_ic), i <= t
    S_C       = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

so everything but the hand-over of ``S`` is computed for all chunks at once.
A decay is only ever taken of a difference that is at most 0, never as
``exp(-G)``: inside a sub-block of ``sub`` tokens the differences
``G_t - G_i`` are formed a channel at a time; between sub-blocks both sides
are taken against the running sum at the later block's start
(``exp(G_t - R) * exp(R - G_i)``, both exponents at most 0). ``(I + A)^-1``
of the strictly lower triangular ``A`` is taken by halves down to 16 rows,
and there as the finite product ``(I - A)(I + A^2)(I + A^4)...``.

:func:`kda_chunk_kernel` is the same mathematics as one Pallas TPU kernel
that keeps a chunk's matrices and the state on the chip and reads q, k, v, g
where the projections left them (inside a sub-block it multiplies the decays
up a token at a time, each at most 1, where ``kda_chunked`` takes the decay
of a difference); :func:`kda_formulation` says which of the two a trace
takes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import mosaic_gate

HI = lax.Precision.HIGHEST


def causal_conv(x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution along the sequence: ``x`` (B, S, C),
    ``kernel`` (K, C), left-padded with zeros, no bias;
    ``y_t = sum_j kernel[j] * x_{t - (K - 1) + j}``."""
    k = kernel.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(pad[:, j:j + s] * kernel[j].astype(x.dtype) for j in range(k))


def l2norm(t: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """``t`` over its last axis's length, in float32."""
    t32 = t.astype(jnp.float32)
    return t32 * lax.rsqrt(jnp.sum(t32 * t32, -1, keepdims=True) + eps)


def rms_norm(x, weight, eps):
    """``x`` over its last axis's root mean square, weighted, in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) * (
        weight.astype(jnp.float32))


def kda_recurrent(q, k, v, g, beta):
    """The token recurrence in float32. ``q``, ``k`` (B, S, H, dk), ``v``
    (B, S, H, dv), ``g`` (B, S, H, dk) log-decays (at most 0), ``beta``
    (B, S, H). Returns ``o`` (B, S, H, dv) float32."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, state, precision=HI))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state, precision=HI)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    init = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(lax.scan(step, init, xs)[1], 0, 1)


def _unit_lower_inverse(a: jnp.ndarray, leaf: int = 16) -> jnp.ndarray:
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., C, C). Down
    to ``leaf`` rows by halves: with ``T1``, ``T2`` the inverses of the two
    diagonal blocks, the block under the diagonal is ``-T2 a21 T1``. A leaf
    is ``sum_k (-a)^k`` as ``(I + x)(I + x^2)(I + x^4)...``, ``x = -a``."""
    c = a.shape[-1]
    mm = lambda m, n: jnp.matmul(m, n, precision=HI)
    if c > leaf and c % 2 == 0:
        h = c // 2
        t = _unit_lower_inverse(
            jnp.stack([a[..., :h, :h], a[..., h:, h:]], axis=-3), leaf)
        t1, t2 = t[..., 0, :, :], t[..., 1, :, :]
        under = -mm(mm(t2, a[..., h:, :h]), t1)
        return jnp.concatenate([
            jnp.concatenate([t1, jnp.zeros_like(t1)], -1),
            jnp.concatenate([under, t2], -1)], -2)
    eye = jnp.eye(c, dtype=a.dtype)
    power, inv, span = -a, eye - a, 2
    while span < c:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        span *= 2
    return inv


def _chunk_matrices(q, k, g_cum, sub: int):
    """``A`` (before ``beta``, strictly lower) and ``A'`` (lower with the
    diagonal) of every chunk: ``q``, ``k``, ``g_cum`` (..., C, d) float32
    -> two (..., C, C)."""
    c = q.shape[-2]
    ns = c // sub
    lead = q.shape[:-2]
    blk = lambda t: t.reshape(lead + (ns, sub, t.shape[-1]))
    qb, kb, gb = blk(q), blk(k), blk(g_cum)
    # inside a sub-block: the differences themselves, a channel at a time
    t_ge_i = jnp.tril(jnp.ones((sub, sub), bool))
    diff = gb[..., :, None, :] - gb[..., None, :, :]
    decay = jnp.where(t_ge_i[..., None], jnp.exp(
        jnp.where(t_ge_i[..., None], diff, 0.0)), 0.0)
    kk_diag = (kb[..., :, None, :] * kb[..., None, :, :] * decay).sum(-1)
    qk_diag = (qb[..., :, None, :] * kb[..., None, :, :] * decay).sum(-1)
    kk_rows, qk_rows = [], []
    for b in range(ns):
        lo, hi = b * sub, (b + 1) * sub
        kk, qk = [kk_diag[..., b, :, :]], [qk_diag[..., b, :, :]]
        if b:
            # against the running sum at this block's start: both
            # exponents are at most 0
            ref = g_cum[..., lo - 1:lo, :]
            left = jnp.exp(g_cum[..., lo:hi, :] - ref)
            right = k[..., :lo, :] * jnp.exp(ref - g_cum[..., :lo, :])
            off = lambda m: jnp.einsum("...tc,...ic->...ti", m * left, right,
                                       precision=HI)
            kk.insert(0, off(k[..., lo:hi, :]))
            qk.insert(0, off(q[..., lo:hi, :]))
        if hi < c:
            zeros = jnp.zeros(lead + (sub, c - hi), q.dtype)
            kk.append(zeros)
            qk.append(zeros)
        kk_rows.append(jnp.concatenate(kk, -1))
        qk_rows.append(jnp.concatenate(qk, -1))
    a_kk = jnp.concatenate(kk_rows, -2)
    a_qk = jnp.concatenate(qk_rows, -2)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    return jnp.where(strict, a_kk, 0.0), a_qk


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
                dtype=jnp.float32):
    """The chunked form: equal to :func:`kda_recurrent`. Shapes as there.
    The chunk's own matrices are float32; the products with the state take
    their operands in ``dtype`` and accumulate in float32, and the state is
    carried in float32. A sequence that is no multiple of ``chunk`` is
    padded with tokens that leave the state as it is."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)
    pad = n * chunk - s

    def chunks(t):  # (B, S, H, x) -> (B, H, N, C, x) float32
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])
    # the running sum inside a chunk, as a product with the lower triangle
    # of ones (a scan along 64 rows of a tiled operand is the slower way)
    g_cum = jnp.matmul(jnp.tril(jnp.ones((chunk, chunk), jnp.float32)), g,
                       precision=HI)
    a_kk, a_qk = _chunk_matrices(q, k, g_cum, sub)
    inv = _unit_lower_inverse(a_kk * beta)
    decay = jnp.exp(g_cum)
    w_v = jnp.matmul(inv, v * beta, precision=HI)
    w_k = jnp.matmul(inv, k * decay * beta, precision=HI)
    q_in = q * decay
    g_last = g_cum[..., -1:, :]
    k_out = k * jnp.exp(g_last - g_cum)
    carry_decay = jnp.exp(g_last[..., 0, :])  # (B, H, N, dk)

    def step(state, t):
        w_v_n, w_k_n, q_n, a_n, k_n, d_n = t
        mm = lambda eq, m, x: jnp.einsum(
            eq, m, x.astype(dtype), preferred_element_type=jnp.float32)
        u = w_v_n - mm("bhck,bhkv->bhcv", w_k_n, state)
        o = mm("bhck,bhkv->bhcv", q_n, state) + mm("bhct,bhtv->bhcv", a_n, u)
        state = state * d_n[..., None] + mm("bhck,bhcv->bhkv", k_n, u)
        return state, o

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (
        w_v, w_k.astype(dtype), q_in.astype(dtype), a_qk.astype(dtype),
        k_out.astype(dtype), carry_decay))
    init = jnp.zeros((b, h, dk, dv), jnp.float32)
    o = lax.scan(step, init, xs)[1]  # (N, B, H, C, dv)
    o = jnp.moveaxis(o, (0, 2), (1, 3))  # (B, N, C, H, dv)
    return o.reshape(b, n * chunk, h, dv)[:, :s]


# --------------------------------------------------------------------------
# The chunked form as one kernel (formulation ``chunk_kernel``).
#
# ``kda_chunked`` writes every chunk's matrices to HBM in float32 and
# reshuffles them between thirty small products; of the 10.7 ms a layer an
# image that took on the chip, 2.6 were passes that compute nothing and the
# rest small fusions bound by HBM and launches (PERF.md, PR 29). Here one
# grid step is one chunk of ``_HB`` heads: q, k, g and v arrive as
# (B, S, H x d), lanes selecting the heads, the chunk axis is the grid's last
# and sequential, and the state (one d x d float32 a head, kept transposed so
# that the decay of its rows is a lane broadcast) is VMEM scratch carried
# from chunk to chunk. Nothing of a chunk but ``o`` goes back to HBM.
#
# The heads go two at a time. A pair's C x C matrices (C = 64) share one
# (C, 128) float32 tile, the first head's in lanes [0, C) and the second's in
# [C, 128). A float32 product takes its operands as three bfloat16 parts each
# (``pallas_attn._split3``) and the six largest of the nine products, which
# is what ``Precision.HIGHEST`` does; two of the six share a pass of the
# 128-deep MXU, side by side along the contraction, and the pair shares the
# pass's weights. What the chip showed (PERF.md, PR 30): a lane roll costs
# about three element-wise operations and a 128 x 128 transpose 350 cycles,
# so the sub-blocks' diagonals are built from one roll by a single lane a
# step, and the running sum is a scan down the sublanes, whose rolls cost
# nothing.
# --------------------------------------------------------------------------
_CHUNK, _SUB, _LANES = 64, 16, 128
_HB = 8  # heads a grid step, at most: chosen on the chip (PERF.md, PR 30)
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_BF16 = jnp.bfloat16


def _parts(x):
    """Float32 ``x`` as three float32 arrays, each exact in bfloat16, that
    sum to it."""
    from tmr_tpu.ops.pallas_attn import _split3

    return _split3(x, _BF16)


def _rhs(parts):
    """A right operand's (C, n) parts stacked for :func:`_lhs`: (384, n)."""
    hi, mid, lo = (p.astype(_BF16) for p in parts)
    return jnp.concatenate([hi, mid, hi, mid, lo, hi], axis=0)


def _kda_chunk_body(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, o_ref,
                    state, *, hb: int, dtype, q_scale, norm_eps):
    """One chunk of ``hb`` heads. Refs: q, k, g (1, C, hb x 128) float32, v
    the same in the compute type, beta (1, C, H) float32, w (1, 128), o
    (1, C, hb x 128) float32; scratch ``state`` (hb, 128, 128) float32, a
    head's state transposed (value channel, key channel). With ``q_scale``
    q and k arrive as the convolutions left them, in any float type, and a
    head's row is normalised here (:func:`l2norm`; q times ``q_scale``
    after); with ``norm_eps`` a head's row of ``o`` leaves as
    :func:`rms_norm` of it, weighted by ``w``. The heads are a loop, two a
    step: a pair's C x C matrices share a tile and a pass of the MXU. The
    16 diagonals of the sub-blocks are a loop too: the chip compiles the
    kernel with every program that holds it, in proportion to its code."""
    c, sub, f32 = _CHUNK, _SUB, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    iota = lambda shape, dim: lax.broadcasted_iota(jnp.int32, shape, dim)
    rows, lanes = iota((c, _LANES), 0), iota((c, _LANES), 1)
    sub_rows, sub_lanes = iota((sub, _LANES), 0), iota((sub, _LANES), 1)
    # a pair's matrices side by side in one tile: the first head's in lanes
    # [0, C), the second's in [C, 128)
    first, sub_first = lanes < c, sub_lanes < c
    col, sub_col = lanes % c, sub_lanes % c
    same16 = rows // sub == col // sub
    same32 = rows // (2 * sub) == col // (2 * sub)
    skew = sub_rows - sub_lanes % sub
    scan_rows = iota((c, 2 * _LANES), 0)
    beta_all = beta_ref[0]
    beta_lanes = iota(beta_all.shape, 1)
    first_head = pl.program_id(1) * hb

    def _lhs(parts):
        """The pair's left operands for :func:`pair_dot`, the first head's
        rows over the second's: (2 C, 384). A head's ``[hi | hi]``,
        ``[mid | mid]``, ``[hi | lo]`` meet ``_rhs``'s ``[hi; mid]``,
        ``[hi; mid]``, ``[lo; hi]`` along the contraction."""
        hi, mid, lo = parts
        r_hi, r_mid, r_lo = (pltpu.roll(p, c, 1) for p in parts)
        pick = lambda x, y: jnp.where(first, x, y)
        return jnp.concatenate([
            jnp.concatenate([pick(hi, r_hi), pick(mid, r_mid),
                             pick(hi, r_lo)], axis=1),
            jnp.concatenate([pick(r_hi, hi), pick(r_mid, mid),
                             pick(r_hi, lo)], axis=1)], axis=0).astype(_BF16)

    def pair_dot(lhs, rhs):
        """``[x1 @ y1 | x2 @ y2]`` of two pairs of C x C matrices at
        ``HIGHEST``'s accuracy: hi.hi + hi.mid + mid.hi + mid.mid + hi.lo +
        lo.hi, accumulated in float32, two products a pass."""
        out = jnp.dot(lhs, rhs, preferred_element_type=f32)
        return jnp.where(first, out[:c], out[c:])

    def pair(j, carry):
        heads = (2 * j, 2 * j + 1)
        ats = [pl.ds(pl.multiple_of(h * _LANES, _LANES), _LANES)
               for h in heads]
        q, k, g = ([ref[0, :, at].astype(f32) for at in ats]
                   for ref in (q_ref, k_ref, g_ref))
        if q_scale is not None:
            q = [l2norm(t) * q_scale for t in q]
            k = [l2norm(t) for t in k]
        beta = [jnp.sum(jnp.where(beta_lanes == first_head + h, beta_all,
                                  0.0), axis=1, keepdims=True)
                for h in heads]  # (C, 1) each
        # the running sum down the chunk, both heads at once
        g_cum = jnp.concatenate(g, axis=1)
        for step in (1, 2, 4, 8, 16, 32):
            g_cum = g_cum + jnp.where(scan_rows >= step,
                                      pltpu.roll(g_cum, step, 0), 0.0)
        g_cum = [g_cum[:, :_LANES], g_cum[:, _LANES:]]

        # inside a sub-block, a channel at a time: channels down the
        # sublanes, the two heads' tokens along the lanes. Diagonal d (token
        # t against t - d) is k[t - d] times the decays of the d tokens up
        # to t, one more a step, each at most 1; summed over the channels it
        # goes to its earlier token's column
        across = lambda two: jnp.concatenate(two, axis=0).T
        a_x, k_x, q_x = jnp.exp(across(g)), across(k), across(q)

        def diagonal(d, carry):
            p, kk, qk = carry
            back = (_LANES - d) % _LANES
            kk_d = pltpu.roll(jnp.sum(k_x * p, 0, keepdims=True), back, 1)
            qk_d = pltpu.roll(jnp.sum(q_x * p, 0, keepdims=True), back, 1)
            here = skew == d
            return (a_x * pltpu.roll(p, 1, 1), jnp.where(here, kk_d, kk),
                    jnp.where(here, qk_d, qk))

        zeros = jnp.zeros((sub, _LANES), f32)
        _, kk_diag, qk_diag = lax.fori_loop(0, sub, diagonal,
                                            (k_x, zeros, zeros))

        kk_rows, qk_rows = [], []
        for b in range(c // sub):
            lo = b * sub
            mine = sub_col // sub == b
            kk = jnp.where(mine, kk_diag, 0.0)
            qk = jnp.where(mine, qk_diag, 0.0)
            if b:
                # between sub-blocks, against the running sum at this
                # block's start: both exponents are at most 0 (rows from
                # ``lo`` on are masked). One product for the pair: a head's
                # rows against its own columns
                xs, rights = [], []
                for n in (0, 1):
                    ref = g_cum[n][lo - 1:lo]
                    left = jnp.exp(g_cum[n][lo:lo + sub] - ref)
                    xs += [k[n][lo:lo + sub] * left, q[n][lo:lo + sub] * left]
                    rights.append(
                        k[n] * jnp.exp(jnp.minimum(ref - g_cum[n], 0.0)))
                xh, xm, xl = _parts(jnp.concatenate(xs, axis=0))
                rh, rm, rl = _parts(jnp.concatenate(rights, axis=0))
                off = lax.dot_general(
                    jnp.concatenate([xh, xh, xm, xh, xl, xm],
                                    axis=1).astype(_BF16),
                    jnp.concatenate([rh, rm, rh, rl, rh, rm],
                                    axis=1).astype(_BF16),
                    _NT, preferred_element_type=f32)
                earlier = sub_col < lo
                kk = jnp.where(earlier, jnp.where(
                    sub_first, off[:sub], off[2 * sub:3 * sub]), kk)
                qk = jnp.where(earlier, jnp.where(
                    sub_first, off[sub:2 * sub], off[3 * sub:]), qk)
            kk_rows.append(kk)
            qk_rows.append(qk)
        a_qk = jnp.concatenate(qk_rows, axis=0)
        a = jnp.where(col < rows, jnp.concatenate(kk_rows, axis=0),
                      0.0) * jnp.where(first, beta[0], beta[1])

        # (I + a)^-1: the four leaves on the diagonal at once, as the finite
        # product; then by halves, -T2 a21 T1 as T - (T a_off) T
        leaf = jnp.where(same16, a, 0.0)
        inv = (rows == col).astype(f32) - leaf
        power = _parts(-leaf)
        for _ in range(3):  # spans 2, 4, 8
            power = _parts(pair_dot(_lhs(power), _rhs(power)))
            inv = inv + pair_dot(_lhs(_parts(inv)), _rhs(power))
        for under in (jnp.where(same32 & ~same16, a, 0.0),
                      jnp.where(same32, 0.0, a)):
            t = _parts(inv)
            ta = pair_dot(_lhs(t), _rhs(_parts(under)))
            inv = inv - pair_dot(_lhs(_parts(ta)), _rhs(t))
        inv = _lhs(_parts(inv))

        # (float32 operands, which no trace of the trunk asks for, at full
        # accuracy: a probe's way to read the chunk's own arithmetic)
        mm = lambda x, y, dims: lax.dot_general(
            x.astype(dtype), y.astype(dtype), dims,
            precision=HI if dtype == jnp.float32 else None,
            preferred_element_type=f32)
        for n, h in enumerate(heads):
            decay = jnp.exp(g_cum[n])
            v = v_ref[0, :, ats[n]].astype(f32)
            w = jnp.dot(inv[n * c:(n + 1) * c], _rhs(_parts(jnp.concatenate(
                [v * beta[n], k[n] * decay * beta[n]], axis=1))),
                preferred_element_type=f32)
            w_v, w_k = w[:, :_LANES], w[:, _LANES:]
            s_t = state[h]
            s_in = s_t.astype(dtype)
            u = w_v - mm(w_k, s_in, _NT)
            a_n = a_qk if n == 0 else pltpu.roll(a_qk, c, 1)
            o = mm(q[n] * decay, s_in, _NT) + mm(
                a_n[:, :c], u, (((1,), (0,)), ((), ())))
            if norm_eps is not None:
                o = rms_norm(o, w_ref[...], norm_eps)
            o_ref[0, :, ats[n]] = o
            g_last = g_cum[n][c - 1:c]
            state[h] = s_t * jnp.exp(g_last) + mm(
                u, k[n] * jnp.exp(g_last - g_cum[n]), _TN)
        return carry

    lax.fori_loop(0, hb // 2, pair, 0)


def _heads_per_step(heads: int) -> int:
    """The most heads a grid step takes, up to ``_HB``, in pairs, that
    divide the model's; 0 where the heads do not pair."""
    return max((n for n in range(2, _HB + 1, 2) if heads % n == 0),
               default=0)


@functools.partial(jax.jit, static_argnames=("dtype", "q_scale", "norm_eps"))
def _kda_chunk_fwd_impl(q, k, v, g, beta, weight, *, dtype, q_scale,
                        norm_eps):
    b, s, h, d = q.shape
    hb = _heads_per_step(h)
    flat = lambda t: t.reshape(b, s, h * d)
    block = pl.BlockSpec((1, _CHUNK, hb * d), lambda i, j, n: (i, n, j))
    f32 = jnp.float32
    o = pl.pallas_call(
        functools.partial(_kda_chunk_body, hb=hb, dtype=dtype,
                          q_scale=q_scale, norm_eps=norm_eps),
        grid=(b, h // hb, s // _CHUNK),
        in_specs=[block, block, block, block,
                  pl.BlockSpec((1, _CHUNK, h), lambda i, j, n: (i, n, 0)),
                  pl.BlockSpec((1, d), lambda i, j, n: (0, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), f32),
        scratch_shapes=[pltpu.VMEM((hb, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(*(flat(t if q_scale is not None else t.astype(f32)) for t in (q, k)),
      flat(v.astype(dtype)), flat(g.astype(f32)), beta.astype(f32),
      weight.astype(f32).reshape(1, d))
    return o.reshape(b, s, h, d)


def kda_chunk_kernel(q, k, v, g, beta, dtype=jnp.bfloat16, q_scale=None,
                     out_norm=None):
    """:func:`kda_chunked` at ``chunk=64, sub=16`` as one Pallas TPU kernel.
    Shapes as there, with the sequence a multiple of 64 and ``dk == dv ==
    128`` (:func:`kda_formulation`); q, k, g are read as float32, v in
    ``dtype``, each where the projections left it. The per-head norms on
    either side of the recurrence can ride in the kernel, a chunk at a time,
    because a (B, S, H) factor broadcast over d is, for the program around
    it, a pass over HBM and a relayout besides: with ``q_scale`` the kernel
    takes q and k as the convolutions left them and computes ``l2norm(q) *
    q_scale`` and ``l2norm(k)`` itself; with ``out_norm = (weight, eps)`` it
    returns ``rms_norm(o, weight, eps)``. Differentiable by recomputing
    through :func:`kda_chunked`."""
    weight, eps = out_norm if out_norm is not None else (
        jnp.ones((v.shape[-1],), jnp.float32), None)
    return _kda_kernel_vjp(q, k, v, g, beta, weight, dtype, q_scale, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda_kernel_vjp(q, k, v, g, beta, weight, dtype, q_scale, norm_eps):
    return _kda_chunk_fwd_impl(q, k, v, g, beta, weight, dtype=dtype,
                               q_scale=q_scale, norm_eps=norm_eps)


def _kda_vjp_fwd(q, k, v, g, beta, weight, dtype, q_scale, norm_eps):
    return _kda_chunk_fwd_impl(
        q, k, v, g, beta, weight, dtype=dtype, q_scale=q_scale,
        norm_eps=norm_eps), (q, k, v, g, beta, weight)


def _kda_vjp_bwd(dtype, q_scale, norm_eps, res, ct):
    def chunked(q, k, v, g, beta, weight):
        if q_scale is not None:
            q, k = l2norm(q) * q_scale, l2norm(k)
        o = kda_chunked(q, k, v, g, beta, dtype=dtype)
        return o if norm_eps is None else rms_norm(o, weight, norm_eps)

    return jax.vjp(chunked, *res)[1](ct)


_kda_kernel_vjp.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


@mosaic_gate
def kda_chunk_ok(dk: int, hb: int) -> bool:
    """Compiled self-check of :func:`kda_chunk_kernel` as the mixer calls
    it, at ``hb`` heads of ``dk``, once a process: two chunks of a batch of
    two (the state is carried, and reset), a decay near 0 in one head and
    near 1 in another, against the token recurrence. One program, and no
    second oracle: a process pays this at every start (``chip_smoke.py``
    holds the kernel to :func:`kda_chunked` at the backbone's shape)."""
    from tmr_tpu.diagnostics import gate_refused, run_outside_trace

    config = {"dk": dk, "hb": hb}
    if jax.default_backend() != "tpu":
        return gate_refused(
            "kda_chunk_ok", f"backend {jax.default_backend()!r} != 'tpu'",
            "backend", config)

    @jax.jit
    def gap():
        ks = jax.random.split(jax.random.key(0), 6)
        shape = (2, 2 * _CHUNK, hb, dk)
        q, k, v = (jax.random.normal(key, shape).astype(_BF16)
                   for key in ks[:3])
        decay = jnp.asarray([1e-9, 1.0 - 1e-6, 0.5, 0.97])[
            jnp.arange(hb) % 4][:, None]
        g = jnp.log(decay) * jnp.exp(0.3 * jax.random.normal(ks[3], shape))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
        weight = 1.0 + 0.1 * jax.random.normal(ks[5], (dk,))
        got = kda_chunk_kernel(q, k, v, g, beta, _BF16, dk ** -0.5,
                               (weight, 1e-5))
        want = rms_norm(kda_recurrent(l2norm(q) * dk ** -0.5, l2norm(k), v,
                                      g, beta), weight, 1e-5)
        return jnp.abs(got - want).max() / jnp.abs(want).max()

    try:
        widest = run_outside_trace(lambda: float(gap()), "kda_chunk_ok")
    except Exception as e:  # Mosaic's refusals included
        return gate_refused("kda_chunk_ok", str(e)[:500], "exception", config,
                            exception=type(e).__name__)
    # bfloat16 operands against a float32 recurrence: 0.004 on the chip
    if not widest < 2e-2:
        return gate_refused(
            "kda_chunk_ok", f"widest gap to the token recurrence {widest:.3g} "
            "of its range", "forward-mismatch", config)
    return True


def kda_formulation(seq: int, dk: int, dv: int, dtype, heads: int = _HB) -> str:
    """What the recurrence traces with, by what can be observed: on a TPU in
    bfloat16, at a length that is whole chunks, heads that are one 128-lane
    slab (``dk == dv == 128``) and pair, where the kernel's self-check says
    yes (it says no inside a trace XLA partitions), the Pallas kernel
    (``chunk_kernel``); else ``chunked_xla``, :func:`kda_chunked`."""
    hb = _heads_per_step(heads)
    if (seq % _CHUNK == 0 and dk == dv == _LANES and dtype == jnp.bfloat16
            and hb and jax.default_backend() == "tpu"
            and kda_chunk_ok(dk, hb)):
        return "chunk_kernel"
    return "chunked_xla"

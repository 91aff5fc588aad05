"""The Mamba-2 state-space recurrence (state-space duality, arXiv
2405.21060): a scalar decay a head, and ``B`` and ``C`` shared by all the
heads of a group.

Per head, with the state ``H`` (P x N), ``H_0 = 0``, step size
``Delta_t > 0``, decay ``a_t = exp(-Delta_t A)`` in (0, 1) one value a
head, ``B_t``, ``C_t`` (N,) of the head's group and ``u_t`` (P,)::

    H_t = a_t H_{t-1} + Delta_t u_t B_t^T
    y_t = H_t C_t + D u_t

:func:`ssd_recurrent` is that recurrence, one token a step: the definition.
:func:`ssd_chunked` is what a program runs. Inside a chunk of ``Q`` tokens,
with ``G_t`` the running sum of ``log a`` from the chunk's start and
``L_ti = exp(G_t - G_i)`` for ``i <= t`` (0 above the diagonal)::

    Y     = (L o (C B^T)) (Delta U) + exp(G) C H_0 + D U
    H_end = exp(G_end) H_0 + sum_i exp(G_end - G_i) Delta_i u_i B_i^T

Every exponent is at most 0: no ``exp(-G)`` is taken. ``C B^T`` is one
(Q x Q) product a chunk and group, shared by the group's heads; the mask
``L`` is a head's own, and is only ever formed for one chunk at a time (all
heads of a chunk of 256 are 128 x 256 x 256 float32 = 34 MB an image; of all
chunks at once a gigabyte a step). The decays, ``Delta`` and the state are
float32; the products take their operands in ``dtype`` and accumulate in
float32. The result does not depend on the chunk.

:func:`ssd_formulation` says what a trace takes: one answer today.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def ssd_recurrent(u, delta, a, b, c, d):
    """The token recurrence in float32. ``u`` (B, S, H, P), ``delta``
    (B, S, H) the step sizes (after their softplus), ``a`` (H,) positive
    (``exp(A_log)``), ``b``, ``c`` (B, S, G, N) with ``H`` a multiple of
    ``G`` (head ``h`` reads group ``h // (H / G)``), ``d`` (H,). Returns
    ``y`` (B, S, H, P) float32."""
    f32 = jnp.float32
    u, delta, a, b, c, d = (t.astype(f32) for t in (u, delta, a, b, c, d))
    bsz, _, h, p = u.shape
    rep = h // b.shape[2]
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)

    def step(state, t):
        u_t, dt_t, b_t, c_t = t
        decay = jnp.exp(-dt_t * a)
        state = (state * decay[..., None, None]
                 + (dt_t[..., None] * u_t)[..., None] * b_t[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI)
        return state, y + d[:, None] * u_t

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (u, delta, b, c))
    init = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    return jnp.moveaxis(lax.scan(step, init, xs)[1], 0, 1)


def hand_over(state, decay_end, local):
    """The state a chunk hands to the next: what it was handed, decayed
    over the whole chunk, and what the chunk's own tokens left. ``state``,
    ``local`` (B, H, P, N), ``decay_end`` (B, H), all float32."""
    return state * decay_end[..., None, None] + local


def ssd_chunked(u, delta, a, b, c, d, chunk: int = 256, dtype=jnp.float32):
    """The chunked form: equal to :func:`ssd_recurrent`. Shapes as there;
    returns ``y`` (B, S, H, P) in ``dtype``. One chunk a ``lax.scan`` step,
    all heads in it. A sequence that is no multiple of ``chunk`` is padded
    with tokens of step size 0, which leave the state as it is."""
    f32 = jnp.float32
    bsz, s, h, p = u.shape
    g, n = b.shape[2:]
    rep = h // g
    nc = -(-s // chunk)
    pad = nc * chunk - s

    def chunks(t):  # (B, S, ...) -> (nc, B, Q, ...)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((bsz, nc, chunk) + t.shape[2:]), 1, 0)

    delta = delta.astype(f32)
    a, d = a.astype(f32), d.astype(f32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    ones = lower.astype(f32)

    def step(state, t):
        u_n, dt_n, b_n, c_n = t  # (B, Q, H, P), (B, Q, H), 2 x (B, Q, G, N)
        # the running sum of log a inside the chunk, as a product with the
        # lower triangle of ones (ops/kda.py: a scan down a tiled operand is
        # the slower way)
        g_head = jnp.einsum("ti,bih->bht", ones, -dt_n * a, precision=HI)
        g_cum = jnp.moveaxis(g_head, 1, 2)  # (B, Q, H)
        du = (dt_n[..., None] * u_n.astype(f32)).astype(dtype)
        cb = jnp.einsum("btgn,bign->bgti", c_n, b_n,
                        preferred_element_type=f32)
        # L: a head's own mask; exponents at most 0 on and under the diagonal
        diff = g_head[..., :, None] - g_head[..., None, :]  # (B, H, t, i)
        mask = jnp.exp(jnp.where(lower, diff, -jnp.inf)).reshape(
            bsz, g, rep, chunk, chunk)
        m = (mask * cb[:, :, None]).astype(dtype).reshape(
            bsz, h, chunk, chunk)
        y = jnp.einsum("bhti,bihp->bthp", m, du, preferred_element_type=f32)
        # what the chunk was handed, read by its tokens: exp(G) C H_0
        read = jnp.einsum(
            "btgn,bgrpn->btgrp", c_n,
            state.astype(dtype).reshape(bsz, g, rep, p, n),
            preferred_element_type=f32).reshape(bsz, chunk, h, p)
        y = y + jnp.exp(g_cum)[..., None] * read
        y = y + d[:, None] * u_n.astype(f32)
        # what its own tokens leave: sum_i exp(G_end - G_i) Delta_i u_i B_i^T
        g_end = g_cum[:, -1, :]  # (B, H)
        out = (jnp.exp(g_end[:, None, :] - g_cum)[..., None]
               * du.astype(f32)).astype(dtype)
        local = jnp.einsum(
            "bigrp,bign->bgrpn", out.reshape(bsz, chunk, g, rep, p), b_n,
            preferred_element_type=f32).reshape(bsz, h, p, n)
        return hand_over(state, jnp.exp(g_end), local), y.astype(dtype)

    xs = (chunks(u.astype(dtype)), chunks(delta), chunks(b.astype(dtype)),
          chunks(c.astype(dtype)))
    init = jnp.zeros((bsz, h, p, n), f32)
    y = lax.scan(step, init, xs)[1]  # (nc, B, Q, H, P)
    return jnp.moveaxis(y, 0, 1).reshape(bsz, nc * chunk, h, p)[:, :s]


def ssd_formulation(seq: int, heads: int, head_dim: int, state: int,
                    dtype) -> str:
    """What the state-space recurrence traces with (counter
    ``trunk.ssm.<formulation>``). One answer today: :func:`ssd_chunked`,
    plain XLA; a kernel that keeps a chunk's mask and the state on the chip
    gets its name and its gate (``diagnostics.mosaic_gate``) here."""
    return "chunked_xla"

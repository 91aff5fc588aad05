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

:func:`ssd_chunk_kernel` is the same chunked form as one Pallas TPU kernel,
with the mixer's gated norm after it: it reads u, B, C and z where the
convolution and ``in_proj`` wrote them and writes ``rmsnorm(y * silu(z))``
flat, what ``out_proj`` reads. :func:`ssd_formulation` says which of the two
a trace takes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import mosaic_gate
from tmr_tpu.ops.kda import rms_norm

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


def _operands(xbc, zx, heads: int, head_dim: int):
    """u (B, S, H, P), B and C (B, S, 1, N) and z (B, S, H x P) out of the
    convolution's ``[u | B | C]`` and ``in_proj``'s ``[z | ...]``."""
    bsz, s, _ = xbc.shape
    inner = heads * head_dim
    n = (xbc.shape[-1] - inner) // 2
    return (xbc[..., :inner].reshape(bsz, s, heads, head_dim),
            xbc[..., inner:inner + n].reshape(bsz, s, 1, n),
            xbc[..., inner + n:inner + 2 * n].reshape(bsz, s, 1, n),
            zx[..., :inner])


def ssd_gated_chunked(xbc, zx, delta, a, d, weight, eps: float, heads: int,
                      head_dim: int, chunk: int = 256):
    """What :func:`ssd_chunk_kernel` computes, as XLA: :func:`ssd_chunked`
    in ``xbc``'s dtype, then ``rms_norm(y * silu(z)) * weight`` in float32,
    rounded to that dtype (``SSMMixer``'s ``chunked_xla`` path). Shapes as
    there."""
    dtype = xbc.dtype
    u, b, c, z = _operands(xbc, zx, heads, head_dim)
    y = ssd_chunked(u, delta, a, b, c, d, chunk, dtype)
    y = y.reshape(z.shape).astype(jnp.float32)
    return rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), weight,
                    eps).astype(dtype)


# --------------------------------------------------------------------------
# The chunked form as one kernel (formulation ``chunk_kernel``).
#
# XLA runs ``ssd_chunked`` on u and y as (B, S, H, 64): a head of 64 takes a
# whole 128-lane tile, so every pass over them moves twice their bytes, and
# the gated norm and ``out_proj`` read y through a relayout to (B, S, H x 64)
# (PERF.md, section 5). Here one grid step is one chunk of one image, all
# heads: u, B and C are blocks of the convolution's (B, S, H x P + 2 N)
# output, z a block of ``in_proj``'s, Delta (B, S, H) float32 with the heads
# on the lanes; the output is (B, S, H x P), gated and normalised. Nothing
# is sliced or reshaped around the kernel.
#
# - The heads go two at a time, a 128-lane slab of u, z and y each: a pair's
#   states (P x N each) are one (128, N) float32 tile stack in VMEM scratch,
#   the first head's rows over the second's, carried from chunk to chunk.
#   ``C H^T`` and the state's update ``(w u)^T B`` are one product for the
#   pair; the chunk's ``(L o C B^T) (Delta u)`` is one product whose
#   contraction holds the pair's two masks side by side, each against its
#   own head's half of the slab.
# - The running sums G of the chunk are one scan down the sublanes for all
#   heads; a head's column of them is a masked sum over lanes, its row a
#   row of their transpose, and its mask ``exp(G_t - G_i)`` (exponents at
#   most 0) lives in VMEM only.
# - The masks are formed ``_SUB`` rows at a time: a block's own tokens by
#   its (sub x sub) masks, the earlier blocks' through G at the last of them,
#   ``exp(G_t - R) exp(R - G_i)`` (both exponents at most 0), so a chunk of
#   256 takes about three quarters of the exponentials of whole masks.
# - The gate ``y * silu(z)`` is kept in float32 scratch, its squares summed
#   by row as the pairs go; the last pass over the slabs normalises and
#   rounds once.
# - The hand-over of the state is :func:`hand_over`, looked up on this
#   module when the kernel is traced (a patch of it reaches the kernel).
# - A sequence that is no multiple of the chunk ends in a partial block:
#   its rows past the sequence are read as zeros (u, B, C, Delta), which
#   leave the state and every earlier row as they are.
# --------------------------------------------------------------------------
_LANES = 128
_SUB = 128
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _ssd_kernel_body(u_ref, b_ref, c_ref, z_ref, dt_ref, a_ref, d_ref, w_ref,
                     o_ref, state, gated, g_rows, *, head_dim: int,
                     seq: int, eps: float, hand, sub: int):
    """One chunk of one image, all heads. Refs: u, z, out (1, Q, H x P);
    b, c (1, Q, N); dt (1, Q, H) float32; a (1, H), d and w (1, H x P)
    float32. Scratch: ``state`` (H / 2, 2 P, N) float32, a pair's states
    stacked; ``gated`` (Q, H x P) float32; ``g_rows`` (H rounded up to 8,
    Q) float32."""
    f32, dtype = jnp.float32, u_ref.dtype
    q, heads = dt_ref.shape[1:]
    inner = u_ref.shape[2]
    p = head_dim

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    iota = lambda shape, dim: lax.broadcasted_iota(jnp.int32, shape, dim)
    valid = seq - pl.program_id(1) * q  # rows of this chunk in the sequence
    here = iota((q, _LANES), 0) < valid
    keep = lambda t: jnp.where(iota(t.shape, 0) < valid, t, 0)
    b_n, c_n = keep(b_ref[0]).astype(dtype), keep(c_ref[0]).astype(dtype)
    dt = keep(dt_ref[0])
    prec = lax.Precision.HIGHEST if dtype == f32 else None
    mm = lambda x, y, dims: lax.dot_general(
        x, y, dims, precision=prec, preferred_element_type=f32)

    # the running sums of log a down the chunk, all heads: a scan down the
    # sublanes, whose rolls cost nothing
    g = -dt * a_ref[...]
    rows = iota((q, heads), 0)
    step = 1
    while step < q:
        g = g + jnp.where(rows >= step, pltpu.roll(g, step, 0), 0.0)
        step *= 2
    g_rows[pl.ds(0, heads), :] = g.T
    cb = mm(c_n, b_n, _NT)  # (Q, Q), shared by every head
    lower = iota((sub, sub), 0) >= iota((sub, sub), 1)
    head_lanes = iota((q, heads), 1)
    first = iota((q, _LANES), 1) < p
    first_sub = iota((sub, _LANES), 1) < p

    def column(t, h):  # head h's (Q, 1) of a (Q, H) array
        return jnp.sum(jnp.where(head_lanes == h, t, 0.0), 1, keepdims=True)

    def pair(j, ss):
        heads2 = (2 * j, 2 * j + 1)
        at = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
        g_col = [column(g, h) for h in heads2]
        g_pair = jnp.where(first, g_col[0], g_col[1])  # (Q, 128)
        dt_pair = jnp.where(first, column(dt, heads2[0]), column(dt, heads2[1]))
        u = jnp.where(here, u_ref[0, :, at].astype(f32), 0.0)
        du = (dt_pair * u).astype(dtype)
        # a head's row of G: a sum down the aligned eight rows that hold it
        # (a row read at any other offset cannot be cut into blocks)
        eight = g_rows[pl.ds(pl.multiple_of(j // 4 * 8, 8), 8), :]
        g_row = [jnp.sum(jnp.where(iota((8, q), 0) == h % 8, eight, 0.0), 0,
                         keepdims=True) for h in heads2]
        ys = []
        for lo in range(0, q, sub):
            r = slice(lo, lo + sub)
            # a block's own tokens: L o C B^T a head, side by side,
            # [M_0 | M_1] against the pair's halves of Delta u, each zeroed
            # in the other's lanes
            masks = [jnp.where(lower, jnp.exp(jnp.minimum(
                g_col[n][r] - g_row[n][:, r], 0.0)), 0.0) * cb[r, r]
                for n in (0, 1)]
            y = mm(jnp.concatenate(masks, axis=1).astype(dtype),
                   jnp.concatenate([jnp.where(first_sub, du[r], 0),
                                    jnp.where(first_sub, 0, du[r])], axis=0),
                   _NN)
            if lo:
                # the earlier blocks' tokens, through G at the last of them:
                # exp(G_t - G_i) = exp(G_t - R) exp(R - G_i), both exponents
                # at most 0, and C B^T needs no mask
                ref = g_pair[lo - 1:lo]
                early = (jnp.exp(ref - g_pair[:lo])
                         * du[:lo].astype(f32)).astype(dtype)
                y = y + jnp.exp(g_pair[r] - ref) * mm(
                    cb[r, :lo].astype(dtype), early, _NN)
            ys.append(y)
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=0)
        # what the chunk was handed, read by its tokens: exp(G) C H_0
        s_pair = state[j]
        y = y + jnp.exp(g_pair) * mm(c_n, s_pair.astype(dtype), _NT)
        y = y + d_ref[:, at] * u
        # what its own tokens leave: sum_i exp(G_end - G_i) Delta_i u_i B_i^T
        g_end = g_pair[q - 1:q]
        out = (jnp.exp(g_end - g_pair) * du.astype(f32)).astype(dtype)
        local = mm(out, b_n, _TN)  # (2 P, N)
        one = lambda t: t.reshape(1, 1, p, t.shape[-1])
        state[j] = jnp.concatenate([
            hand(one(s_pair[n * p:(n + 1) * p]), jnp.exp(g_col[n][q - 1:q]),
                 one(local[n * p:(n + 1) * p])).reshape(p, -1)
            for n in (0, 1)], axis=0)
        # gated, in float32: y is not rounded ahead of the norm
        x = y * jax.nn.silu(z_ref[0, :, at].astype(f32))
        gated[:, at] = x
        return ss + x * x

    ss = lax.fori_loop(0, heads // 2, pair, jnp.zeros((q, _LANES), f32))
    scale = lax.rsqrt(jnp.sum(ss, 1, keepdims=True) / inner + eps)

    def write(j, carry):
        at = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
        o_ref[0, :, at] = (gated[:, at] * scale * w_ref[:, at]).astype(
            o_ref.dtype)
        return carry

    lax.fori_loop(0, inner // _LANES, write, 0)


def ssd_kernel_supported(heads: int, head_dim: int, state: int, groups: int,
                         chunk: int) -> bool:
    """Heads of 64 in pairs (a pair is one 128-lane slab), a state of 128
    (one lane tile; B and C start on a tile), one group, and a chunk of whole
    sublane tiles up to 256 (at 128 heads a step then asks about 60 MiB of
    VMEM)."""
    return (head_dim == _LANES // 2 and heads % 2 == 0 and state == _LANES
            and groups == 1 and chunk % 8 == 0 and 8 <= chunk <= 256)


def _vmem_bytes(chunk: int, heads: int, inner: int, itemsize: int) -> int:
    """Two buffers of every block, the scratch, and room for a pair's
    (Q, 2 Q) masks and their temporaries."""
    blocks = chunk * (3 * inner * itemsize + 2 * _LANES * itemsize
                      + max(heads, _LANES) * 4)
    scratch = inner * _LANES * 2 * 4 + chunk * inner * 4 + heads * chunk * 4
    return 2 * blocks + scratch + 16 * chunk * chunk * 4 + (8 << 20)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _ssd_kernel_fwd_impl(xbc, zx, delta, a, d, weight, heads, head_dim, chunk,
                         eps, hand):
    # a jit of its own: a trunk's state-space layers are one shape, and share
    # one traced and lowered function in the enclosing program
    bsz, s, width = xbc.shape
    inner = heads * head_dim
    n = (width - inner) // 2
    if not (ssd_kernel_supported(heads, head_dim, n, 1, chunk)
            and width == inner + 2 * n and zx.shape[:2] == (bsz, s)
            and zx.shape[-1] >= inner and delta.shape == (bsz, s, heads)):
        raise ValueError(
            f"xbc {xbc.shape}, zx {zx.shape}, delta {delta.shape} at {heads} "
            f"heads of {head_dim}, chunks of {chunk}, have no kernel; gate "
            "callers on ssd_formulation()")
    f32 = jnp.float32
    wide = pl.BlockSpec((1, chunk, inner), lambda i, c: (i, c, 0))
    tile = lambda at: pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, at))
    lanes = lambda width: pl.BlockSpec((1, width), lambda i, c: (0, 0))
    per_lane = lambda t: jnp.repeat(t.astype(f32), head_dim).reshape(1, inner)
    return pl.pallas_call(
        functools.partial(_ssd_kernel_body, head_dim=head_dim, seq=s,
                          eps=eps, hand=hand,
                          sub=chunk if chunk % _SUB else _SUB),
        grid=(bsz, -(-s // chunk)),
        in_specs=[wide, tile(inner // n), tile(inner // n + 1), wide,
                  pl.BlockSpec((1, chunk, heads), lambda i, c: (i, c, 0)),
                  lanes(heads), lanes(inner), lanes(inner)],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((bsz, s, inner), xbc.dtype),
        scratch_shapes=[pltpu.VMEM((heads // 2, 2 * head_dim, n), f32),
                        pltpu.VMEM((chunk, inner), f32),
                        pltpu.VMEM((-(-heads // 8) * 8, chunk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(chunk, heads, inner,
                                         xbc.dtype.itemsize)),
        interpret=jax.default_backend() != "tpu",
    )(xbc, xbc, xbc, zx, delta.astype(f32), a.astype(f32).reshape(1, heads),
      per_lane(d), weight.astype(f32).reshape(1, inner))


def ssd_chunk_kernel(xbc, zx, delta, a, d, weight, eps: float, heads: int,
                     head_dim: int, chunk: int = 256):
    """:func:`ssd_gated_chunked` as one Pallas TPU kernel, on the
    convolution's and ``in_proj``'s own outputs: ``xbc`` (B, S, H x P + 2 N)
    ``[u | B | C]`` after the convolution, ``zx`` (B, S, >= H x P) whose
    first H x P columns are z, ``delta`` (B, S, H) the step sizes after
    their softplus, ``a`` (H,) positive, ``d`` (H,), ``weight`` (H x P,) the
    norm's; one group (:func:`ssd_kernel_supported`), any length. Returns
    ``rms_norm(y * silu(z), weight, eps)`` (B, S, H x P) in ``xbc``'s dtype,
    what ``out_proj`` reads; y is not rounded ahead of the gate, where the
    XLA form rounds it to the dtype. Differentiable by recomputing through
    :func:`ssd_gated_chunked`."""
    return _ssd_kernel_vjp(xbc, zx, delta, a, d, weight, heads, head_dim,
                           chunk, eps, hand_over)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _ssd_kernel_vjp(xbc, zx, delta, a, d, weight, heads, head_dim, chunk,
                    eps, hand):
    return _ssd_kernel_fwd_impl(xbc, zx, delta, a, d, weight, heads,
                                head_dim, chunk, eps, hand)


def _ssd_vjp_fwd(xbc, zx, delta, a, d, weight, heads, head_dim, chunk, eps,
                 hand):
    return _ssd_kernel_fwd_impl(xbc, zx, delta, a, d, weight, heads,
                                head_dim, chunk, eps, hand), (
        xbc, zx, delta, a, d, weight)


def _ssd_vjp_bwd(heads, head_dim, chunk, eps, hand, res, ct):
    return jax.vjp(
        lambda *t: ssd_gated_chunked(*t, eps, heads, head_dim, chunk), *res
    )[1](ct)


_ssd_kernel_vjp.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


@mosaic_gate
def ssd_kernel_ok(heads: int, chunk: int) -> bool:
    """Compiled self-check of :func:`ssd_chunk_kernel` as the mixer calls
    it, at ``heads`` heads of 64 on a state of 128, once a machine: a batch
    of two (the state is reset) of a chunk and a half (the state is handed
    over, and the last chunk is partial), step sizes by the family's rule (a
    head's memory from under one token to a thousand), against
    :func:`ssd_recurrent` and the gated norm in float32. One program, its
    inputs drawn on the host (``chip_smoke.py`` holds the kernel to the
    recurrence at the backbone's shape)."""
    import numpy as np

    from tmr_tpu.diagnostics import gate_refused, run_outside_trace

    config = {"heads": heads, "chunk": chunk}
    if jax.default_backend() != "tpu":
        return gate_refused(
            "ssd_kernel_ok", f"backend {jax.default_backend()!r} != 'tpu'",
            "backend", config)
    b, s, p, n, eps = 2, chunk + chunk // 2, _LANES // 2, _LANES, 1e-5
    inner = heads * p

    @jax.jit
    def gap(xbc, z, delta, a, d, weight):
        got = ssd_chunk_kernel(xbc, z, delta, a, d, weight, eps, heads, p,
                               chunk).astype(jnp.float32)
        u, bb, cc, _ = _operands(xbc, z, heads, p)
        y = ssd_recurrent(u, delta, a, bb, cc, d).reshape(b, s, inner)
        want = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), weight, eps)
        return jnp.abs(got - want).max() / jnp.abs(want).max()

    def check():
        rng = np.random.default_rng(0)
        draw = lambda *shape: rng.standard_normal(shape, np.float32)
        return float(gap(
            draw(b, s, inner + 2 * n).astype(jnp.bfloat16),
            draw(b, s, inner).astype(jnp.bfloat16),
            1e-3 * 100.0 ** rng.uniform(size=(b, s, heads)).astype(
                np.float32),
            rng.uniform(1.0, 16.0, heads).astype(np.float32),
            np.ones(heads, np.float32), 1.0 + 0.1 * draw(inner)))

    try:
        widest = run_outside_trace(check, "ssd_kernel_ok")
    except Exception as e:  # Mosaic's refusals included
        return gate_refused("ssd_kernel_ok", str(e)[:500], "exception",
                            config, exception=type(e).__name__)
    # bfloat16 operands against a float32 recurrence
    if not widest < 3e-2:
        return gate_refused(
            "ssd_kernel_ok", f"widest gap to the token recurrence "
            f"{widest:.3g} of its range", "forward-mismatch", config)
    return True


def ssd_formulation(heads: int, head_dim: int, state: int, groups: int,
                    chunk: int, dtype) -> str:
    """What the state-space recurrence and its gated norm trace with
    (counter ``trunk.ssm.<formulation>``), by what can be observed: on a TPU
    in bfloat16, at heads of 64 in pairs, a state of 128, one group and a
    chunk the kernel takes (:func:`ssd_kernel_supported`; any length), where
    the kernel's self-check says yes (it says no inside a trace XLA
    partitions), the Pallas kernel (``chunk_kernel``); else
    ``chunked_xla``, :func:`ssd_chunked` and the norm as XLA."""
    if (dtype == jnp.bfloat16 and jax.default_backend() == "tpu"
            and ssd_kernel_supported(heads, head_dim, state, groups, chunk)
            and ssd_kernel_ok(heads, chunk)):
        return "chunk_kernel"
    return "chunked_xla"

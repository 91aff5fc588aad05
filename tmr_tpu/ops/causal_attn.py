"""Causal softmax attention with no bias and no S x S score tensor
anywhere: latent attention's (a query-key width of 128 + 64 against a value
width of 128) and grouped-query attention's (query heads sharing key-value
heads, of 64 or 128). Softmax in float32, over the true row maximum.
Grouped-query attention also takes block diffusion's block-causal mask
(``diffusion_block`` L: query ``i`` sees key ``j`` where
``j // L <= i // L``); at L = 1 every form is the causal one, traced as it
was before the mask existed. It takes a causal sliding window too
(``window`` W: query ``i`` sees key ``j`` where ``i - W < j <= i``), whose
forms never multiply a key that lies wholly before a row block's windows;
``window`` None is the causal text as it was.

:func:`causal_attention_blocked` is plain XLA: one block of query rows at a
time against the keys at or before it, so the scores of a step are
(block, S) a head at most and the keys after the block are never
multiplied. Its float32 scores live in HBM: a scores product, an
exp-and-sum pass over them and a values product a row block, sixteen times
a layer at 4,096 tokens (8.4 ms a layer and image on the v5e, PERF.md
section 5).

:func:`latent_attention_kernel` is the same mathematics as one Pallas TPU
kernel on the operands as the projections wrote them: ``q`` (B, S, H x 192)
from ``q_b`` / ``q_proj``, ``kv`` (B, S, H x 256) from ``kv_b`` (a head's
256 columns are ``[k_nope | v]``, two lane-aligned slabs) and the one
``k_pe`` (B, S, 64) all heads share, so that the broadcast of ``k_pe`` over
the heads, both concatenates and every (B, S, H, d) relayout go. A query
block's whole row of scores stays in VMEM and a head's keys and values stay
there across its query blocks. :func:`gqa_attention_kernel` is the same
kernel's two passes (:func:`_causal_softmax`) on grouped-query attention's
``q`` (B, S, H x d), ``k`` and ``v`` (B, S, Hkv x d), a key-value slab read
once for all the query heads that share it, with the mixer's q/k norms and
rotary applied inside it where the trunk has them. :func:`mla_formulation` and
:func:`gqa_formulation` say which form a trace takes; the blocked form is
each kernel's fallback, its gate's oracle and its backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import mosaic_gate
from tmr_tpu.ops import rope as rope_ops
from tmr_tpu.ops.kda import rms_norm


def causal_attention_blocked(q, k, v, scale: float, block: int = 256,
                             diffusion_block: int = 1, window=None):
    """``q`` (B, S, H, dqk), ``k`` (B, S, Hkv, dqk), ``v`` (B, S, Hkv, dv)
    -> (B, S, H, dv) in ``v``'s dtype. With fewer key-value heads than query
    heads (grouped-query attention) query head ``j`` reads key-value head
    ``j // (H / Hkv)``; the key-value heads are never written out again.
    ``diffusion_block`` ``L``: the block-causal mask of block diffusion,
    query ``i`` sees key ``j`` where ``j // L <= i // L`` (bidirectional
    within a block of ``L`` tokens, causal across blocks); 1 is causal.
    ``window`` ``W``: the causal sliding window, query ``i`` sees key ``j``
    where ``i - W < j <= i``; a row block multiplies only the keys from its
    first query's window on."""
    if window is not None and diffusion_block != 1:
        raise ValueError("a sliding window under a block-causal mask is not "
                         "written")
    b, s, h, _ = q.shape
    hkv = k.shape[2]
    if hkv == h:
        to_scores, to_out = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
    else:
        q = q.reshape(b, s, hkv, h // hkv, -1)
        to_scores, to_out = "bqgrd,bkgd->bgrqk", "bgrqk,bkgd->bqgrd"
    outs = []
    for lo in range(0, s, block):
        hi = min(lo + block, s)
        # the keys up to the end of the diffusion block that holds the last
        # query of the row block
        end = hi if diffusion_block == 1 else min(
            s, -(-hi // diffusion_block) * diffusion_block)
        # the first key the row block's first query sees
        first = 0 if window is None else max(0, lo - window + 1)
        scores = jnp.einsum(to_scores, q[:, lo:hi], k[:, first:end],
                            preferred_element_type=jnp.float32) * scale
        hidden = (_after(lo, hi, end, diffusion_block) if window is None
                  else _outside(lo, hi, first, end, window))
        probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), axis=-1)
        outs.append(jnp.einsum(to_out, probs.astype(v.dtype),
                               v[:, first:end]))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h, -1)


def _after(lo: int, hi: int, end: int, diffusion_block: int):
    """(hi - lo, end): where key ``j`` lies after query ``lo + i``'s view;
    at ``diffusion_block`` 1 the causal mask as it always was, no divide."""
    if diffusion_block == 1:
        return jnp.arange(end)[None, :] > jnp.arange(lo, hi)[:, None]
    return (jnp.arange(end)[None, :] // diffusion_block
            > jnp.arange(lo, hi)[:, None] // diffusion_block)


def _outside(lo: int, hi: int, first: int, end: int, window: int):
    """(hi - lo, end - first): where key ``first + j`` lies outside query
    ``lo + i``'s sliding window, after it or ``window`` or more before it."""
    col = jnp.arange(first, end)[None, :]
    row = jnp.arange(lo, hi)[:, None]
    return (col > row) | (col <= row - window)


def gqa_attention_blocked(q, k, v, heads: int, kv_heads: int,
                          scale: float, qk_norm=None, theta=None,
                          diffusion_block: int = 1, window=None):
    """:func:`causal_attention_blocked` on the projections' own outputs (the
    kernel's signature, :func:`gqa_attention_kernel`): ``q`` (B, S, H x d),
    ``k`` and ``v`` (B, S, Hkv x d) -> (B, S, H x d). ``qk_norm`` (q's
    weight, k's weight, eps): an RMSNorm over each head's dims on q and on k
    first, and ``theta``: the default rotary on both after it, in float32
    and rounded to the operands' dtype once, as the chip's XLA computes
    ``GQAMixer``'s path (its fusion keeps the norm's output in float32).
    ``window``: the causal sliding window, as
    :func:`causal_attention_blocked`'s."""
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    q, k = q.reshape(b, s, heads, d), k.reshape(b, s, kv_heads, d)
    if qk_norm is not None or theta is not None:
        dtype, (q, k) = q.dtype, (t.astype(jnp.float32) for t in (q, k))
        if qk_norm is not None:
            wq, wk, eps = qk_norm
            q, k = rms_norm(q, wq, eps), rms_norm(k, wk, eps)
        if theta is not None:
            inv_freq = rope_ops.default_inv_freq(d, theta)
            q, k = (rope_ops.rotate(t, jnp.arange(s), inv_freq)
                    for t in (q, k))
        q, k = q.astype(dtype), k.astype(dtype)
    o = causal_attention_blocked(q, k, v.reshape(b, s, kv_heads, d), scale,
                                 diffusion_block=diffusion_block,
                                 window=window)
    return o.reshape(b, s, -1)


def latent_attention_blocked(q, kv, k_pe, heads: int, scale: float,
                             rot=None):
    """:func:`causal_attention_blocked` on the projections' own outputs (the
    kernel's signature, :func:`latent_attention_kernel`): the heads
    unpacked, the "rope" dims of ``q`` turned where ``rot`` is given
    (``ops/rope.py:rotate``; ``k_pe`` arrives turned), ``k_pe`` broadcast
    over the heads and concatenated onto ``k_nope``."""
    b, s, _ = q.shape
    dp = k_pe.shape[-1]
    q = q.reshape(b, s, heads, -1)
    dn = q.shape[-1] - dp
    kv = kv.reshape(b, s, heads, -1)
    if rot is not None:
        q = jnp.concatenate(
            [q[..., :dn], rope_ops.rotate(q[..., dn:], *_rotation(s, rot))],
            -1)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, dp))], -1)
    o = causal_attention_blocked(q, k, kv[..., dn:], scale)
    return o.reshape(b, s, -1)


def _rotation(seq: int, rot):
    """``rot`` (the inverse frequencies as a tuple, so that it hashes, and
    the gain) as ``ops/rope.py:rotate``'s arguments after ``x``."""
    inv_freq, gain = rot
    return jnp.arange(seq), np.asarray(inv_freq, np.float32), gain


# --------------------------------------------------------------------------
# The same as one kernel (formulation ``causal_kernel``).
#
# One grid step is one query block of one head of one image; the grid is
# (image, head, query block) with the query blocks innermost, and the
# head's ``[k_nope | v]`` block and ``k_pe`` do not depend on the query
# block, so Pallas fetches them once a head: every operand crosses HBM
# once.
#
# - scores = q_nope . k_nope + q_pe . k_pe, two products 128 deep: ``k_pe``
#   arrives as ``[k_pe | k_pe]`` and the head's 64 "rope" dims of ``q`` sit
#   in one half of a slab with the other half zeroed.
# - ``q``'s 192 columns a head are not lane-aligned for odd heads, so a
#   step reads its pair of heads' three slabs ``[nope0 | pe0 nope1a |
#   nope1b pe1]`` and picks its own by the head's parity: a select, and for
#   an odd head's ``nope`` one roll by half a slab.
# - the rotation of the query's "rope" dims (``ops/rope.py:tables``,
#   (S, 32) cosines and sines tiled over the slab with the sign of the sine
#   by half) is a multiply-add with the slab's halves of 32 swapped, on the
#   block as it was read: float32, rounded to the operand dtype as
#   ``ops/rope.py:rotate`` does.
# - softmax runs over the query block's whole row of scores, kept in VMEM
#   as float32: the maximum and the sum are folded lane on lane across the
#   key blocks and reduced across lanes once a query block (an online
#   softmax's running maximum costs two reductions and four broadcasts
#   across lanes a key block: PERF.md section 6, PR 32). Only the key blocks
#   up to the diagonal exist (loops with a run-time bound) and only the
#   diagonal block is masked. The probabilities are rounded to the operand
#   dtype for the values product, which accumulates in float32, and their
#   float32 sum divides the accumulated row.
# --------------------------------------------------------------------------
#: the widths the kernel is written for: what both published trunks have
_DN, _DP, _DV = 128, 64, 128
_LANES = 128
#: tokens a query block and a key block hold (PERF.md section 6, PR 34)
_BLOCK = (512, 512)
#: the longest sequence: 72 MiB of the v5e's 128 MiB of VMEM
#: (:func:`_vmem_bytes`)
_MAX_SEQ = 16384
_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b^T


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def key_blocks(iq, bq: int, bk: int, window=None, maximum=max):
    """The key blocks ``[first, end)`` query block ``iq`` visits: causal,
    from the first to the diagonal's last; under a sliding ``window``, from
    the one that holds its first query's first key. ``maximum``: ``max`` of
    Python integers, or ``jnp.maximum`` of a kernel's program id."""
    end = (iq + 1) * (bq // bk)
    if window is None:
        return 0, end
    return maximum(iq * bq - window + 1, 0) // bk, end


def _causal_softmax(iq, scores_of, values_of, s_ref, m_ref, l_ref, acc_ref,
                    *, scale: float, bq: int, bk: int, dtype,
                    diffusion_block: int = 1, window=None):
    """The two passes every causal kernel of this file makes for query
    block ``iq``, whatever its operands: ``scores_of(cols)`` is the block's
    (bq, bk) float32 scores against the keys ``cols`` before ``scale``,
    ``values_of(cols)`` those keys' (bk, 128) values. Returns the block's
    (bq, 128) float32 output, normalised by the float32 sum.
    ``diffusion_block`` ``L`` (a power of two that divides ``bk``): the
    block-causal mask, ``col // L > row // L`` masked. A query block then
    sees the same key blocks as under the causal mask, so only the diagonal
    blocks' mask changes. ``window`` ``W``: the causal sliding window, only
    the key blocks :func:`key_blocks` names are visited (a key block wholly
    before every window of the query block is neither multiplied nor read)
    and each is masked, ``col > row`` or ``col <= row - W``."""
    per = bq // bk

    def folded(op, t):  # (bq, bk) -> (bq, 128), lane on lane
        return functools.reduce(op, [
            t[:, j * _LANES:(j + 1) * _LANES] for j in range(bk // _LANES)])

    def scores(ik, masked):
        cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        s = scores_of(cols) * scale
        if masked:
            row = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if diffusion_block > 1:
                # the first key of col's diffusion block, (col // L) L,
                # passes row exactly where col // L > row // L
                col = col & -diffusion_block
            hidden = col > row
            if window is not None:
                hidden = hidden | (col <= row - window)
            s = jnp.where(hidden, _NEG, s)
        s_ref[:, cols] = s
        m_ref[...] = jnp.maximum(m_ref[...], folded(jnp.maximum, s))

    def before_diagonal(ik, c):
        scores(ik, False)
        return c

    def in_window(ik, c):
        scores(ik, True)
        return c

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    if window is None:
        lax.fori_loop(0, iq * per, before_diagonal, 0)
        for d in range(per):  # the key blocks the diagonal crosses
            scores(iq * per + d, True)
    else:
        first, end = key_blocks(iq, bq, bk, window, jnp.maximum)
        lax.fori_loop(first, end, in_window, 0)
    m_ref[...] = jnp.broadcast_to(
        jnp.max(m_ref[...], axis=1, keepdims=True), m_ref.shape)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def weighted(ik, c):
        cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        p = jnp.exp(s_ref[:, cols] - jnp.tile(m_ref[...], (1, bk // _LANES)))
        l_ref[...] += folded(jnp.add, p)
        acc_ref[...] += _dot(p.astype(dtype), values_of(cols))
        return c

    if window is None:
        first, end = key_blocks(iq, bq, bk)
    lax.fori_loop(first, end, weighted, 0)
    return acc_ref[...] / jnp.sum(l_ref[...], axis=1, keepdims=True)


def _latent_kernel_body(q_ref, kv_ref, kpe_ref, *rest, scale: float,
                        bq: int, bk: int, rope: bool):
    """Refs: q (1, bq, 384) the pair of heads' columns, kv (1, S, 256) the
    head's ``[k_nope | v]``, kpe (1, S, 128) ``[k_pe | k_pe]``, with
    ``rope`` cos and sin (bq, 128) float32, out (1, bq, 128); scratch: the
    block's scores (bq, S) and m, l, acc (bq, 128), float32. ``bk``
    divides ``bq``."""
    if rope:
        cos_ref, sin_ref, *rest = rest
    o_ref, *scratch = rest
    f32, dtype = jnp.float32, q_ref.dtype
    iq = pl.program_id(2)
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    low = lane < _DP
    odd = (jnp.zeros((1, _LANES), jnp.int32) + pl.program_id(1)) % 2 == 1

    slab = lambda i: q_ref[0, :, i * _LANES:(i + 1) * _LANES].astype(f32)
    s0, s1, s2 = slab(0), slab(1), slab(2)
    q_nope = jnp.where(
        odd, pltpu.roll(jnp.where(low, s2, s1), _DP, 1), s0).astype(dtype)
    # the head's "rope" dims: the low half of slab 1 (even), the high half
    # of slab 2 (odd); the other half is zeroed after the rotation
    q_pe = jnp.where(odd, s2, s1)
    if rope:
        first = lane % _DP < _DP // 2
        swapped = jnp.where(first, pltpu.roll(q_pe, _LANES - _DP // 2, 1),
                            pltpu.roll(q_pe, _DP // 2, 1))
        q_pe = q_pe * cos_ref[...] + swapped * sin_ref[...]
    q_pe = jnp.where(low != odd, q_pe, 0.0).astype(dtype)
    o_ref[0] = _causal_softmax(
        iq, lambda cols: (_dot(q_nope, kv_ref[0, cols, :_DN], _NT)
                          + _dot(q_pe, kpe_ref[0, cols, :], _NT)),
        lambda cols: kv_ref[0, cols, _DN:], *scratch, scale=scale, bq=bq,
        bk=bk, dtype=dtype).astype(o_ref.dtype)


def _vmem_bytes(seq: int, bq: int, lanes: int, itemsize: int) -> int:
    """What a kernel asks of VMEM: a query block's row of float32 scores,
    the ``lanes`` columns a token it holds of keys and values twice over
    (Pallas double-buffers them: the latent kernel's ``[k_nope | v]`` and
    doubled ``k_pe``, 384; grouped-query attention's k and v slabs, 256),
    and 16 MiB for the blocks of q, the tables and the output, the three
    float32 rows and Mosaic's own temporaries: 30 / 28 MiB at 4,096 tokens.
    No more than it needs: what a kernel reserves, XLA cannot keep its
    neighbours' operands in."""
    return bq * seq * 4 + 2 * seq * lanes * itemsize + 16 * 1024 * 1024


def latent_kernel_supported(seq: int, heads: int, dn: int, dp: int, dv: int,
                            block=_BLOCK) -> bool:
    """The widths the kernel is written for, heads that pair, and a sequence
    that is whole query blocks (of whole key blocks) and that VMEM holds."""
    bq, bk = block
    return ((dn, dp, dv) == (_DN, _DP, _DV) and heads % 2 == 0
            and bq % bk == 0 and seq % bq == 0 and seq <= _MAX_SEQ)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _latent_kernel_fwd_impl(q, kv, k_pe, heads, scale, rot, block):
    # a jit of its own: a trunk's latent layers are one shape, and share one
    # traced and lowered function in the enclosing program
    b, s, _ = q.shape
    if not (latent_kernel_supported(s, heads, _DN, _DP, _DV, block)
            and q.shape[-1] == heads * (_DN + _DP)
            and kv.shape[-1] == heads * (_DN + _DV)
            and k_pe.shape[-1] == _DP):
        raise ValueError(
            f"q {q.shape}, kv {kv.shape}, k_pe {k_pe.shape} at {heads} heads "
            "have no kernel; gate callers on mla_formulation()")
    bq, bk = block
    operands = [q, kv, jnp.concatenate([k_pe, k_pe], -1)]
    in_specs = [
        pl.BlockSpec((1, bq, 3 * _LANES), lambda i, h, n: (i, n, h // 2)),
        pl.BlockSpec((1, s, _DN + _DV), lambda i, h, n: (i, 0, h)),
        pl.BlockSpec((1, s, _LANES), lambda i, h, n: (i, 0, 0)),
    ]
    if rot is not None:
        cos, sin = rope_ops.tables(*_rotation(s, rot))
        operands += [jnp.tile(cos, (1, 4)),
                     jnp.tile(jnp.concatenate([-sin, sin], -1), (1, 2))]
        in_specs += [pl.BlockSpec((bq, _LANES), lambda i, h, n: (n, 0))] * 2
    row = pltpu.VMEM((bq, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_latent_kernel_body, scale=scale, bq=bq, bk=bk,
                          rope=rot is not None),
        grid=(b, heads, s // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, _DV), lambda i, h, n: (i, n, h)),
        out_shape=jax.ShapeDtypeStruct((b, s, heads * _DV), kv.dtype),
        scratch_shapes=[pltpu.VMEM((bq, s), jnp.float32), row, row, row],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(s, bq, _DN + _DV + _LANES,
                                         q.dtype.itemsize)),
        interpret=jax.default_backend() != "tpu",
    )(*operands)


def latent_attention_kernel(q, kv, k_pe, heads: int, scale: float, rot=None,
                            block=_BLOCK):
    """Causal attention of ``heads`` heads of 128 + 64 query-key and 128
    value dims as one Pallas TPU kernel, on the projections' own outputs:
    ``q`` (B, S, H x 192), a head's columns ``[nope | pe]``; ``kv``
    (B, S, H x 256), a head's ``[k_nope | v]``; ``k_pe`` (B, S, 64), turned
    already where the trunk has rotary; ``rot``, there, YaRN's inverse
    frequencies (a tuple) and gain, by which the kernel turns the query's
    "rope" dims itself. Returns (B, S, H x 128) in ``kv``'s dtype, what
    ``o_proj`` reads. The sequence is whole blocks
    (:func:`latent_kernel_supported`). Same mathematics as
    :func:`latent_attention_blocked`, but for where the probabilities are
    normalised: there ahead of their rounding to the operand dtype, here
    after the values product, by their float32 sum. Differentiable by
    recomputing through the blocked form."""
    return _latent_kernel_vjp(q, kv, k_pe, heads, scale, rot, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _latent_kernel_vjp(q, kv, k_pe, heads, scale, rot, block):
    return _latent_kernel_fwd_impl(q, kv, k_pe, heads, scale, rot, block)


def _latent_vjp_fwd(q, kv, k_pe, heads, scale, rot, block):
    return _latent_kernel_fwd_impl(q, kv, k_pe, heads, scale, rot, block), (
        q, kv, k_pe)


def _latent_vjp_bwd(heads, scale, rot, block, res, ct):
    return jax.vjp(
        lambda *a: latent_attention_blocked(*a, heads, scale, rot), *res
    )[1](ct)


_latent_kernel_vjp.defvjp(_latent_vjp_fwd, _latent_vjp_bwd)


@mosaic_gate
def latent_kernel_ok(rope: bool) -> bool:
    """Compiled self-check of :func:`latent_attention_kernel` as the mixer
    calls it, once a process: two query blocks (a full key block and the
    diagonal), a pair of heads (an even and an odd one), a batch of two and
    a ``k_pe`` of its own, with the rotation where the trunk has it, against
    :func:`latent_attention_blocked`. One program: a process pays this at
    every start (``chip_smoke.py`` holds the kernel to the blocked form at
    the backbone's shape)."""
    from tmr_tpu.diagnostics import gate_refused, run_outside_trace

    config = {"rope": rope}
    if jax.default_backend() != "tpu":
        return gate_refused(
            "latent_kernel_ok", f"backend {jax.default_backend()!r} != 'tpu'",
            "backend", config)

    b, s, h = 2, 2 * _BLOCK[0], 2
    rot = (tuple(10000.0 ** (-np.arange(_DP // 2) * 2.0 / _DP)),
           1.1) if rope else None
    scale = (_DN + _DP) ** -0.5

    @jax.jit
    def gap(q, kv, k_pe):
        got = latent_attention_kernel(q, kv, k_pe, h, scale, rot)
        want = latent_attention_blocked(q, kv, k_pe, h, scale, rot)
        gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        return gap.max() / jnp.abs(want.astype(jnp.float32)).max()

    def check():
        # drawn on the host: a generator in the program is a third of what
        # the program takes to compile
        rng = np.random.default_rng(0)
        draw = lambda width: rng.standard_normal(
            (b, s, width), np.float32).astype(jnp.bfloat16)
        return float(gap(draw(h * (_DN + _DP)), draw(h * (_DN + _DV)),
                         draw(_DP)))

    try:
        widest = run_outside_trace(check, "latent_kernel_ok")
    except Exception as e:  # Mosaic's refusals included
        return gate_refused("latent_kernel_ok", str(e)[:500], "exception",
                            config, exception=type(e).__name__)
    # both sides bfloat16, rounded in other places: under 0.01 on the chip
    if not widest < 2e-2:
        return gate_refused(
            "latent_kernel_ok", f"widest gap to the blocked form "
            f"{widest:.3g} of its range", "forward-mismatch", config)
    return True


def mla_formulation(seq: int, heads: int, dn: int, dp: int, dv: int, dtype,
                    rope: bool = False) -> str:
    """What latent attention traces with, by what can be observed: in
    bfloat16, at query-key widths of 128 + 64 and a value width of 128 (what
    both published trunks have), heads that pair and a sequence of whole
    512-token blocks that VMEM holds (4,096 tokens take 15 MiB; the 1536
    bucket's 9,216 would take 34 MiB and the kernel too, unmeasured; over
    16,384 not), where the kernel's self-check says yes (it says no off a
    TPU and inside a trace XLA partitions), the Pallas kernel
    (``causal_kernel``); else ``blocked_xla``,
    :func:`causal_attention_blocked`."""
    if (dtype == jnp.bfloat16 and latent_kernel_supported(seq, heads, dn, dp,
                                                          dv)
            and latent_kernel_ok(bool(rope))):
        return "causal_kernel"
    return "blocked_xla"


# --------------------------------------------------------------------------
# Grouped-query attention as the same kernel (formulation ``causal_kernel``).
#
# q (B, S, H x d), k and v (B, S, Hkv x d) are read where the projections
# wrote them: no (B, S, H, d) relayout and no key-value head written out
# once a query head. One grid step is one query block of one query head;
# the grid is (image, slab of q, query block, head within the slab), the
# query blocks and the heads of a slab innermost. A head reads the 128-lane
# slab of k and of v that holds its key-value head, which depends on
# neither, so Pallas fetches it once for all the query heads that share it.
#
# - heads of 128: a slab is one head and one key-value head.
# - heads of 64: a slab of q holds a pair of heads, and a slab of k a pair
#   of key-value heads. The step keeps its head's half of the q slab,
#   zeroes the other and rolls it by half a slab where its key-value head
#   sits in the other half of its slab, so that one 128-deep product is the
#   head's scores; the values product's (bq, 128) carries the key-value
#   head's 64 columns in that same half, rolled back to the head's half of
#   the output block, which the pair's two steps share.
# - with the mixer's q/k norms or its rotary (the prologue), q and k are
#   read as the projections wrote them and turned into what the XLA path
#   would have handed over (:func:`_norm_turn`): each head's RMSNorm over
#   its own lanes (two masked sums over lanes where a slab holds two
#   heads), then the rotation as a multiply-add with each head's halves
#   swapped by two lane rolls, against (S, 128) tables of cosines and
#   signed sines that stay in VMEM for the whole call; float32, rounded to
#   the operand dtype once, at the end, as XLA's fusion of the two rounds
#   on the chip. k's whole slab is turned once, at the first step that
#   reads it, and q's block once a slab (by the first head of a pair at
#   heads of 64), into scratches the later steps read, so the slab axis
#   runs in order. Without either, a Python branch keeps the kernel as it
#   was.
# - the scores, the causal softmax and the values are
#   :func:`_causal_softmax`, the latent kernel's two passes; with a
#   diffusion block of L tokens (block diffusion's mask: bidirectional
#   within a block, causal across blocks) only the diagonal blocks' mask
#   differs, since L divides a key block.
# - with a sliding window of W tokens a query block visits only the key
#   blocks from the one that holds its first query's first key
#   (:func:`key_blocks`), each masked on both edges, in blocks of
#   ``_WINDOW_BLOCK``: under a window of at most a key block, a
#   (bq, bq + bk) band of scores, no more.
# --------------------------------------------------------------------------
#: tokens a query block and a key block hold under a sliding window: of
#: the shapes timed on a v5e at a window of 128 over 4,096 tokens, 64 heads
#: on 8 of 128 (PERF.md section 6), the fastest with rotary and without
_WINDOW_BLOCK = (512, 256)


def _block_for(window):
    return _BLOCK if window is None else _WINDOW_BLOCK


def _norm_turn(x, w, cos, sin, *, eps, head_dim: int, dtype):
    """A (rows, 128) slab of heads as ``GQAMixer``'s XLA path leaves it on
    the chip, in float32 holding ``dtype``'s values: with ``eps``, each
    head's RMSNorm weighted by ``w`` (1, 128); with ``cos`` and ``sin``
    (rows, 128), each head's rotation (:func:`_turn_tables`); rounded to
    ``dtype`` once, at the end, as the chip's fusion of the two rounds
    (XLA keeps the norm's output in float32 inside it)."""
    f32 = jnp.float32
    x = x.astype(f32)
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    if eps is not None:
        sq = x * x
        if head_dim == _LANES:
            ss = jnp.sum(sq, axis=1, keepdims=True)
        else:  # the two heads' sums, each over its own half
            low = lane < head_dim
            ss = jnp.where(
                low, jnp.sum(jnp.where(low, sq, 0.0), axis=1, keepdims=True),
                jnp.sum(jnp.where(low, 0.0, sq), axis=1, keepdims=True))
        x = x * lax.rsqrt(ss * (1.0 / head_dim) + eps) * w
    if cos is not None:
        half = head_dim // 2
        # lane i's partner: i + half in a head's first half, i - half after
        partner = jnp.where(lane % head_dim < half,
                            pltpu.roll(x, _LANES - half, 1),
                            pltpu.roll(x, half, 1))
        x = x * cos + partner * sin
    return x.astype(dtype).astype(f32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _turn_tables(seq: int, head_dim: int, theta: float):
    """(cos, sin), each (S, 128) float32, by which the default rotary of
    ``theta`` turns a slab of heads as ``x cos + partner sin``: over each
    head, the cosines of its pairs' angles at each token twice, and their
    sines negated and then not. The unit pairs (1, 0) and (0, 1) turned by
    ``ops/rope.py:rotate``, the very rotation the XLA path applies, give
    both. One program of its own, which the kernel's caller runs as it
    traces (the tables are constants of what it traces)."""
    half = head_dim // 2
    first = jnp.arange(head_dim) < half
    units = jnp.stack([first, ~first]).astype(jnp.float32)
    # (cos, sin) and (-sin, cos) over each head
    turned = rope_ops.rotate(
        jnp.broadcast_to(units, (1, seq, 2, head_dim)), jnp.arange(seq),
        rope_ops.default_inv_freq(head_dim, theta))[0]
    cos = jnp.where(first, turned[:, 0], turned[:, 1])
    sin = jnp.where(first, turned[:, 1], turned[:, 0])
    return tuple(jnp.tile(t, (1, _LANES // head_dim)) for t in (cos, sin))


def _gqa_kernel_body(q_ref, k_ref, v_ref, *rest, scale: float, bq: int,
                     bk: int, head_dim: int, group: int,
                     diffusion_block: int, eps=None, rope: bool = False,
                     window=None):
    """Refs: q (1, bq, 128) a slab of query heads, k and v (1, S, 128) the
    slab that holds the step's key-value head, with ``eps`` w (2, 128) q's
    and k's norm weights over a slab, float32, with ``rope`` the (S, 128)
    cos and sin of :func:`_turn_tables`, out (1, bq, 128); scratch as
    :func:`_latent_kernel_body`'s and, with either, k's slab turned
    (S, 128) and at heads of 64 the q slab turned (bq, 128). ``group``
    query heads share a key-value head; ``window``: the sliding window."""
    prologue = eps is not None or rope
    w_ref = cos_ref = sin_ref = None
    if eps is not None:
        w_ref, *rest = rest
    if rope:
        cos_ref, sin_ref, *rest = rest
    o_ref, *scratch = rest
    f32, dtype = jnp.float32, q_ref.dtype
    iq = pl.program_id(2)
    if prologue:
        if head_dim == _LANES:
            *scratch, kt_ref = scratch
        else:
            *scratch, kt_ref, qt_ref = scratch

        def turn(x, row, rows):  # row 0: q's weight, 1: k's
            return _norm_turn(
                x, None if w_ref is None else w_ref[row:row + 1, :],
                *((None, None) if cos_ref is None
                  else (cos_ref[rows, :], sin_ref[rows, :])),
                eps=eps, head_dim=head_dim, dtype=dtype)

        # the slab's first step: its query heads' first, its first block
        @pl.when((pl.program_id(1) % group == 0) & (iq == 0)
                 & (pl.program_id(3) == 0))
        def _():
            def block(i, c):
                rows = pl.ds(pl.multiple_of(i * bk, bk), bk)
                kt_ref[rows, :] = turn(k_ref[0, rows, :], 1, rows).astype(
                    dtype)
                return c

            lax.fori_loop(0, k_ref.shape[1] // bk, block, 0)

        rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)
        if head_dim == _LANES:
            q = turn(q_ref[0], 0, rows)
        else:  # the pair's first step turns the slab both read
            @pl.when(pl.program_id(3) == 0)
            def _():
                qt_ref[...] = turn(q_ref[0], 0, rows).astype(dtype)

            q = qt_ref[...].astype(f32)
        keys = lambda cols: kt_ref[cols, :]
    else:
        keys = lambda cols: k_ref[0, cols, :]
    if head_dim == _LANES:
        q = q.astype(dtype) if prologue else q_ref[0]
    else:
        vector = lambda n: jnp.zeros((1, _LANES), jnp.int32) + n
        mine = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // head_dim \
            == vector(pl.program_id(3))
        # the step's head and its key-value head lie in different halves
        head = pl.program_id(1) * (_LANES // head_dim) + pl.program_id(3)
        apart = vector(pl.program_id(3)) != vector(head // group) % 2
        q = jnp.where(mine, q if prologue else q_ref[0].astype(f32), 0.0)
        q = jnp.where(apart, pltpu.roll(q, head_dim, 1), q).astype(dtype)
    o = _causal_softmax(
        iq, lambda cols: _dot(q, keys(cols), _NT),
        lambda cols: v_ref[0, cols, :], *scratch, scale=scale, bq=bq, bk=bk,
        dtype=dtype, diffusion_block=diffusion_block, window=window)
    if head_dim == _LANES:
        o_ref[0] = o.astype(o_ref.dtype)
    else:
        o = jnp.where(apart, pltpu.roll(o, head_dim, 1), o)
        o_ref[0] = jnp.where(mine, o.astype(o_ref.dtype), o_ref[0])


def gqa_kernel_supported(seq: int, heads: int, kv_heads: int, head_dim: int,
                         block=None, diffusion_block: int = 1,
                         window=None) -> bool:
    """Heads of 128, or of 64 where the two heads of a slab of q read one
    slab of k and v (a group of one or of an even count); whole slabs of k
    and v; a sequence that is whole query blocks (of whole key blocks, by
    default ``_BLOCK``, under a window ``_WINDOW_BLOCK``) and that VMEM
    holds; a diffusion block that is a power of two and divides the key
    block; a window of at least one token, not under a diffusion block."""
    bq, bk = block or _block_for(window)
    if head_dim not in (64, _LANES) or heads % kv_heads:
        return False
    if window is not None and (window < 1 or diffusion_block != 1):
        return False
    group, n = heads // kv_heads, diffusion_block
    return ((kv_heads * head_dim) % _LANES == 0
            and (head_dim == _LANES or group == 1 or group % 2 == 0)
            and bq % bk == 0 and seq % bq == 0 and seq <= _MAX_SEQ
            and n >= 1 and n & (n - 1) == 0 and bk % n == 0)


@functools.partial(jax.jit, static_argnums=tuple(range(4, 12)))
def _gqa_kernel_fwd_impl(q, k, v, weights, heads, kv_heads, scale, eps,
                         theta, block, diffusion_block, window):
    # a jit of its own: a trunk's attention layers are one shape, and share
    # one traced and lowered function in the enclosing program
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    if not (gqa_kernel_supported(s, heads, kv_heads, d, block,
                                 diffusion_block, window)
            and q.shape[-1] == heads * d and k.shape[-1] == kv_heads * d
            and v.shape == k.shape):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape} at {heads} / {kv_heads} "
            f"heads, diffusion block {diffusion_block}, window {window}, "
            "have no kernel; gate callers on gqa_formulation()")
    bq, bk = block
    per, group = _LANES // d, heads // kv_heads
    slab = pl.BlockSpec((1, bq, _LANES), lambda i, j, n, r: (i, n, j))
    kv = pl.BlockSpec((1, s, _LANES),
                      lambda i, j, n, r: (i, 0, (j * per + r) // group // per))
    # what the whole call reads, fetched once and held once
    whole = lambda rows: pl.BlockSpec((rows, _LANES),
                                      lambda i, j, n, r: (0, 0),
                                      pipeline_mode=pl.Buffered(1))
    row = pltpu.VMEM((bq, _LANES), jnp.float32)
    operands, in_specs = [q, k, v], [slab, kv, kv]
    scratch = [pltpu.VMEM((bq, s), jnp.float32), row, row, row]
    axes, prologue_bytes = ("parallel", "parallel"), 0
    if weights is not None:
        operands.append(jnp.tile(jnp.stack(weights).astype(jnp.float32),
                                 (1, per)))
        in_specs.append(whole(2))
    if theta is not None:
        with jax.ensure_compile_time_eval():
            operands += _turn_tables(s, d, theta)
        in_specs += [whole(s)] * 2
        prologue_bytes += 2 * s * _LANES * 4
    if weights is not None or theta is not None:
        # k's slab turned, and at heads of 64 the pair's q slab
        scratch.append(pltpu.VMEM((s, _LANES), k.dtype))
        prologue_bytes += s * _LANES * k.dtype.itemsize
        if per > 1:
            scratch.append(pltpu.VMEM((bq, _LANES), q.dtype))
        axes = ("parallel", "arbitrary")  # the turned slabs carry over
    return pl.pallas_call(
        functools.partial(_gqa_kernel_body, scale=scale, bq=bq, bk=bk,
                          head_dim=d, group=group,
                          diffusion_block=diffusion_block, eps=eps,
                          rope=theta is not None, window=window),
        grid=(b, heads // per, s // bq, per),
        in_specs=in_specs,
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct(q.shape, v.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=axes + ("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(s, bq, 2 * _LANES,
                                         q.dtype.itemsize) + prologue_bytes),
        interpret=jax.default_backend() != "tpu",
    )(*operands)


def gqa_attention_kernel(q, k, v, heads: int, kv_heads: int, scale: float,
                         qk_norm=None, theta=None, block=None,
                         diffusion_block: int = 1, window=None):
    """Causal grouped-query attention as one Pallas TPU kernel, on the
    projections' own outputs: ``q`` (B, S, H x d), ``k`` and ``v``
    (B, S, Hkv x d), query head ``j`` on key-value head ``j // (H / Hkv)``,
    heads of 128 or of 64 (:func:`gqa_kernel_supported`); with a
    ``diffusion_block`` over 1, block-causal as
    :func:`causal_attention_blocked`; with a ``window`` W, causal over the
    last W tokens, in blocks of ``_WINDOW_BLOCK`` unless ``block`` says
    otherwise. ``qk_norm`` (q's weight, k's weight,
    each of a head's width, and eps) and ``theta``: the mixer's norms and
    default rotary on q and k, which the kernel applies itself. Returns
    (B, S, H x d) in ``v``'s dtype, what ``o_proj`` reads. Same mathematics
    as :func:`gqa_attention_blocked`, but for where the probabilities are
    normalised: there ahead of their rounding to the operand dtype, here
    after the values product, by their float32 sum. Differentiable by
    recomputing through the blocked form."""
    weights, eps = (None, None) if qk_norm is None else (qk_norm[:2],
                                                         qk_norm[2])
    return _gqa_kernel_vjp(q, k, v, weights, heads, kv_heads, scale, eps,
                           theta, block or _block_for(window),
                           diffusion_block, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(4, 12)))
def _gqa_kernel_vjp(q, k, v, weights, heads, kv_heads, scale, eps, theta,
                    block, diffusion_block, window):
    return _gqa_kernel_fwd_impl(q, k, v, weights, heads, kv_heads, scale,
                                eps, theta, block, diffusion_block, window)


def _gqa_vjp_fwd(q, k, v, weights, heads, kv_heads, scale, eps, theta, block,
                 diffusion_block, window):
    return _gqa_kernel_fwd_impl(q, k, v, weights, heads, kv_heads, scale,
                                eps, theta, block, diffusion_block, window), (
        q, k, v, weights)


def _gqa_vjp_bwd(heads, kv_heads, scale, eps, theta, block, diffusion_block,
                 window, res, ct):
    def blocked(q, k, v, weights):
        qk_norm = None if weights is None else (*weights, eps)
        return gqa_attention_blocked(q, k, v, heads, kv_heads, scale,
                                     qk_norm, theta, diffusion_block, window)

    return jax.vjp(blocked, *res)[1](ct)


_gqa_kernel_vjp.defvjp(_gqa_vjp_fwd, _gqa_vjp_bwd)


@mosaic_gate
def gqa_kernel_ok(head_dim: int, diffusion_block: int = 1,
                  qk_norm: bool = False, rope: bool = False,
                  window=None) -> bool:
    """Compiled self-check of :func:`gqa_attention_kernel` as the mixer
    calls it, once a machine: two query blocks (a full key block and the
    diagonal), a batch of two and two slabs of k and v (sixteen query heads:
    groups of eight heads of 128 on their key-value head; of heads of 64,
    groups of four, whose pairs roll and whose pairs do not), masked with
    the ``diffusion_block`` it is asked for, with the q/k norms (weights
    drawn about the benchmark's 1.75) and the rotary (theta 1e6) where
    asked, under the sliding ``window`` where asked (then eight query blocks
    of ``_WINDOW_BLOCK``, most of whose key blocks lie before the window),
    against :func:`gqa_attention_blocked` under the same mask and prologue.
    One program, its inputs drawn on the host (``chip_smoke.py`` holds the
    kernel to the blocked form at the backbones' shapes)."""
    from tmr_tpu.diagnostics import gate_refused, run_outside_trace

    config = {"head_dim": head_dim, "diffusion_block": diffusion_block,
              "qk_norm": qk_norm, "rope": rope}
    if window is not None:
        config["window"] = window
    if jax.default_backend() != "tpu":
        return gate_refused(
            "gqa_kernel_ok", f"backend {jax.default_backend()!r} != 'tpu'",
            "backend", config)

    b, heads = 2, 16
    s = 2 * _BLOCK[0] if window is None else 8 * _WINDOW_BLOCK[0]
    kv_heads, scale = 2 * _LANES // head_dim, head_dim ** -0.5
    theta = 1e6 if rope else None

    @jax.jit
    def gap(q, k, v, wq, wk):
        norms = (wq, wk, 1e-6) if qk_norm else None
        got = gqa_attention_kernel(q, k, v, heads, kv_heads, scale, norms,
                                   theta, diffusion_block=diffusion_block,
                                   window=window)
        want = gqa_attention_blocked(q, k, v, heads, kv_heads, scale, norms,
                                     theta, diffusion_block, window)
        gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        return gap.max() / jnp.abs(want.astype(jnp.float32)).max()

    def check():
        rng = np.random.default_rng(0)
        draw = lambda width: rng.standard_normal(
            (b, s, width), np.float32).astype(jnp.bfloat16)
        weight = lambda: rng.uniform(1.25, 2.25, head_dim).astype(
            jnp.bfloat16)
        return float(gap(draw(heads * head_dim), draw(kv_heads * head_dim),
                         draw(kv_heads * head_dim), weight(), weight()))

    try:
        widest = run_outside_trace(check, "gqa_kernel_ok")
    except Exception as e:  # Mosaic's refusals included
        return gate_refused("gqa_kernel_ok", str(e)[:500], "exception",
                            config, exception=type(e).__name__)
    if not widest < 2e-2:
        return gate_refused(
            "gqa_kernel_ok", f"widest gap to the blocked form "
            f"{widest:.3g} of its range", "forward-mismatch", config)
    return True


def gqa_formulation(seq: int, heads: int, kv_heads: int, head_dim: int,
                    dtype, diffusion_block: int = 1, qk_norm: bool = False,
                    rope: bool = False, window=None) -> str:
    """What grouped-query attention traces with (counter
    ``trunk.gqa.<formulation>``), by what can be observed: in bfloat16, at
    heads of 128, or of 64 whose pairs share a slab of k and v, a sequence
    of whole 512-token blocks that VMEM holds (4,096 tokens ask 28 MiB, 33
    with the prologue's tables and turned k, the 1536 bucket's 9,216 43 MiB
    and 54; over 16,384 not) and a diffusion block that divides a key block,
    where the kernel's self-check at that diffusion block and with the
    mixer's q/k norms and rotary (``qk_norm``, ``rope``) and under its
    sliding ``window`` (in blocks of ``_WINDOW_BLOCK``) says yes (it says
    no off a TPU and inside a trace XLA partitions), the Pallas kernel
    (``causal_kernel``); else ``blocked_xla``,
    :func:`causal_attention_blocked`."""
    # the gate asked as it was where there is no window
    wide = () if window is None else (window,)
    if (dtype == jnp.bfloat16
            and gqa_kernel_supported(seq, heads, kv_heads, head_dim,
                                     diffusion_block=diffusion_block,
                                     window=window)
            and gqa_kernel_ok(head_dim, diffusion_block, bool(qk_norm),
                              bool(rope), *wide)):
        return "causal_kernel"
    return "blocked_xla"

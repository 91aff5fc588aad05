"""Causal softmax attention whose query-key width differs from its value
width (latent attention's 128 + 64 against 128), with no bias and no S x S
score tensor anywhere. Softmax in float32, over the true row maximum.

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
there across its query blocks. :func:`mla_formulation` says which of the two
a trace takes; the blocked form is the kernel's fallback, its gate's oracle
and its backward pass.
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


def causal_attention_blocked(q, k, v, scale: float, block: int = 256):
    """``q`` (B, S, H, dqk), ``k`` (B, S, Hkv, dqk), ``v`` (B, S, Hkv, dv)
    -> (B, S, H, dv) in ``v``'s dtype. With fewer key-value heads than query
    heads (grouped-query attention) query head ``j`` reads key-value head
    ``j // (H / Hkv)``; the key-value heads are never written out again."""
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
        scores = jnp.einsum(to_scores, q[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32) * scale
        after = jnp.arange(hi)[None, :] > jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(after, -jnp.inf, scores), axis=-1)
        outs.append(jnp.einsum(to_out, probs.astype(v.dtype), v[:, :hi]))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h, -1)


def gqa_formulation(seq: int, heads: int, kv_heads: int, head_dim: int,
                    dtype) -> str:
    """What grouped-query attention traces with (counter
    ``trunk.gqa.<formulation>``). One answer today:
    :func:`causal_attention_blocked`, plain XLA; a kernel gets its name and
    its gate here."""
    return "blocked_xla"


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


def _latent_kernel_body(q_ref, kv_ref, kpe_ref, *rest, scale: float,
                        bq: int, bk: int, rope: bool):
    """Refs: q (1, bq, 384) the pair of heads' columns, kv (1, S, 256) the
    head's ``[k_nope | v]``, kpe (1, S, 128) ``[k_pe | k_pe]``, with
    ``rope`` cos and sin (bq, 128) float32, out (1, bq, 128); scratch: the
    block's scores (bq, S) and m, l, acc (bq, 128), float32. ``bk``
    divides ``bq``."""
    if rope:
        cos_ref, sin_ref, *rest = rest
    o_ref, s_ref, m_ref, l_ref, acc_ref = rest
    f32, dtype = jnp.float32, q_ref.dtype
    iq, per = pl.program_id(2), bq // bk
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

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return lax.dot_general(a, b, dims, preferred_element_type=f32)

    def folded(op, t):  # (bq, bk) -> (bq, 128), lane on lane
        return functools.reduce(op, [
            t[:, j * _LANES:(j + 1) * _LANES] for j in range(bk // _LANES)])

    def scores(ik, masked):
        cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        s = (dot(q_nope, kv_ref[0, cols, :_DN], _NT)
             + dot(q_pe, kpe_ref[0, cols, :], _NT)) * scale
        if masked:
            row = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(col > row, _NEG, s)
        s_ref[:, cols] = s
        m_ref[...] = jnp.maximum(m_ref[...], folded(jnp.maximum, s))

    def before_diagonal(ik, c):
        scores(ik, False)
        return c

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    lax.fori_loop(0, iq * per, before_diagonal, 0)
    for d in range(per):  # the key blocks the diagonal crosses
        scores(iq * per + d, True)
    m_ref[...] = jnp.broadcast_to(
        jnp.max(m_ref[...], axis=1, keepdims=True), m_ref.shape)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def weighted(ik, c):
        cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        p = jnp.exp(s_ref[:, cols] - jnp.tile(m_ref[...], (1, bk // _LANES)))
        l_ref[...] += folded(jnp.add, p)
        acc_ref[...] += dot(p.astype(dtype), kv_ref[0, cols, _DN:])
        return c

    lax.fori_loop(0, (iq + 1) * per, weighted, 0)
    o_ref[0] = (acc_ref[...] / jnp.sum(l_ref[...], axis=1, keepdims=True)
                ).astype(o_ref.dtype)


def _vmem_bytes(seq: int, bq: int, itemsize: int) -> int:
    """What the kernel asks of VMEM: a query block's row of float32 scores,
    the head's ``[k_nope | v]`` and the doubled ``k_pe`` twice over (Pallas
    double-buffers them), and 16 MiB for the blocks of q, the tables and the
    output, the three float32 rows and Mosaic's own temporaries: 30 MiB at
    4,096 tokens. No more than it needs: what a kernel reserves, XLA cannot
    keep its neighbours' operands in."""
    held = 2 * seq * (_DN + _DV + _LANES) * itemsize
    return bq * seq * 4 + held + 16 * 1024 * 1024


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
            vmem_limit_bytes=_vmem_bytes(s, bq, q.dtype.itemsize)),
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

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

How a pair's row travels. In (:func:`dispatch`): by XLA's gather of
``x[order // k]``, on the chip as off it; it runs at the HBM's rate there.
Out (:func:`combine`): two ways, chosen by what :func:`pairs_formulation`
observes. ``xla_gather`` is the oracle: the CPU's, float32's, the tests' and
the backward pass's; it gathers every pair's result, held here or not, as
(T, k, D), which on a TPU is a relayout of them all in float32. ``row_dma``
is the chip's (:func:`sum_rows`): one Pallas kernel writes the blocks of the
products' result that hold a pair held here as rows a DMA can copy, a second
copies each token's rows into VMEM and sums them there. On that path no row
past the groups' total is read or written: those rows hold whatever the
product left there, and a pair not held here reads a row of zeros instead.
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

HI = lax.Precision.HIGHEST

_LANES = 128
#: sorted rows a grid step of the packing kernel: the grouped product's ``tm``
_ROWS = 512
#: tokens a grid step of the summing kernel
_TOKENS = 64
#: the pairs' rows are a scalar-prefetch operand: what SMEM holds of int32
_MAX_ROWS = 131072


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
    ``slot`` (T, k) int32, the row of ``xs`` each pair went to.

    One path, on the chip as off it: the rows go in by XLA's gather, which
    on a TPU copies rows out of the tokens at the HBM's rate (a Pallas kernel
    of row DMAs that skipped the pairs not held here read half the rows and
    took twice as long, PERF.md section 6, PR 39)."""
    t, k = idx.shape
    local = idx - expert_offset
    here = (local >= 0) & (local < experts_held)
    key = jnp.where(here, local, experts_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    # the inverse permutation by a second sort and the counts by a compare
    # and a sum: on a TPU a scatter of T x k integers and a scatter-add
    # into the experts' bins cost two to five times as much (PERF.md, PR 39)
    slot = jnp.argsort(order).astype(jnp.int32)
    group_sizes = (key[:, None] == jnp.arange(experts_held)[None]).sum(
        0, dtype=jnp.int32)
    return x[order // k], group_sizes, here, slot.reshape(t, k)


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


def combine(ys, weights, here, slot, formulation: str = "xla_gather"):
    """Each token's weighted sum over its pairs held here: ``ys`` (T * k, D)
    in sorted order -> (T, D) float32. How the results' rows travel is
    ``formulation`` (:func:`pairs_formulation`): ``xla_gather``, the oracle
    (the CPU's, float32's and the backward pass's), gathers every pair's
    row, held here or not, as (T, k, D), and leaves a pair not held here
    out by a select, never by a product with zero; ``row_dma``, the chip's,
    copies a row a DMA (:func:`sum_rows`) and never reads a row past the
    groups' total: a pair not held here reads a row of zeros."""
    if formulation == "row_dma":
        return sum_rows(ys, weights, here, slot)
    pairs = ys[slot.reshape(-1)].reshape(slot.shape + ys.shape[-1:])
    # rows past the groups' total hold whatever the product left there
    return jnp.where(here[..., None],
                     pairs.astype(jnp.float32) * weights[..., None],
                     0.0).sum(1)


# --------------------------------------------------------------------------
# The chip's way to bring a pair's result to its token: one DMA a row.
#
# Mosaic slices a reference only by whole tiles of its two minor axes, and a
# bfloat16 row shares its 32-bit words with the row beside it, so no row of
# the products' (rows, D) bfloat16 result can be copied by itself. A row
# travels as D / 2 words of a (rows, 1, D / 2) uint32 array instead, whose
# leading axis is free to slice and whose rows lie one after the other in
# memory: word j holds the row's element j in its low half and element
# j + D / 2 in its high half (the halves are whole 128-lane slabs, so packing
# and unpacking move no lane). ``_pack_body`` writes the results so; the
# kernel that sums them reads such rows out of VMEM a 128-word column of all
# its rows at a time (one strided load a vreg), which is the (8, 128) tiling
# the arithmetic wants.
# --------------------------------------------------------------------------

def _halves(words):
    """The two bfloat16 values of every word, as float32 (exact)."""
    f32 = jnp.float32
    return (lax.bitcast_convert_type(words << 16, f32),
            lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), f32))


def _word(low, high):
    """A word from its two bfloat16 values."""
    bits = lambda v: lax.bitcast_convert_type(v.astype(jnp.float32),
                                              jnp.uint32)
    return (bits(low) >> 16) | (bits(high) & jnp.uint32(0xFFFF0000))


def _vmem_limit(*block_bytes: int) -> int:
    """What a kernel asks of VMEM: its blocks (Pallas holds each twice) and
    scratch as given, and 4 MiB for Mosaic's own temporaries. No more than
    it needs: what a kernel reserves, XLA cannot keep its neighbours'
    operands in."""
    return sum(block_bytes) + 4 * 1024 * 1024


def _each(count, body):
    """``body(r)`` for every r below ``count``, as a loop and not unrolled:
    the chip compiles a kernel at every load of a program that holds it."""
    def step(r, carry):
        body(r)
        return carry

    lax.fori_loop(0, count, step, 0)


def _last_block(total_ref):
    """The last block of sorted rows that holds a pair held here (0 where
    none is): a step past it keeps that block's index, so that Pallas
    neither fetches nor writes a block nobody reads."""
    return jnp.maximum(total_ref[0] - 1, 0) // _ROWS


def _pack_body(total_ref, ys_ref, out_ref, flat, *, chunks: int):
    step, last = pl.program_id(0), pl.num_programs(0) - 1

    @pl.when((step < last) & (step * _ROWS < total_ref[0]))
    def _():
        def column(c):
            col = pl.multiple_of(c * _LANES, _LANES)
            flat[pl.ds(c, _ROWS, stride=chunks), :] = _word(
                ys_ref[:, pl.ds(col, _LANES)],
                ys_ref[:, pl.ds(chunks * _LANES + col, _LANES)])

        _each(chunks, column)
        out_ref[...] = flat.reshape(*out_ref.shape)[...]

    @pl.when(step == last)  # one block more: the rows of zeros
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _sum_body(src_ref, packed_ref, w_ref, out_ref, buf, sem, *, k: int,
              chunks: int):
    step, rows = pl.program_id(0), k * _TOKENS

    def start(block, carry):
        # one copy a pair, pair j of token t to row j x _TOKENS + t of its
        # half of the buffer. No branch and a count known beforehand: a pair
        # not held here copies a row of zeros, which costs less than the
        # test that would skip it
        half = block % 2

        def token(t):
            for j in range(k):
                pltpu.make_async_copy(
                    packed_ref.at[pl.ds(src_ref[block * rows + t * k + j],
                                        1)],
                    buf.at[pl.ds(half * rows + j * _TOKENS + t, 1)],
                    sem.at[half]).start()

        _each(_TOKENS, token)
        return carry

    # the next block's rows are on their way while this one's are summed:
    # step 0 starts blocks 0 and 1, every later step the block after its own
    lax.fori_loop(jnp.where(step == 0, 0, step + 1),
                  jnp.minimum(step + 2, pl.num_programs(0)), start, 0)
    half = step % 2
    # one wait for the half's every copy: as many bytes as the half holds
    pltpu.make_async_copy(packed_ref.at[pl.ds(0, rows)],
                          buf.at[pl.ds(half * rows, rows)],
                          sem.at[half]).wait()
    flat = buf.reshape(2 * rows * chunks, _LANES)

    def column(c):
        low = high = jnp.zeros((_TOKENS, _LANES), jnp.float32)
        for j in range(k):  # the order combine() sums in
            a, b = _halves(flat[pl.ds(
                (half * rows + j * _TOKENS) * chunks + c, _TOKENS,
                stride=chunks), :])
            low, high = low + a * w_ref[j], high + b * w_ref[j]
        col = pl.multiple_of(c * _LANES, _LANES)
        out_ref[:, pl.ds(col, _LANES)] = low
        out_ref[:, pl.ds(chunks * _LANES + col, _LANES)] = high

    _each(chunks, column)


@jax.jit
def _pack_impl(ys, total):
    # a jit of its own, as the next: a trunk's expert layers are one shape,
    # and share one traced and lowered function in the enclosing program
    rows, d = ys.shape
    blocks, chunks = rows // _ROWS, d // 2 // _LANES
    held = lambda i, total: (jnp.minimum(i, _last_block(total)), 0)
    return pl.pallas_call(
        functools.partial(_pack_body, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks + 1,),
            in_specs=[pl.BlockSpec((_ROWS, d), held)],
            out_specs=pl.BlockSpec(
                (_ROWS, 1, d // 2), lambda i, total: (
                    jnp.where(i == blocks, blocks, held(i, total)[0]), 0, 0)),
            scratch_shapes=[pltpu.VMEM((_ROWS * chunks, _LANES),
                                       jnp.uint32)]),
        out_shape=jax.ShapeDtypeStruct((rows + _ROWS, 1, d // 2), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(5 * _ROWS * d * 2)),
        interpret=jax.default_backend() != "tpu",
    )(total.reshape(1).astype(jnp.int32), ys)


@jax.jit
def _sum_rows_impl(ys, weights, here, slot):
    (t, k), (rows, d) = slot.shape, ys.shape
    chunks = d // 2 // _LANES
    packed = _pack_impl(ys, here.sum(dtype=jnp.int32))
    # a pair not held here: the first row of zeros, and no weight
    source = jnp.where(here, slot, rows).reshape(-1)
    weigh = jnp.broadcast_to(
        jnp.where(here, weights.astype(jnp.float32), 0.0).T[..., None],
        (k, t, _LANES))
    return pl.pallas_call(
        functools.partial(_sum_body, k=k, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // _TOKENS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((k, _TOKENS, _LANES),
                                   lambda i, src: (0, i, 0))],
            out_specs=pl.BlockSpec((_TOKENS, d), lambda i, src: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * k * _TOKENS, 1, d // 2), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # a step starts the next's
            vmem_limit_bytes=_vmem_limit(
                2 * k * _TOKENS * d * 2, 2 * _TOKENS * d * 4,
                2 * k * _TOKENS * _LANES * 4)),
        interpret=jax.default_backend() != "tpu",
    )(source, packed, weigh)


def pairs_kernels_supported(rows: int, top_k: int, d: int) -> bool:
    """The shapes the row kernels are written for: a row of whole pairs of
    128-lane slabs, whole blocks of sorted rows and of tokens, and no more
    rows than the kernels' scalar operands hold."""
    return (d % (2 * _LANES) == 0 and rows % _ROWS == 0
            and rows % (top_k * _TOKENS) == 0 and 0 < rows <= _MAX_ROWS)


def sum_rows(ys, weights, here, slot):
    """:func:`combine` by row DMAs. One Pallas TPU kernel walks blocks of
    512 sorted rows of ``ys`` and writes those that hold a pair held here as
    rows of words, and one block more of zeros; blocks wholly past the
    groups' total are neither read nor written. A second walks blocks of 64
    tokens with ``slot`` as its scalar-prefetch operand and the packed rows
    left in HBM: it copies the row of every pair of the next block's tokens
    into VMEM, one DMA a pair, all of them in flight while the block before
    is summed (a pair not held here copies a row of zeros: a branch a pair
    costs more than the copy), widens the rows to float32, weighs them (a
    pair not held here weighs nothing) and sums them in ``combine``'s order,
    and writes the (64, D) float32 block once. Differentiable through
    :func:`combine`."""
    (rows, d), k = ys.shape, slot.shape[1]
    if not (pairs_kernels_supported(rows, k, d) and ys.dtype == jnp.bfloat16):
        raise ValueError(
            f"{rows} rows of {k} a token, {d} wide in {ys.dtype.name} have "
            "no row kernel; gate callers on pairs_formulation()")
    return _sum_rows_vjp(ys, weights, here, slot)


@jax.custom_vjp
def _sum_rows_vjp(ys, weights, here, slot):
    return _sum_rows_impl(ys, weights, here, slot)


def _sum_vjp_fwd(ys, weights, here, slot):
    return _sum_rows_impl(ys, weights, here, slot), (ys, weights, here, slot)


def _sum_vjp_bwd(res, ct):
    ys, weights, here, slot = res
    return jax.vjp(lambda ys, weights: combine(ys, weights, here, slot),
                   ys, weights)[1](ct) + (None, None)


_sum_rows_vjp.defvjp(_sum_vjp_fwd, _sum_vjp_bwd)


@mosaic_gate
def pairs_kernels_ok() -> bool:
    """Compiled self-check of both row kernels as :class:`MoEFFN` calls
    them, once a machine: 1,024 tokens of 256, 2 a token over 4 experts of
    which the first 2 are held (four blocks of sorted rows, so that those
    past the total are skipped, sixteen of tokens, and a token has pairs
    held here, elsewhere, or both), rows past the total filled with NaN,
    against :func:`combine` as XLA runs it. One program, a few blocks: a
    cold start pays it once a machine."""
    from tmr_tpu.diagnostics import gate_refused, run_outside_trace

    if jax.default_backend() != "tpu":
        return gate_refused(
            "pairs_kernels_ok", f"backend {jax.default_backend()!r} != 'tpu'",
            "backend", {})
    t, k, d, held = 16 * _TOKENS, 2, 2 * _LANES, 2

    @jax.jit
    def gap(x, idx, weights, ys):
        _, sizes, here, slot = dispatch(x, idx, held)
        ys = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], ys,
                       jnp.nan)
        want = combine(ys, weights, here, slot)
        got = combine(ys, weights, here, slot, "row_dma")
        return jnp.abs(got - want).max() / jnp.abs(want).max()

    def check():
        # drawn on the host: a generator in the program is a third of what
        # the program takes to compile
        rng = np.random.default_rng(0)
        draw = lambda *shape: rng.standard_normal(shape, np.float32)
        return float(gap(
            draw(t, d).astype(jnp.bfloat16),
            rng.integers(0, 2 * held, (t, k)).astype(np.int32),
            np.abs(draw(t, k)), draw(t * k, d).astype(jnp.bfloat16)))

    try:
        widest = run_outside_trace(check, "pairs_kernels_ok")
    except Exception as e:  # Mosaic's refusals included
        return gate_refused("pairs_kernels_ok", str(e)[:500], "exception", {},
                            exception=type(e).__name__)
    # float32 sums of the same bfloat16 rows on both sides: 1e-7 on the chip
    if not widest < 1e-5:
        return gate_refused(
            "pairs_kernels_ok", f"widest gap to combine() {widest:.3g} of "
            "its range", "forward-mismatch", {})
    return True


def pairs_formulation(rows: int, top_k: int, d: int, dtype) -> str:
    """How the pairs' results reach their tokens, by what can be observed:
    on a TPU in bfloat16, at shapes the row kernels take
    (:func:`pairs_kernels_supported`), where their self-check says yes (it
    says no off a TPU and inside a trace XLA partitions), ``row_dma``; else
    ``xla_gather``, which the CPU, float32, the tests and the backward pass
    take."""
    if (jax.default_backend() == "tpu" and dtype == jnp.bfloat16
            and pairs_kernels_supported(rows, top_k, d)
            and pairs_kernels_ok()):
        return "row_dma"
    return "xla_gather"

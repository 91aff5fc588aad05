"""Custom Pallas TPU kernels for the ViT's attention blocks, and the
choosers that decide which form a block traces with.

Both kernels read ``qkv`` where the product wrote it, a row a token with
q | k | v along the row and heads within each, and write what ``proj``
reads: no head-major operand, transpose, pad or concatenate between the
two products.

- ``packed_windowed_attention``: one window, all of its heads, a grid
  step; ``window_formulation`` answers ``packed`` for it, else ``dense``.
- ``packed_global_attention``: one image's group of heads a grid step;
  ``global_formulation`` answers ``packed`` for it, else ``flash``
  (ops/flash_attn.py), else ``blockwise`` (models/vit.py).

Each choice reads only what it can observe: the backend, the dtype, the
rel-pos tables, the grid and heads, and the kernel's own self-check
against the exact blockwise oracle (``packed_window_ok`` /
``packed_global_ok``, compiled, forward and gradients; ops/flash_attn.
_self_check). Both kernels are differentiable by a ``jax.custom_vjp``
whose backward recomputes through that oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import mosaic_gate

_NEG_INF = -1e30


def _pick_block(s: int, preferred: int = 512) -> Optional[int]:
    # the one block-selection rule, the flash path's (flash_attn._block_for)
    from tmr_tpu.ops.flash_attn import _block_for

    return _block_for(s, preferred)


# --------------------------------------------------------------------------
# Packed windowed attention (``window_formulation``: a TPU's bf16 path).
#
# The head-major forms take q/k/v as (B', H, S, D): between the
# ``qkv`` product, which writes (B', S, 3*dim) token-major, and ``proj``,
# which reads (B', S, dim), that costs a transpose of every operand, and
# pads and concatenates besides: of the 11.1 ms an image that the 8
# windowed blocks' attention took on ViT-B/1024 (PR 25's chip trace), 7.9
# were HBM passes that compute nothing. This kernel reads what ``qkv``
# wrote and writes what ``proj`` reads. One grid step is one window, all
# of its heads:
#
# - head h's q, k, v are lanes [h*D, (h+1)*D) of their third of the row.
#   The kernel never slices lanes off a 128 boundary (head dim 80 is not a
#   lane multiple): it loads the aligned slab, or pair of slabs, that
#   covers the head and zeroes q's other lanes, so the contraction over
#   the slab is the head's own q.k; the a.v product is taken against the
#   same lanes of v and the head's lanes selected into the output.
# - the decomposed bias enters as one more product on the MXU: the small
#   float32 projections q.RH / q.RW (gh + gw numbers a token and head, 32
#   lanes a head, so four heads a slab) are split in VMEM into three
#   parts of the operand dtype that sum to the float32 value exactly, laid
#   side by side in the slab (lane rolls) and multiplied by a constant 0/1
#   selector; accumulated in float32 like the scores.
# - a window is 14 rows of 16 tokens (``_padded_width``), a whole number
#   of tiles, so the products write and read the kernel's rows directly.
# --------------------------------------------------------------------------
_PROJ_LANES = 32  # lanes a head's bias projections take: gh + gw <= 32
_PROJ_HEADS = 128 // _PROJ_LANES  # heads a 128-lane slab of them holds


def _split3(x: jnp.ndarray, dtype) -> Tuple[jnp.ndarray, ...]:
    """Three float32 arrays, each exact in ``dtype``, that sum to float32
    ``x``: exactly for bfloat16 (3 x 8 mantissa bits), trivially for
    float32 (x, 0, 0)."""
    hi = x.astype(dtype).astype(jnp.float32)
    r1 = x - hi
    mid = r1.astype(dtype).astype(jnp.float32)
    lo = (r1 - mid).astype(dtype).astype(jnp.float32)
    return hi, mid, lo


def _packed_win_kernel(
    qkv_ref, proj_ref, sel_ref, out_ref,
    *, num_heads: int, head_dim: int, scale: float,
):
    """One window, all of its heads. Refs: qkv (S, 3*dim), proj (S, P)
    float32, sel (4, 128, S), out (S, dim); S counts the window's pad
    tokens too (``_padded_width``). The heads are a loop, not an unroll:
    the chip compiles a Mosaic kernel every time a program that holds it
    is loaded, compile cache or not, in proportion to its code, and a
    model has one instance a windowed block."""
    dim = num_heads * head_dim
    dtype = qkv_ref.dtype
    width = _head_lanes(head_dim)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    group = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) // _PROJ_LANES

    def head(h, carry):
        a = h * head_dim
        lo = pl.multiple_of(
            jnp.minimum(a // 128 * 128, dim - width), 128)
        mine = (lanes >= a - lo) & (lanes < a - lo + head_dim)
        q = qkv_ref[:, pl.ds(lo, width)]
        q = jnp.where(mine, q, jnp.zeros_like(q))
        s = jax.lax.dot_general(
            q, qkv_ref[:, pl.ds(dim + lo, width)], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

        # the head's bias projections: three parts that sum to the float32
        # value, side by side (its own 32 lanes, then rolled into the two
        # groups after it); ones in the fourth group, where sel[c] holds
        # the pad keys' -1e30
        c = h % _PROJ_HEADS
        p0 = pl.multiple_of(h // _PROJ_HEADS * 128, 128)
        parts = _split3(proj_ref[:, pl.ds(p0, 128)], dtype)
        parts = (parts[0], pltpu.roll(parts[1], _PROJ_LANES, 1),
                 pltpu.roll(parts[2], 2 * _PROJ_LANES, 1))
        x = jnp.ones_like(parts[0])
        for n in (2, 1, 0):
            x = jnp.where(group == (c + n) % _PROJ_HEADS, parts[n], x)
        s += jax.lax.dot_general(
            x.astype(dtype), sel_ref[c], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        inv = 1.0 / jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(dtype), qkv_ref[:, pl.ds(2 * dim + lo, width)],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * inv
        # the slabs are shared with other heads: write this head's lanes
        out = out_ref[:, pl.ds(lo, width)]
        out_ref[:, pl.ds(lo, width)] = jnp.where(
            mine, o.astype(out.dtype), out)
        return carry

    jax.lax.fori_loop(0, num_heads, head, 0)


def _padded_width(gw: int) -> int:
    """Tokens a window row takes in the packed operands: the grid's
    columns rounded up to the 16-row tile of a 16-bit operand (14 -> 16).
    With whole tiles a row, (B', gh, gwp, C) and (B'*gh*gwp, C) are the
    same bytes, so the products on either side write and read the kernel's
    rows with no relayout, and a window is a legal block. (At 196 tokens a
    window XLA puts the windows second-minor, and every reshape between
    that and the kernel's row-major order is a pass over the operand.) The
    pad tokens are keys no query may see: ``_packed_selector`` masks
    them; as queries they cost their share of the tile and are dropped by
    the caller."""
    return -(-gw // 16) * 16


def _proj_width(num_heads: int) -> int:
    return -(-num_heads // _PROJ_HEADS) * 128


def _packed_projections(
    qkv: jnp.ndarray, rh: jnp.ndarray, rw: jnp.ndarray,
    grid_hw: Tuple[int, int], num_heads: int,
) -> jnp.ndarray:
    """(B'*S, 3*dim) qkv + (gh, gh, D)/(gw, gw, D) tables -> (B'*S, P)
    float32: for token t = (y, x) of its window and head h, lanes
    [32h, 32h + gh) hold q.RH[y] and the next gw q.RW[x].

    The tables become block-diagonal weights over the heads, (dim, P) for
    each grid row and each grid column, so that the two products contract
    q's whole row where it lies: a third of the ``qkv`` product's
    operations, no per-head operand, and q read in the rows it has. As
    there, a float32 operand is rounded to the operand dtype by a TPU's
    default precision and the sum is float32."""
    gh, gw = grid_hw
    dim = qkv.shape[1] // 3
    D = dim // num_heads
    P = _proj_width(num_heads)
    # w[g, h*D + d, 32*h + first + j] = table[g, j, d], zero elsewhere.
    # Spread over rows and columns by two products with 0/1 matrices (each
    # entry a single term, so exact) and cut to the diagonal blocks by a
    # mask: cheap where an outer product with eye(H) reshaped to 2-D is a
    # lane shuffle.
    row, col = jnp.arange(dim)[:, None], jnp.arange(P)[None, :]
    rows = (row % D == jnp.arange(D)[None, :]).astype(jnp.float32)
    cols = (col % _PROJ_LANES == jnp.arange(_PROJ_LANES)[:, None]).astype(
        jnp.float32)
    blocks = row // D == col // _PROJ_LANES

    def weights(table, first):  # (g, k, D) -> (g, dim, P)
        table = jnp.pad(table.astype(jnp.float32), (
            (0, 0), (first, _PROJ_LANES - first - table.shape[1]), (0, 0)))
        w = jnp.einsum("rd,gjd,jn->grn", rows, table, cols,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(blocks, w, 0.0).astype(qkv.dtype)

    gwp = _padded_width(gw)
    q = qkv[:, :dim].reshape(-1, gh, gwp, dim)
    if jax.default_backend() == "cpu":
        # XLA:CPU has no bfloat16 x bfloat16 -> float32 product
        q = q.astype(jnp.float32)
    w_cols = jnp.pad(weights(rw, gh), ((0, gwp - gw), (0, 0), (0, 0)))
    proj = sum(
        jnp.einsum(spec, q, w.astype(q.dtype),
                   preferred_element_type=jnp.float32)
        for spec, w in (("byxc,ycn->byxn", weights(rh, 0)),
                        ("byxc,xcn->byxn", w_cols)))
    return row_major(proj).reshape(-1, P)


def row_major(x: jnp.ndarray) -> jnp.ndarray:
    """Pin ``x`` to the row-major layout. Of a product over windows XLA
    would rather put the windows second-minor and copy the result to the
    order the kernel reads; told so, the product writes that order."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _packed_selector(grid_hw: Tuple[int, int], dtype) -> jnp.ndarray:
    """(4, 128, S) constants. Head c of a slab has its three parts in lane
    groups c, c+1, c+2 (mod 4); in each, lane j < gh selects the keys of
    grid row j and lane gh + j the keys of grid column j: the product with
    the parts is bias[t, u] = proj[t, ky(u)] + proj[t, gh + kx(u)]. The
    fourth group's first lane holds 1 in the kernel and here -1e30 for the
    pad keys (kx >= gw)."""
    import numpy as np

    gh, gw = grid_hw
    gwp = _padded_width(gw)
    u = np.arange(gh * gwp)
    ky, kx = u // gwp, u % gwp
    j = np.arange(128)[:, None] % _PROJ_LANES
    one = ((j == ky) | ((j - gh == kx) & (j < gh + gw))) & (kx < gw)
    grp = np.arange(128)[:, None] // _PROJ_LANES
    sel = np.zeros((_PROJ_HEADS, 128, u.size), np.float32)
    for c in range(_PROJ_HEADS):
        sel[c] = one & (((grp - c) % _PROJ_HEADS) < 3)
        sel[c, ((c + 3) % _PROJ_HEADS) * _PROJ_LANES, kx >= gw] = _NEG_INF
    return jnp.asarray(sel, dtype)


def _head_lanes(head_dim: int) -> int:
    """The lanes one head's slices take: its own slab where heads tile the
    128 lanes (64), else the two slabs that cover it (80)."""
    return 128 if 128 % head_dim == 0 else 256


def packed_supported(
    grid_hw: Tuple[int, int], num_heads: int, head_dim: int
) -> bool:
    """Whole 128-lane slabs of heads, a head within the lanes its slices
    take, and room for its projections in their 32 lanes."""
    dim = num_heads * head_dim
    return (dim % 128 == 0 and head_dim <= 128
            and dim >= _head_lanes(head_dim)
            and sum(grid_hw) <= _PROJ_LANES)


def packed_windowed_attention(
    qkv: jnp.ndarray,
    rh: jnp.ndarray,
    rw: jnp.ndarray,
    grid_hw: Tuple[int, int],
    num_heads: int,
    scale: float,
) -> jnp.ndarray:
    """Windowed attention on the ``qkv`` product's own output: qkv
    (B'*S, 3*dim), a row a token (``pad_window_rows``: windows of
    S = gh*gwp rows one after the other), q | k | v along the row and
    heads within each, as ``nn.Dense(3 * dim)`` writes it; rh (gh, gh, D) /
    rw (gw, gw, D) the get_rel_pos tables. Returns (B'*S, dim), what
    ``proj`` reads; the pad tokens' rows hold nothing of use
    (``drop_window_pad``). Same math as ``blockwise_decomposed_attention``
    on the real tokens' unpacked heads; differentiable by recomputing
    through it."""
    return _packed_win_vjp(qkv, rh, rw, grid_hw, num_heads, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _packed_win_vjp(qkv, rh, rw, grid_hw, num_heads, scale):
    return _packed_win_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _packed_win_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale):
    # a jit of its own: a model's 8 or 28 windowed blocks are one shape,
    # and share one traced and lowered function in the enclosing program
    rows, c3 = qkv.shape
    S = grid_hw[0] * _padded_width(grid_hw[1])
    dim = c3 // 3
    if not packed_supported(grid_hw, num_heads, dim // num_heads):
        raise ValueError(
            f"window grid {grid_hw} at {num_heads} heads of "
            f"{dim // num_heads} has no packed layout; gate callers on "
            "packed_supported()"
        )
    P = _proj_width(num_heads)
    kernel = functools.partial(
        _packed_win_kernel, num_heads=num_heads, head_dim=dim // num_heads,
        scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(rows // S,),
        in_specs=[
            pl.BlockSpec((S, c3), lambda b: (b, 0)),
            pl.BlockSpec((S, P), lambda b: (b, 0)),
            pl.BlockSpec((_PROJ_HEADS, 128, S), lambda b: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((S, dim), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, dim), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=jax.default_backend() != "tpu",
    )(
        qkv, _packed_projections(qkv, rh, rw, grid_hw, num_heads),
        _packed_selector(grid_hw, qkv.dtype),
    )


def pad_window_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(B', gh, gw, C) -> (B'*gh*gwp, C), a window row padded with zero
    tokens to ``_padded_width``: the rows ``packed_windowed_attention``
    takes."""
    B, gh, gw, C = x.shape
    gwp = _padded_width(gw)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, gwp - gw), (0, 0)))
    return x.reshape(B * gh * gwp, C)


def drop_window_pad(x: jnp.ndarray, grid_hw: Tuple[int, int]) -> jnp.ndarray:
    """The inverse: (B'*gh*gwp, C) -> (B', gh, gw, C)."""
    gh, gw = grid_hw
    return x.reshape(-1, gh, _padded_width(gw), x.shape[-1])[:, :, :gw]


def _packed_oracle(qkv, rh, rw, grid_hw, num_heads, scale):
    """The same on the exact blockwise path: the real tokens' heads
    unpacked, head-major; zeros in the pad tokens' rows."""
    from tmr_tpu.models.vit import blockwise_decomposed_attention

    gh, gw = grid_hw
    t = drop_window_pad(qkv, grid_hw).reshape(
        -1, gh * gw, 3, num_heads, qkv.shape[1] // 3 // num_heads)
    q, k, v = jnp.moveaxis(t, 2, 0).transpose(0, 1, 3, 2, 4)
    out = blockwise_decomposed_attention(q, k, v, rh, rw, grid_hw, scale)
    out = out.transpose(0, 2, 1, 3).reshape(-1, gh, gw, qkv.shape[1] // 3)
    return pad_window_rows(out)


def _packed_vjp_fwd(qkv, rh, rw, grid_hw, num_heads, scale):
    return _packed_win_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale), (
        qkv, rh, rw,
    )


def _packed_vjp_bwd(grid_hw, num_heads, scale, res, g):
    _, pull = jax.vjp(
        lambda a, b, c: _packed_oracle(a, b, c, grid_hw, num_heads, scale),
        *res,
    )
    return pull(g)


_packed_win_vjp.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)


def _packed_on_heads(q, k, v, rh, rw, grid_hw, scale):
    """The packed path behind the head-major signature ``_self_check``
    drives: q/k/v (B', H, S, D) packed as ``qkv`` lays them out."""
    B, H, S, D = q.shape
    qkv = jnp.stack([q, k, v], axis=2)  # (B', H, 3, S, D)
    qkv = qkv.transpose(0, 3, 2, 1, 4).reshape(B, *grid_hw, 3 * H * D)
    out = packed_windowed_attention(
        pad_window_rows(qkv), rh, rw, grid_hw, H, scale)
    return drop_window_pad(out, grid_hw).reshape(B, S, H, D).transpose(
        0, 2, 1, 3)


@mosaic_gate
def packed_window_ok(
    gh: int, gw: int, head_dim: int, num_heads: int
) -> bool:
    """Per-geometry compiled self-check of the packed windowed kernel
    against the exact blockwise oracle, forward and gradients, at the
    window grid and — the head count and head dim fix every lane offset
    the kernel uses — at the model's own heads (two windows of them)."""
    from tmr_tpu.ops.flash_attn import _self_check

    return _self_check(
        _packed_on_heads, 2, num_heads, gh, gw, head_dim,
        gate="packed_window_ok",
    )


def window_formulation(
    grid_hw: Tuple[int, int], num_heads: int, head_dim: int, dtype,
    use_rel_pos: bool = True,
) -> str:
    """What a block under 1024 tokens traces with, by what can be observed:
    on a TPU in bfloat16, with rel-pos tables, at heads that have a packed
    layout and where the kernel's self-check says yes (it says no inside a
    trace XLA partitions and under ``diagnostics.mosaic_kernels_off``), the
    kernel above (``packed``); else ``dense``, the einsums of
    models/vit.py:Attention. On the v5e ``packed`` read 9.47 / 55.48 ms an
    image of ``backbone_rest.ms`` on ViT-B / ViT-H where ``dense`` read
    22.96 / 116.99 (PERF.md section 6, PR 28); nothing else selects it."""
    gh, gw = grid_hw
    if (use_rel_pos and dtype == jnp.bfloat16
            and jax.default_backend() == "tpu"
            and packed_supported(grid_hw, num_heads, head_dim)
            and packed_window_ok(gh, gw, head_dim, num_heads)):
        return "packed"
    return "dense"


# --------------------------------------------------------------------------
# Packed global attention (``global_formulation``: a TPU's bf16 path).
#
# The global blocks' twin of the packed windowed kernel: it reads ``qkv``
# where the product wrote it, (B*S, 3*dim) with a row a token, and writes
# what ``proj`` reads. Between the two products the stock flash path built
# three head-major operands 256 lanes wide (transpose, the rel-pos einsum
# on the copy, concatenate, pad) and its kernel contracted and produced
# all 256: 4.5 of ViT-B's 10.1 ms an image were passes that compute
# nothing, and the kernel's own work was four times the model's (PERF.md
# section 6, PR 32). Here one grid step is one image's group of heads, the
# lanes that a whole number of heads and of 128-lane slabs share (128 for
# heads of 64: two heads; 640 for heads of 80: eight):
#
# - a head's q, k, v are read as the slab that holds it. Heads that tile
#   the 128 lanes are told apart by a lane mask on q (the contraction over
#   the slab is then the head's own q.k, and the other head's lanes of the
#   a.v product are never written). A head that straddles slabs (80) is
#   first aligned to lane 0 of one slab by a product with a 0/1 matrix,
#   exact in any dtype: k and v once a head, into VMEM that stays for all
#   of the image's query blocks, q and the output a query block at a time.
#   Every product after that is 128 deep and 128 wide.
# - the decomposed bias needs q.RH[y, ky] and q.RW[x, kx]. The tables
#   ``get_rel_pos`` builds are Toeplitz (entry [y, ky] is row y - ky of
#   the parameter), so one product of q with the 2g - 1 rows of each
#   parameter holds every entry, and a row's own are a lane roll away: by
#   the query's grid row for RH, the same for a row of tokens; by its grid
#   column for RW, a strided roll. No projection is computed outside the
#   kernel.
# - the bias costs no product of its own. A key block holds bk / gw grid
#   rows, so of q.RH only that many numbers a token meet it: they ride in
#   the score product's own contraction, in lanes of the slab that the
#   head leaves free (q' = [q * scale | q.RH[y, ky0 : ky0 + bk / gw]]
#   against k' = [k | one-hot(ky - ky0)], the fold of
#   ``fold_rel_pos_into_qk`` a key block at a time, rounded to the operand
#   dtype as there), and k' is the same for every key block, so it is made
#   once a head beside the aligned K. q.RW[x, kx] repeats every gw keys:
#   tiled over the 128 lanes once a query block, it is added to the scores
#   in float32, the add that a second product's result would have cost.
#   Two MXU passes a head where the stock kernel paid four.
# - softmax runs over a query block's whole row of scores, kept in VMEM
#   as float32; K and V of the head stay in VMEM across the image's query
#   blocks. Heads, query blocks and key blocks are loops, not unrolls (the
#   chip compiles the kernel at every load of a program that holds it).
# --------------------------------------------------------------------------
#: tokens a query block and a key block hold. Read on the v5e, a block and
#: image of ViT-B / ViT-H and the kernel's first call, compile included
#: (PERF.md section 6, PR 32): 512 x 512 0.85 / 1.22 ms, 0.9-1.0 s;
#: 512 x 1024 0.74 / 1.07, 1.0-1.3 s; 512 x 2048 0.69 / 0.99, 1.4-1.7 s;
#: 1024 x 1024 0.67 / 0.96, 1.6-1.9 s. The chip compiles the kernel at
#: every load of a program that holds it, so the last twentieth of a
#: millisecond is not worth half a second of every set-up.
_GLOBAL_QUERY_BLOCK = 512
_GLOBAL_KEY_BLOCK = 1024
#: what the kernel may take of a v5e's 128 MiB of VMEM: heads of 80 hold
#: three (S, 640) operands and the output twice over (42 MB at 4,096
#: tokens) beside the head's K and V, the one-hots and a block of scores
_GLOBAL_VMEM_BYTES = 96 * 1024 * 1024


def _group_lanes(head_dim: int) -> int:
    """Lanes a grid step's group of heads takes: the least that is whole
    heads and whole 128-lane slabs."""
    return head_dim * 128 // math.gcd(head_dim, 128)


def _global_blocks(grid_hw: Tuple[int, int]) -> Tuple[int, int]:
    """(query block, key block) in tokens: both are whole grid rows (the RH
    roll is one amount a row of tokens; a key block's rows are the RH
    entries that ride in its contraction), both divide S."""
    gh, gw = grid_hw
    rows = max(r for r in range(1, gh + 1)
               if gh % r == 0 and r * gw <= _GLOBAL_QUERY_BLOCK)
    return rows * gw, _pick_block(gh * gw, _GLOBAL_KEY_BLOCK)


def packed_global_supported(
    grid_hw: Tuple[int, int], num_heads: int, head_dim: int
) -> bool:
    """Whole groups of heads; the 2g - 1 parameter rows of a table within
    128 lanes; a grid row a whole number of 16-row tiles that tiles the 128
    lanes; key blocks of whole grid rows, and as many free lanes in a
    head's slab as a key block has grid rows (beside a head that tiles the
    lanes, the next head's; past a head aligned to lane 0, the rest)."""
    gh, gw = grid_hw
    dim = num_heads * head_dim
    if (head_dim > 128 or dim % _group_lanes(head_dim) or max(gh, gw) > 64
            or gw % 16 or 128 % gw or _pick_block(gh * gw) is None):
        return False
    bk = _global_blocks(grid_hw)[1]
    tiles = 128 % head_dim == 0
    free = head_dim if tiles else 128 - head_dim
    return (bk % gw == 0 and bk // gw <= free
            and (not tiles or head_dim <= 64))


def _toeplitz_lanes(table: jnp.ndarray) -> jnp.ndarray:
    """(g, g, D) ``get_rel_pos`` table -> (128, D), its 2g - 1 distinct
    rows by the lane they are read from: lane (ky - y) mod 128 holds
    table[y, ky], so that a row of products with them, rolled right by y,
    has q.table[y, ky] in lane ky."""
    g = table.shape[0]
    return jnp.concatenate([
        table[0], jnp.zeros((129 - 2 * g, table.shape[2]), table.dtype),
        table[:0:-1, 0]], axis=0)


def _global_tables(rh, rw, head_dim: int, dtype) -> jnp.ndarray:
    """(128, 256): columns [0, 128) the Toeplitz rows of RH by lane
    (``_toeplitz_lanes``), columns [128, 256) those of RW; the D rows
    repeated for every head of a slab (heads that tile the lanes are read
    unaligned), or padded to the slab (a head aligned to lane 0)."""
    t = jnp.concatenate([_toeplitz_lanes(rh.astype(jnp.float32)).T,
                         _toeplitz_lanes(rw.astype(jnp.float32)).T], axis=1)
    if 128 % head_dim == 0:
        t = jnp.tile(t, (128 // head_dim, 1))
    else:
        t = jnp.pad(t, ((0, 128 - head_dim), (0, 0)))
    return t.astype(dtype)


def _global_key_rows(
    grid_hw: Tuple[int, int], head_dim: int, bk: int, dtype
) -> jnp.ndarray:
    """(S, 128) constant, the one-hots that k' carries beside k: key u of
    grid row ky has a one in the free lane that q' gives to q.RH[y, ky],
    lane ky % (bk / gw) of the free ones: past the head's own where it is
    aligned to lane 0, and of every head's lanes where heads tile the slab
    (q' is zero in all of them but the next head's)."""
    gh, gw = grid_hw
    # made by the program, not baked into it: a megabyte of literal would
    # triple the lowered text
    row = (jnp.arange(gh * gw) // gw % (bk // gw))[:, None]
    j = jnp.arange(128)[None, :]
    if 128 % head_dim == 0:
        return (j % head_dim == row).astype(dtype)
    return (j - head_dim == row).astype(dtype)


def _packed_global_kernel(
    q_ref, k_ref, v_ref, tab_ref, rows_ref, out_ref, *scratch,
    head_dim: int, scale: float, grid_hw: Tuple[int, int], bq: int, bk: int,
):
    """One image's group of heads. Refs: q, k, v (S, W) column blocks of
    ``qkv``, tab (128, 256), rows (S, 128), out (S, W); scratch k' (S, 128)
    and, for heads that straddle slabs, the aligned v, of the operand
    dtype; a query block's scaled q, q.RH, tiled q.RW, m, l, acc
    (bq, 128) and its scores (bq, S), float32."""
    S, W = q_ref.shape
    gh, gw = grid_hw
    dtype = q_ref.dtype
    straddles = 128 % head_dim != 0
    width = 256 if straddles else 128
    rk = bk // gw  # grid rows a key block holds
    kal_ref, *scratch = scratch
    if straddles:
        val_ref, *scratch = scratch
    qs_ref, rel_h_ref, rel_w_ref, s_ref, m_ref, l_ref, acc_ref = scratch
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)

    def head(hh, carry):
        a = hh * head_dim
        lo = pl.multiple_of(jnp.minimum(a // 128 * 128, W - width), 128)
        off = a - lo
        mine = (lanes >= off) & (lanes < off + head_dim)
        if straddles:
            # 0/1 matrices that move the head's lanes to lane 0 and back:
            # one term a sum, so exact
            src = jax.lax.broadcasted_iota(jnp.int32, (256, 128), 0)
            dst = jax.lax.broadcasted_iota(jnp.int32, (256, 128), 1)
            align = ((src == dst + off) & (dst < head_dim)).astype(dtype)
            dst = jax.lax.broadcasted_iota(jnp.int32, (128, 256), 1)
            src = jax.lax.broadcasted_iota(jnp.int32, (128, 256), 0)
            place = ((dst == src + off) & (src < head_dim)).astype(dtype)
            free = head_dim  # the first lane q.RH's entries ride in
            own = lane < head_dim
        else:
            free = (off + head_dim) % 128
            own = mine

        def key_rows(i, c):  # k' = [k | one-hot of the key's grid row]
            r = pl.ds(pl.multiple_of(i * bk, bk), bk)
            if straddles:
                k = dot(k_ref[r, pl.ds(lo, 256)], align).astype(dtype)
                val_ref[r, :] = dot(
                    v_ref[r, pl.ds(lo, 256)], align).astype(dtype)
            else:
                k = k_ref[r, pl.ds(lo, 128)]
            kal_ref[r, :] = jnp.where(own, k, rows_ref[r, :])
            return c

        jax.lax.fori_loop(0, S // bk, key_rows, 0)

        def values(r):
            return val_ref[r, :] if straddles else v_ref[r, pl.ds(lo, 128)]

        def query_block(iq, c):
            rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)
            q = q_ref[rows, pl.ds(lo, width)]
            if straddles:
                qf = dot(q, align)
            else:
                qf = jnp.where(mine, q, jnp.zeros_like(q)).astype(
                    jnp.float32)
            qs_ref[...] = qf * scale
            # q against every Toeplitz row of both tables; a token's own
            # entries are a roll away, by its grid row y for q.RH[y] (to
            # lanes [0, gh)) and by its grid column x for q.RW[x] (to every
            # gw lanes of the 128): one strided roll each of the block as
            # (grid rows, gw tokens, 128)
            g = dot(qf.astype(dtype), tab_ref[...]).reshape(
                bq // gw, gw, 256)
            rel_h_ref[...] = pltpu.roll(
                g[:, :, :128], iq * (bq // gw), 2, stride=1, stride_axis=0
            ).reshape(bq, 128)
            tiled = None
            for n in range(128 // gw):
                by_col = pltpu.roll(
                    g[:, :, 128:], n * gw, 2, stride=1, stride_axis=1)
                tiled = by_col if tiled is None else jnp.where(
                    lane >= n * gw, by_col, tiled)
            rel_w_ref[...] = tiled.reshape(bq, 128)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def folded(op, t):  # (bq, bk) -> (bq, 128), lane on lane
                return functools.reduce(op, [
                    t[:, j * 128:(j + 1) * 128] for j in range(bk // 128)])

            # the whole row of scores, float32, stays in VMEM: its maximum
            # and its sum are taken lane on lane across the key blocks and
            # across lanes once a query block. (Online, the running maximum
            # costs two reductions and four broadcasts across lanes a key
            # block, and they bound it: 1.99 ms a block and image on ViT-B
            # at key blocks of 512 where this read 1.05.)
            def scores(ik, c):
                cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
                # the key block's rk entries of q.RH, in the free lanes
                by_row = pltpu.roll(
                    rel_h_ref[...], (free + 128 - ik * rk) % 128, 1)
                q_k = jnp.where((lane >= free) & (lane < free + rk),
                                by_row, qs_ref[...]).astype(dtype)
                s = dot(q_k, kal_ref[cols, :], (((1,), (1,)), ((), ())))
                s += jnp.tile(rel_w_ref[...], (1, bk // 128))
                s_ref[:, cols] = s
                m_ref[...] = jnp.maximum(m_ref[...], folded(jnp.maximum, s))
                return c

            jax.lax.fori_loop(0, S // bk, scores, 0)
            m_ref[...] = jnp.broadcast_to(
                jnp.max(m_ref[...], axis=1, keepdims=True), m_ref.shape)

            def weighted(ik, c):
                cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
                p = jnp.exp(
                    s_ref[:, cols] - jnp.tile(m_ref[...], (1, bk // 128)))
                l_ref[...] += folded(jnp.add, p)
                acc_ref[...] += dot(p.astype(dtype), values(cols))
                return c

            jax.lax.fori_loop(0, S // bk, weighted, 0)
            o = (acc_ref[...] / jnp.sum(
                l_ref[...], axis=1, keepdims=True)).astype(dtype)
            if straddles:
                o = dot(o, place).astype(dtype)
            # the slabs are shared with other heads: write this head's
            cur = out_ref[rows, pl.ds(lo, width)]
            out_ref[rows, pl.ds(lo, width)] = jnp.where(mine, o, cur)
            return c

        jax.lax.fori_loop(0, S // bq, query_block, 0)
        return carry

    jax.lax.fori_loop(0, W // head_dim, head, 0)


def packed_global_attention(
    qkv: jnp.ndarray,
    rh: jnp.ndarray,
    rw: jnp.ndarray,
    grid_hw: Tuple[int, int],
    num_heads: int,
    scale: float,
) -> jnp.ndarray:
    """Global attention on the ``qkv`` product's own output: qkv
    (B*S, 3*dim), a row a token (images of S = gh*gw rows one after the
    other), q | k | v along the row and heads within each, as
    ``nn.Dense(3 * dim)`` writes it; rh (gh, gh, D) / rw (gw, gw, D) the
    ``get_rel_pos`` tables, which are Toeplitz (``_toeplitz_lanes``: tables
    that are not give other numbers than the oracle). Returns (B*S, dim),
    what ``proj`` reads. Same math as ``blockwise_decomposed_attention`` on
    the unpacked heads, with q * scale and q.RH rounded to the operand
    dtype; differentiable by recomputing through it."""
    return _packed_global_vjp(qkv, rh, rw, grid_hw, num_heads, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _packed_global_vjp(qkv, rh, rw, grid_hw, num_heads, scale):
    return _packed_global_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _packed_global_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale):
    # a jit of its own: a model's 4 global blocks are one shape, and share
    # one traced and lowered function in the enclosing program
    rows, c3 = qkv.shape
    gh, gw = grid_hw
    S, dim = gh * gw, c3 // 3
    D = dim // num_heads
    if not packed_global_supported(grid_hw, num_heads, D):
        raise ValueError(
            f"grid {grid_hw} at {num_heads} heads of {D} has no packed "
            "layout; gate callers on packed_global_supported()"
        )
    W = _group_lanes(D)
    groups = dim // W
    bq, bk = _global_blocks(grid_hw)
    kernel = functools.partial(
        _packed_global_kernel, head_dim=D, scale=scale, grid_hw=grid_hw,
        bq=bq, bk=bk,
    )
    held = [pltpu.VMEM((S, 128), qkv.dtype)] * (1 if 128 % D == 0 else 2)
    return pl.pallas_call(
        kernel,
        grid=(rows // S, groups),
        in_specs=[
            pl.BlockSpec((S, W), lambda b, g: (b, g)),
            pl.BlockSpec((S, W), lambda b, g: (b, groups + g)),
            pl.BlockSpec((S, W), lambda b, g: (b, 2 * groups + g)),
            pl.BlockSpec((128, 256), lambda b, g: (0, 0)),
            pl.BlockSpec((S, 128), lambda b, g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((S, W), lambda b, g: (b, g)),
        out_shape=jax.ShapeDtypeStruct((rows, dim), qkv.dtype),
        scratch_shapes=held + [pltpu.VMEM((bq, 128), jnp.float32)] * 3 + [
            pltpu.VMEM((bq, S), jnp.float32),
        ] + [pltpu.VMEM((bq, 128), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_GLOBAL_VMEM_BYTES,
        ),
        interpret=jax.default_backend() != "tpu",
    )(
        qkv, qkv, qkv, _global_tables(rh, rw, D, qkv.dtype),
        _global_key_rows(grid_hw, D, bk, qkv.dtype),
    )


def _packed_global_oracle(qkv, rh, rw, grid_hw, num_heads, scale):
    """The same on the exact blockwise path: the heads unpacked,
    head-major."""
    from tmr_tpu.models.vit import blockwise_decomposed_attention

    gh, gw = grid_hw
    dim = qkv.shape[1] // 3
    t = qkv.reshape(-1, gh * gw, 3, num_heads, dim // num_heads)
    q, k, v = jnp.moveaxis(t, 2, 0).transpose(0, 1, 3, 2, 4)
    out = blockwise_decomposed_attention(q, k, v, rh, rw, grid_hw, scale)
    return out.transpose(0, 2, 1, 3).reshape(-1, dim)


def _packed_global_vjp_fwd(qkv, rh, rw, grid_hw, num_heads, scale):
    return _packed_global_fwd_impl(qkv, rh, rw, grid_hw, num_heads, scale), (
        qkv, rh, rw,
    )


def _packed_global_vjp_bwd(grid_hw, num_heads, scale, res, g):
    _, pull = jax.vjp(
        lambda a, b, c: _packed_global_oracle(
            a, b, c, grid_hw, num_heads, scale),
        *res,
    )
    return pull(g)


_packed_global_vjp.defvjp(_packed_global_vjp_fwd, _packed_global_vjp_bwd)


def _packed_global_on_heads(q, k, v, rh, rw, grid_hw, scale):
    """The packed path behind the head-major signature ``_self_check``
    drives: q/k/v (B, H, S, D) packed as ``qkv`` lays them out."""
    B, H, S, D = q.shape
    qkv = jnp.stack([q, k, v], axis=2)  # (B, H, 3, S, D)
    qkv = qkv.transpose(0, 3, 2, 1, 4).reshape(B * S, 3 * H * D)
    out = packed_global_attention(qkv, rh, rw, grid_hw, H, scale)
    return out.reshape(B, S, H, D).transpose(0, 2, 1, 3)


@mosaic_gate
def packed_global_ok(
    gh: int, gw: int, head_dim: int, num_heads: int
) -> bool:
    """Per-geometry compiled self-check of the packed global kernel
    against the exact blockwise oracle, forward and gradients, on one
    image at the model's own heads (the head count and head dim fix every
    lane offset the kernel uses) with tables as ``get_rel_pos`` makes
    them; a side's output and gradients come from one program, so the
    kernel is compiled once."""
    from tmr_tpu.ops.flash_attn import _self_check

    return _self_check(
        _packed_global_on_heads, 1, num_heads, gh, gw, head_dim,
        gate="packed_global_ok", toeplitz=True, one_program=True,
    )


def global_formulation(
    grid_hw: Tuple[int, int], num_heads: int, head_dim: int, dtype,
    use_rel_pos: bool = True,
) -> str:
    """What a block of 1024 tokens or more traces with, by what can be
    observed: on a TPU in bfloat16, with rel-pos tables, at a grid and
    heads that have a packed layout and where the kernel's self-check says
    yes (it says no inside a trace XLA partitions and under
    ``diagnostics.mosaic_kernels_off``), the kernel above (``packed``);
    else in bfloat16 the stock flash kernel on folded operands where its
    gate passes (``flash``); else the exact band scan (``blockwise``):
    float32, the CPU, a partitioned trace. The 96 x 96 grid of the 1536
    bucket (192 projections a token) keeps ``flash``. No knob has a say:
    on the v5e ``packed`` beat ``flash`` end to end in both SAM cells
    (PERF.md section 6)."""
    gh, gw = grid_hw
    if dtype != jnp.bfloat16:
        return "blockwise"
    if (use_rel_pos and jax.default_backend() == "tpu"
            and packed_global_supported(grid_hw, num_heads, head_dim)
            and packed_global_ok(gh, gw, head_dim, num_heads)):
        return "packed"
    from tmr_tpu.ops.flash_attn import flash_attention_ok, flash_supported

    if flash_supported(gh * gw) and flash_attention_ok(gh, gw, head_dim):
        return "flash"
    return "blockwise"

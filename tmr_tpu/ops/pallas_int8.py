"""int8 x int8 -> int32 MXU matmul with a fused dequant epilogue
(TMR_QUANT_KERNEL=pallas).

Why: the stored-int8 path (TMR_QUANT_STORAGE=int8, ops/quant.py) hands
the compiled programs genuine int8 weight leaves — 4x less HBM weight
traffic for those leaves — but the default in-program formulation still widens the
operand to bf16 before the matmul (the bitwise equality-pinned arm).
On TPU the MXU natively multiplies int8 operands at 2x the bf16 rate
into an int32 accumulator; this kernel takes BOTH operands on the int8
grid (the stored weights plus a dynamically quantized activation),
accumulates int8 x int8 in int32 across the K tiles, and applies the
per-row activation scale x per-column weight scale dequant in the f32
epilogue — one multiply per output element, fused after the last K
step. ``preferred_element_type=jnp.int32`` inside the kernel keeps
Mosaic on the integer MXU path.

Numerics: the activation quantization is new rounding relative to the
stored/fake paths, so this arm is admitted through a TOLERANCE gate
(ops/quant.py ``quant_int8dot_ok`` covers the shared epilogue math; the
Mosaic lowering itself is admitted by :func:`pallas_int8_ok`'s compiled
self-check against the XLA int8 dot). It is never the silent default —
``TMR_QUANT_KERNEL`` resolves to the dequant arm unless pallas/int8dot
is explicitly pinned or autotune-elected.

``interpret=True`` must be passed EXPLICITLY for CPU coverage (the
tier-1 parity test does); there is no automatic off-TPU interpret
switch — off-TPU the production path simply never reaches this kernel
because :func:`pallas_int8_ok` refuses with a recorded "backend" cause
like every Mosaic gate.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import gate_refused, mosaic_gate, run_outside_trace

#: MXU-shaped tiles: 128-lane aligned in every dimension. K tiles of 256
#: keep the int8 operand blocks at 32 KB each; the int32 accumulator
#: scratch is block_m x block_n x 4 bytes (64 KB at the defaults).
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 256


def _int8_mm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                    nk: int):
    """One (block_m, block_n) output tile: int32 accumulation over the K
    grid axis, f32 scale epilogue on the last K step."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * (sx_ref[...] * sw_ref[...])
        )


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def int8_matmul(x_q, w_q, x_scale, w_scale,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                block_k: int = DEFAULT_BLOCK_K,
                interpret: bool = False):
    """(M, K) int8 x (K, N) int8 -> (M, N) f32.

    ``x_scale``: (M, 1) f32 per-row activation scales; ``w_scale``:
    (1, N) f32 per-output-channel weight scales. Ragged shapes pad up to
    the tile grid with zeros (zero rows/columns contribute zero to the
    int32 accumulator) and slice back.
    """
    m, k = x_q.shape
    n = w_q.shape[1]
    xp = _pad_to(_pad_to(x_q, block_m, 0), block_k, 1)
    wp = _pad_to(_pad_to(w_q, block_k, 0), block_n, 1)
    sxp = _pad_to(x_scale.astype(jnp.float32), block_m, 0)
    swp = _pad_to(w_scale.astype(jnp.float32), block_n, 1)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    nk = kp // block_k
    kernel = functools.partial(_int8_mm_kernel, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(mp // block_m, np_ // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, s: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(xp, wp, sxp, swp)
    return out[:m, :n]


@mosaic_gate
def pallas_int8_ok(m: int = 256, k: int = 256, n: int = 256) -> bool:
    """Compiled self-check of the Mosaic int8 kernel against the XLA
    int8 dot at a small MXU-aligned shape. Any exception or disagreement
    (the integer part is exact, so the check is equality up to f32 scale
    rounding) refuses with a recorded cause; off-TPU refuses with
    cause "backend" like every Mosaic gate. TMR_NO_PALLAS_INT8=1
    force-disables."""
    cfg = {"M": m, "K": k, "N": n}
    if os.environ.get("TMR_NO_PALLAS_INT8"):
        return gate_refused("pallas_int8_ok",
                            "TMR_NO_PALLAS_INT8 kill-switch",
                            "kill-switch", config=cfg)
    if jax.default_backend() != "tpu":
        return gate_refused(
            "pallas_int8_ok",
            f"backend {jax.default_backend()!r} != 'tpu'", "backend",
            config=cfg,
        )
    import numpy as np

    def check() -> float:
        rng = np.random.default_rng(0)
        xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
        sx = jnp.asarray(rng.random((m, 1)) * 0.01 + 1e-4, jnp.float32)
        sw = jnp.asarray(rng.random((1, n)) * 0.01 + 1e-4, jnp.float32)
        got = np.asarray(int8_matmul(xq, wq, sx, sw, interpret=False))
        want = np.asarray(
            jax.lax.dot_general(
                xq, wq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * (sx * sw)
        )
        return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-6))

    try:
        rel = run_outside_trace(check, gate="pallas_int8_ok")
        ok = rel < 1e-6
        if not ok:
            gate_refused("pallas_int8_ok", f"rel err {rel:.4g} >= 1e-6",
                         "forward-mismatch", config=cfg)
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        ok = gate_refused("pallas_int8_ok", f"{type(e).__name__}: {e}",
                          "exception", config=cfg,
                          exception=type(e).__name__)
    return ok

"""Depthwise cross-correlation as a Pallas TPU kernel (TMR_XCORR_IMPL=pallas).

Why: the matcher's per-image depthwise correlation (reference
template_matching.py:23-41) has no channel reduction, so it can't feed the
MXU's contraction dimension — XLA lowers the ``feature_group_count=B*C``
grouped conv through generic conv machinery that on TPU pays layout
transposes and multi-pass f32 emulation at ``Precision.HIGHEST``
(ops/xcorr.py). The operation itself is just T^2 shifted multiply-adds over
the (H, W) map per channel — pure VPU work. This kernel expresses exactly
that: each grid program holds one (CB-channel, padded-H, padded-W) block in
VMEM and accumulates the T^2 statically-unrolled shifted products in f32.

Numerics: inputs are multiplied after an upcast to f32 and accumulated in
f32, so with f32 inputs the result matches the HIGHEST-precision conv path
(true f32 — the VPU does not do bf16-split emulation), and with bf16 inputs
(TMR_XCORR_PRECISION=bf16) it matches that path's f32-accumulator contract.

Scope: small-capacity buckets only (T <= MAX_UNROLL_T); the unroll count is
T^2, and capacities above the cap fall back to the conv lowering in the
dispatcher (the >65 buckets take the FFT path anyway, ops/xcorr.py).

Not selectable on a TPU today: the chip's compiler refuses the kernel
(``_MOSAIC_REFUSAL`` below), so ``pallas_xcorr_ok`` answers no there with
that cause. ``interpret=True`` (automatic off-TPU) keeps the CPU tests of
the kernel's semantics honest.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: largest template capacity the statically-unrolled kernel accepts: the
#: kernel body is T^2 slice+FMA steps, and past ~33 (1089 steps) Mosaic
#: compile time grows out of proportion to the op's share of the program.
MAX_UNROLL_T = 33

#: channels per grid program: VMEM block is CB*(H+T-1)*(W+T-1)*4 bytes for
#: the padded feature plus the CB*H*W f32 accumulator — 8 keeps the worst
#: production shape (H=W=192, T=33) near 2.5 MB, well inside VMEM.
_CB = 8


#: Retired from what a TPU can select (PR 23): the chip's compiler refuses
#: the per-channel template broadcast at every shape. Read per channel as
#: scalars instead, the kernel lowers, but at the production shape
#: (C 512, 128x128, T 15) its T^2 * CB unroll takes 234 s to compile and
#: overruns VMEM ("Scoped allocation with size 21.68M and limit 16.00M").
#: The kernel stays for the interpreter tests; ROADMAP Design item 2
#: decides whether it is rewritten or deleted.
_MOSAIC_REFUSAL = (
    "Mosaic (jaxlib 0.9.0, v5e): Not implemented: Broadcast in both "
    "sublanes and lanes — vector.broadcast vector<8x1x1xf32> -> "
    "vector<8x128x128xf32>"
)


def _xcorr_kernel(fpad_ref, tmpl_ref, out_ref, *, T: int, H: int, W: int):
    """One (CB, H, W) output block: sum of T^2 shifted products.

    fpad_ref: (1, CB, H+T-1, W+T-1); tmpl_ref: (1, CB, T, T);
    out_ref: (1, CB, H, W). The T^2 loop is a static Python unroll — every
    slice has static offsets, so Mosaic sees straight-line vector code.
    """
    fpad = fpad_ref[0].astype(jnp.float32)
    tmpl = tmpl_ref[0].astype(jnp.float32)
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
    for i in range(T):
        for j in range(T):
            acc = acc + fpad[:, i : i + H, j : j + W] * tmpl[:, i, j][
                :, None, None
            ]
    out_ref[0] = acc


@functools.partial(
    jax.jit, static_argnames=("interpret",)
)
def _run_xcorr(fpad, tmpl, interpret: bool = False):
    B, C, HP, WP = fpad.shape
    T = tmpl.shape[-1]
    H = HP - (T - 1)
    W = WP - (T - 1)
    cb = _CB if C % _CB == 0 else 1
    kernel = functools.partial(_xcorr_kernel, T=T, H=H, W=W)
    return pl.pallas_call(
        kernel,
        grid=(B, C // cb),
        in_specs=[
            pl.BlockSpec((1, cb, HP, WP), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, cb, T, T), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, cb, H, W), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C, H, W), jnp.float32),
        interpret=interpret,
    )(fpad, tmpl)


def xcorr_pallas(
    feature: jnp.ndarray,
    template: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """SAME-padded depthwise correlation, f32 result.

    feature: (B, C, H, W); template: (B, C, T, T), T odd. Semantics equal
    ops/xcorr.py's grouped-conv path (zero padding T//2 per side, no kernel
    flip — correlation, not convolution)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T = template.shape[-1]
    c = T // 2
    fpad = jnp.pad(
        feature, ((0, 0), (0, 0), (c, T - 1 - c), (c, T - 1 - c))
    )
    return _run_xcorr(fpad, template, interpret=interpret)


def pallas_xcorr_ok(C: int, H: int, W: int, T: int) -> bool:
    """Whether the dispatcher may run the kernel at (C, H, W, T): never
    today. Every refusal records its structured cause — kill-switch
    (TMR_NO_PALLAS_XCORR=1), capacity above the unroll cap, wrong backend,
    and on a TPU the compiler's own refusal (``_MOSAIC_REFUSAL``) — and the
    dispatcher falls back to the conv lowering."""
    from tmr_tpu.diagnostics import gate_refused

    def _refused(reason: str, cause: str) -> bool:
        return gate_refused("pallas_xcorr_ok", reason, cause,
                            config={"C": C, "H": H, "W": W, "T": T})

    if os.environ.get("TMR_NO_PALLAS_XCORR"):
        return _refused("TMR_NO_PALLAS_XCORR kill-switch",
                        cause="kill-switch")
    if T > MAX_UNROLL_T:
        return _refused(f"T {T} > MAX_UNROLL_T {MAX_UNROLL_T}",
                        cause="unsupported-shape")
    if jax.default_backend() != "tpu":
        return _refused(f"backend {jax.default_backend()!r} != 'tpu'",
                        cause="backend")
    return _refused(_MOSAIC_REFUSAL, cause="unsupported-shape")

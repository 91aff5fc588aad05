"""Greedy NMS as a Pallas TPU kernel.

The pure-XLA path (ops/nms.py) expresses greedy NMS as a fixpoint of masked
bool-matmuls: each iteration is an (N, N) matrix product, and the iteration
count is the suppression-chain depth. This kernel instead runs the *true*
sequential greedy algorithm — the one torchvision's CUDA kernel implements
(reference utils/TM_utils.py:6,322) — in one pass: boxes live in VMEM
(N x 4 floats, KBs), a ``fori_loop`` walks boxes in score order, and each
step suppresses all later boxes overlapping the current survivor with one
N-wide VPU IoU evaluation. O(N^2) lanes total, no (N, N) matrix ever
materialized, sequential dependency expressed directly instead of iterated
to convergence.

Input must be pre-sorted by descending score (do the sort with XLA outside —
its bitonic sorter is fine); wrapper :func:`nms_keep_mask_pallas` handles
sort/unsort and matches ops/nms.py bit-for-bit on the keep decision.

Runs compiled on TPU; ``interpret=True`` (automatic off-TPU) keeps CPU tests
honest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmr_tpu.diagnostics import mosaic_gate


def _nms_kernel(thr_ref, rows_ref, boxes_ref, valid_ref, keep_ref, *,
                n_real: int):
    """Score-sorted boxes twice: coordinate-major and lane-dense (4, R, 128)
    for the N-wide vector work, and row-major (R * 128, 4) for the per-step
    scalar reads; valid/keep (R, 128) int32.

    Box i's liveness is a masked reduction over the keep vector: a dynamic
    scalar load from a 1-D VMEM ref is what Mosaic refuses ("cannot
    statically prove that index in dimension 0 is a multiple of 1024"),
    while a dynamic row of a 2-D ref lowers.
    """
    r = valid_ref.shape[0]
    x1 = boxes_ref[0]
    y1 = boxes_ref[1]
    x2 = boxes_ref[2]
    y2 = boxes_ref[3]
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)
    thr = thr_ref[0]
    idx = (
        jax.lax.broadcasted_iota(jnp.int32, (r, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (r, 128), 1)
    )

    keep_ref[...] = valid_ref[...]

    def body(i, _):
        # IoU of box i against every box (vectorized over lanes)
        bx1 = rows_ref[i, 0]
        by1 = rows_ref[i, 1]
        bx2 = rows_ref[i, 2]
        by2 = rows_ref[i, 3]
        barea = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0)
        inter = iw * ih
        iou = inter / jnp.maximum(area + barea - inter, 1e-12)

        keep = keep_ref[...]
        alive = jnp.max(jnp.where(idx == i, keep, 0)) > 0
        suppress = alive & (idx > i) & (iou > thr)
        keep_ref[...] = jnp.where(suppress, 0, keep)
        return 0

    jax.lax.fori_loop(0, n_real, body, 0)


@functools.partial(jax.jit, static_argnames=("n_real", "interpret"))
def _run_nms_kernel(boxes, valid, thr, n_real: int, interpret: bool = False):
    """boxes (R * 128, 4) f32, valid (R, 128) int32 -> keep (R, 128) int32;
    the first ``n_real`` slots are real, the rest padding."""
    return pl.pallas_call(
        functools.partial(_nms_kernel, n_real=n_real),
        out_shape=jax.ShapeDtypeStruct(valid.shape, jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(thr, boxes, boxes.T.reshape(4, -1, 128), valid)


def nms_keep_mask_pallas(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Drop-in replacement for ops/nms.py nms_keep_mask (same semantics,
    same original-order output). ``interpret`` defaults to True off-TPU
    (the CPU tests) and is never chosen on a TPU backend."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    sort_scores = jnp.where(valid, scores, -jnp.inf)
    order = jnp.argsort(-sort_scores)
    b = boxes[order].astype(jnp.float32)
    v = valid[order].astype(jnp.int32)
    # pad to whole (8, 128) int32/f32 vregs; padded slots are valid=0 so
    # they neither suppress nor survive
    pad = (-n) % 1024
    if pad:
        b = jnp.pad(b, ((0, pad), (0, 0)))
        v = jnp.pad(v, (0, pad))
    thr = jnp.asarray([iou_threshold], jnp.float32)
    keep = _run_nms_kernel(
        b, v.reshape(-1, 128), thr, n_real=n,
        interpret=interpret,
    )
    keep_sorted = keep.reshape(-1)[:n] > 0
    return jnp.zeros((n,), bool).at[order].set(keep_sorted)


def nms_topk(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
    k: int | None = None,
    interpret: bool | None = None,
    backend: str = "auto",
) -> dict:
    """Batched greedy NMS with a fixed-size padded compact output — NMS +
    top-k box gather in one call, sharing the device decode tail's
    padded-output contract (``count`` + zeroed dead slots). NOTE the
    Predictor's device tail itself compacts with
    ops/postprocess.compact_detections — slot-order-preserving, which the
    bitwise host-parity pin requires — while this primitive reorders
    score-descending; it is the standalone building block for callers
    that want ranked survivors (gallery/union-NMS style batch matching),
    not a drop-in for _refine_nms.

    boxes: (B, N, 4) xyxy; scores: (B, N); valid: optional (B, N) bool.
    Returns {"count" (B,) int32, "boxes" (B, k, 4), "scores" (B, k),
    "index" (B, k) int32}: the surviving boxes per image in descending
    score order (ties break toward the lower input slot — lax.top_k is
    index-stable, so the output is deterministic), compacted to the
    leading ``count`` slots; everything past ``count`` is zeroed (boxes,
    scores) with index -1. ``k`` defaults to N; ``k`` larger than the
    survivor count simply pads (the degenerate cases — all-suppressed,
    empty valid, k > survivors — are pinned by tests/test_pallas_ops.py).

    backend: "auto" uses the Pallas sequential-greedy kernel where its
    self-check admits it and the XLA fixpoint elsewhere (exact same keep
    decisions, tests/test_pallas_ops.py); "pallas"/"xla" force.
    """
    b, n = scores.shape
    k = n if k is None else int(k)
    if valid is None:
        valid = jnp.ones((b, n), bool)
    if backend == "auto":
        backend = (
            "pallas"
            if jax.default_backend() == "tpu" and pallas_nms_compiled_ok()
            else "xla"
        )
    if backend == "pallas":
        fn = lambda bx, s, v: nms_keep_mask_pallas(
            bx, s, iou_threshold, v, interpret=interpret
        )
    else:
        from tmr_tpu.ops.nms import nms_keep_mask

        fn = lambda bx, s, v: nms_keep_mask(bx, s, iou_threshold, v)
    keep = jax.vmap(fn)(boxes, scores, valid)

    ranked = jnp.where(keep, scores, -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(ranked, min(k, n))
    if k > n:  # more output slots than inputs: pad the gather itself
        pad = k - n
        top_scores = jnp.pad(top_scores, ((0, 0), (0, pad)),
                             constant_values=-jnp.inf)
        top_idx = jnp.pad(top_idx, ((0, 0), (0, pad)))
    count = jnp.minimum(keep.sum(axis=1), k).astype(jnp.int32)
    ok = jnp.arange(k)[None, :] < count[:, None]
    gather = jax.vmap(lambda a, i: a[i])
    return {
        "count": count,
        "boxes": jnp.where(ok[..., None], gather(boxes, top_idx), 0.0),
        "scores": jnp.where(ok, top_scores, 0.0),
        "index": jnp.where(ok, top_idx, -1),
    }


@mosaic_gate
def pallas_nms_compiled_ok() -> bool:
    """One-time self-check of the *compiled* kernel on this backend.

    Runs a small randomized case (N deliberately not a lane multiple) through
    the compiled Pallas kernel and the XLA fixpoint (ops/nms.py) and compares
    keep decisions. A refusal — wrong backend, a Mosaic lowering error, a
    mismatch — is recorded with its structured cause
    (diagnostics.record_gate_refusal) and returns False, so ``auto`` callers
    take the XLA path and the cause stays visible.
    """
    import numpy as np

    from tmr_tpu.diagnostics import gate_refused, run_outside_trace
    from tmr_tpu.ops.nms import nms_keep_mask

    if jax.default_backend() != "tpu":
        return gate_refused(
            "pallas_nms_compiled_ok",
            f"backend {jax.default_backend()!r} != 'tpu'", "backend",
        )

    def check() -> bool:
        rng = np.random.default_rng(0)
        n = 150  # not a multiple of 128 -> exercises the padding path
        xy = rng.uniform(0.0, 0.8, (n, 2)).astype(np.float32)
        wh = rng.uniform(0.05, 0.3, (n, 2)).astype(np.float32)
        boxes = jnp.asarray(np.concatenate([xy, xy + wh], -1))
        scores = jnp.asarray(rng.uniform(size=n).astype(np.float32))
        valid = jnp.asarray(rng.uniform(size=n) > 0.2)
        got = nms_keep_mask_pallas(boxes, scores, 0.5, valid, interpret=False)
        want = nms_keep_mask(boxes, scores, 0.5, valid)
        return bool(jnp.array_equal(got, want))

    try:
        if run_outside_trace(check, gate="pallas_nms_compiled_ok"):
            return True
    except Exception as e:
        return gate_refused(
            "pallas_nms_compiled_ok", f"{type(e).__name__}: {e}",
            "exception", exception=type(e).__name__,
        )
    return gate_refused(
        "pallas_nms_compiled_ok",
        "keep decisions differ from the XLA fixpoint", "forward-mismatch",
    )

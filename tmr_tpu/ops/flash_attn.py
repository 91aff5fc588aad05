"""Flash attention for the ViT's global blocks, rel-pos folded into QK.

The SAM encoder's global-attention blocks add a *decomposed relative
position* bias (reference sam_ViT.py:325-361):

    bias[t=(y,x), u=(ky,kx)] = q[t].RH[y,ky] + q[t].RW[x,kx]

A fused (flash) attention kernel cannot take a per-pair bias without
materializing it — which is the whole thing being avoided. The trick here
folds the bias INTO the contraction, making biased attention a *standard*
attention any flash kernel runs unmodified:

    q' = [ q*scale | rel_h_q | rel_w_q ]        (D + gh + gw features)
    k' = [ k       | onehot(ky) | onehot(kx) ]

so  q'.k' = scale*(q.k) + rel_h_q[t, ky] + rel_w_q[t, kx]  exactly, where
rel_h_q = einsum(q, RH) (B, H, S, gh) and rel_w_q = einsum(q, RW) are the
cheap O(S*grid) projections. With gh = gw = 64 and D = 64 the augmented
head dim is 192, padded to 256 for MXU lane alignment — ~4x the qk FLOPs of
the plain path, a few extra ms at v5e peak, in exchange for ZERO S x S HBM
traffic inside jax.experimental.pallas's TPU flash kernel (VMEM-resident
tiles, online softmax).

Used by models/vit.py on the TPU bf16 path where the packed kernel
(ops/pallas_attn.py) has no layout or its gate refuses and this path's own
per-geometry compiled self-check passes (``flash_attention_ok``): the 96 x
96 grid of the 1536 bucket, and blocks without rel-pos tables. Every other
configuration takes the packed kernel or the exact XLA blockwise path.

``_self_check`` here is the one compiled self-check every attention kernel's
gate runs against that blockwise oracle.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tmr_tpu.diagnostics import mosaic_gate


def fold_rel_pos_into_qk(
    q: jnp.ndarray,
    k: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    grid_hw: Tuple[int, int],
    scale: float,
    pad_to: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, H, S, D) q/k + (gh, gh, D)/(gw, gw, D) tables -> augmented q', k'
    with q'.k'^T == scale * q.k^T + decomposed rel-pos bias (exact in f32).

    With rh/rw None the bias terms are skipped (q' = q*scale, k' = k, plus
    optional zero-padding). ``pad_to`` zero-pads the feature axis (zeros
    contribute nothing to the contraction) for lane alignment.
    """
    B, H, S, D = q.shape
    gh, gw = grid_hw
    parts_q = [q * jnp.asarray(scale, q.dtype)]
    parts_k = [k]
    if rh is not None:
        r_q = q.reshape(B, H, gh, gw, D).astype(jnp.float32)
        rel_h_q = jnp.einsum(
            "bhywd,ykd->bhywk", r_q, rh.astype(jnp.float32)
        ).reshape(B, H, S, gh)
        rel_w_q = jnp.einsum(
            "bhywd,wkd->bhywk", r_q, rw.astype(jnp.float32)
        ).reshape(B, H, S, gw)
        parts_q += [rel_h_q.astype(q.dtype), rel_w_q.astype(q.dtype)]
        # key token u = ky*gw + kx selects its bias entries via one-hots
        rows = jnp.repeat(jnp.eye(gh, dtype=k.dtype), gw, axis=0)  # (S, gh)
        cols = jnp.tile(jnp.eye(gw, dtype=k.dtype), (gh, 1))  # (S, gw)
        parts_k += [
            jnp.broadcast_to(rows[None, None], (B, H, S, gh)),
            jnp.broadcast_to(cols[None, None], (B, H, S, gw)),
        ]
    q_aug = jnp.concatenate(parts_q, axis=-1)
    k_aug = jnp.concatenate(parts_k, axis=-1)
    if pad_to is not None and q_aug.shape[-1] < pad_to:
        pad = pad_to - q_aug.shape[-1]
        widths = ((0, 0), (0, 0), (0, 0), (0, pad))
        q_aug = jnp.pad(q_aug, widths)
        k_aug = jnp.pad(k_aug, widths)
    return q_aug, k_aug


def _lane_pad(d: int) -> int:
    return ((d + 127) // 128) * 128


def _block_for(s: int, preferred: int) -> Optional[int]:
    """Largest power-of-two block <= preferred that divides ``s`` (the stock
    kernel asserts seq_len % block == 0); None when s has no usable
    power-of-two factor >= 128."""
    b = preferred
    while b >= 128:
        if s % b == 0:
            return b
        b //= 2
    return None


def flash_supported(seq_len: int) -> bool:
    """True when the stock kernel's block constraints can be met for S."""
    return _block_for(seq_len, 512) is not None


def flash_decomposed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    grid_hw: Tuple[int, int],
    scale: float,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """Pallas TPU flash attention over the augmented q'/k' (bias exact up to
    input-dtype rounding). q/k/v: (B, H, S, D); returns (B, H, S, D)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    B, H, S, D = q.shape
    d_aug = D + (grid_hw[0] + grid_hw[1] if rh is not None else 0)
    pad_to = _lane_pad(d_aug)
    q_aug, k_aug = fold_rel_pos_into_qk(
        q, k, rh, rw, grid_hw, scale, pad_to=pad_to
    )
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_to - D)))
    bq = _block_for(S, block_q)
    bk = _block_for(S, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"sequence length {S} has no power-of-two block >= 128; gate "
            "callers on flash_supported()"
        )
    sizes = BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )
    out = flash_attention(
        q_aug, k_aug, v_pad, causal=False, sm_scale=1.0, block_sizes=sizes
    )
    return out[..., :D].astype(q.dtype)


def flash_windowed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    window_hw: Tuple[int, int],
    scale: float,
) -> jnp.ndarray:
    """Stock Pallas flash kernel over 196-token attention windows.

    No model path calls this any more (``pallas_attn.window_formulation``
    answers ``packed`` or ``dense``): it is kept as ``flash_window_ok``'s
    subject, which is kept for the benchmark's driver alone (see there).

    The ViT's windowed blocks attend within 14x14=196-token windows — below
    the kernel's 128 block granularity and not a power-of-two multiple. The
    windows are therefore zero-padded to the next 128 multiple (256) and the
    pad tokens put in a SECOND segment: the kernel's segment mask keeps real
    queries attending to exactly the 196 real keys, pad rows attend only to
    pad (zero V -> zero output) and are sliced off. Rel-pos bias rides
    inside QK via fold_rel_pos_into_qk (d_aug = 64+14+14 = 92 -> 128 lanes).

    q/k/v: (B', H, S, D) with B' = B * n_windows, S = win_h * win_w.
    Returns (B', H, S, D). Numerics: online-softmax flash over the same
    masked score matrix the dense path materializes.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention,
    )

    B, H, S, D = q.shape
    gh, gw = window_hw
    d_aug = D + (gh + gw if rh is not None else 0)
    pad_to = _lane_pad(d_aug)
    q_aug, k_aug = fold_rel_pos_into_qk(
        q, k, rh, rw, window_hw, scale, pad_to=pad_to
    )
    s_pad = _lane_pad(S)
    ps = s_pad - S
    widths = ((0, 0), (0, 0), (0, ps), (0, 0))
    q_aug = jnp.pad(q_aug, widths)
    k_aug = jnp.pad(k_aug, widths)
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, ps), (0, pad_to - D)))
    seg = jnp.concatenate(
        [jnp.zeros((B, S), jnp.int32), jnp.ones((B, ps), jnp.int32)], axis=-1
    )
    bq = _block_for(s_pad, 256)
    bk = _block_for(s_pad, 256)
    sizes = BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )
    out = flash_attention(
        q_aug, k_aug, v_pad, segment_ids=SegmentIds(q=seg, kv=seg),
        causal=False, sm_scale=1.0, block_sizes=sizes,
    )
    return out[..., :S, :D].astype(q.dtype)


def _self_check(
    attn_fn, B: int, H: int, gh: int, gw: int, D: int,
    require_tpu: bool = True,
    gate: Optional[str] = None,
    config: Optional[dict] = None,
    toeplitz: bool = False,
    one_program: bool = False,
) -> bool:
    """Shared compiled self-check: run ``attn_fn`` (a flash-path callable
    with the (q, k, v, rh, rw, grid_hw, scale) signature) against the exact
    XLA blockwise path on bf16 inputs at the given geometry. Any exception
    (Mosaic lowering, unsupported backend) or disagreement beyond bf16
    tolerance -> False. TMR_NO_FLASH_ATTN=1 force-disables.

    ``require_tpu=False`` lifts the backend requirement and the
    kill-switch, so that a test can run a kernel's check in the Pallas
    interpreter.

    Callers invoke this while TRACING the model (Attention.__call__ only
    ever runs under jit), so the whole check runs outside the ambient trace
    (``diagnostics.run_outside_trace``) — concrete values, real compiled
    executions, the Pallas kernels' included.

    Every refusal records a STRUCTURED cause (diagnostics.record_gate_
    refusal: category, swallowed exception class + message, the gate's
    ``gate`` name and ``config`` — its cache key made explicit — plus the
    device kind) so "Mosaic can't lower this", "kernel miscompiles
    numerically", and "wrong backend" stay distinguishable after the fact
    (round-5 verdict #1). ``TMR_GATE_DEBUG=1`` additionally mirrors each
    reason to stderr for interactive runs.

    ``toeplitz`` draws the rel-pos tables as the model makes them
    (``get_rel_pos`` of a random parameter: entry [y, ky] depends on
    y - ky alone), for a subject that relies on it; else every entry is
    drawn on its own. ``one_program`` takes a side's output and gradients
    from one compiled program (``value_and_grad`` with the output beside
    the loss), for a subject whose forward under differentiation is its
    plain forward: a chip compiles a Mosaic kernel at every load of a
    program that holds it, so the kernel is paid once, not twice.
    """
    from tmr_tpu.diagnostics import record_gate_refusal

    gate_name = gate or getattr(attn_fn, "__name__", str(attn_fn))
    gate_config = {
        "B": B, "H": H, "gh": gh, "gw": gw, "head_dim": D,
        **(config or {}),
    }

    def _refused(
        reason: str, cause: str = "exception", exception: Optional[str] = None
    ) -> bool:
        record_gate_refusal(
            gate_name, cause, message=reason, exception=exception,
            config=gate_config,
        )
        if os.environ.get("TMR_GATE_DEBUG"):
            import sys

            print(
                f"[gate] {gate_name} "
                f"B{B} H{H} {gh}x{gw} D{D}: refused — {reason}",
                file=sys.stderr,
            )
        return False

    if require_tpu:
        if os.environ.get("TMR_NO_FLASH_ATTN"):
            return _refused("TMR_NO_FLASH_ATTN kill-switch",
                            cause="kill-switch")
        if jax.default_backend() != "tpu":
            return _refused(f"backend {jax.default_backend()!r} != 'tpu'",
                            cause="backend")
    import numpy as np

    from tmr_tpu.diagnostics import run_outside_trace
    from tmr_tpu.models.vit import blockwise_decomposed_attention, get_rel_pos

    def check() -> bool:
        rng = np.random.default_rng(0)
        S = gh * gw
        q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
        def table(g):
            if toeplitz:
                return get_rel_pos(g, g, jnp.asarray(
                    rng.standard_normal((2 * g - 1, D)) * 0.2, jnp.float32))
            return jnp.asarray(
                rng.standard_normal((g, g, D)) * 0.2, jnp.float32)

        rh, rw = table(gh), table(gw)
        scale = D**-0.5

        def both(fn):  # ((loss, output), gradients) of one program
            def run(*a):
                out = fn(*a, rh, rw, (gh, gw), scale)
                return jnp.sum(out.astype(jnp.float32) ** 2), out
            return jax.jit(jax.value_and_grad(
                run, argnums=(0, 1, 2), has_aux=True))(q, k, v)

        if one_program:
            (_, got), g_got = both(attn_fn)
            (_, want), g_want = both(blockwise_decomposed_attention)
        else:
            got = jax.jit(lambda *a: attn_fn(*a, (gh, gw), scale))(
                q, k, v, rh, rw
            )
            want = jax.jit(
                lambda *a: blockwise_decomposed_attention(
                    *a, (gh, gw), scale)
            )(q, k, v, rh, rw)
        err = np.abs(
            np.asarray(got, np.float32) - np.asarray(want, np.float32)
        ).max()
        scale_ref = np.abs(np.asarray(want, np.float32)).max() + 1e-6
        # NOTE: comparisons are phrased as ``not (diff < tol)`` so a NaN
        # (classic Mosaic-miscompile symptom) REJECTS — ``diff >= tol``
        # would let NaN through, since both comparisons are False on NaN
        if not (err / scale_ref < 0.05):
            return _refused(
                f"forward rel err {err / scale_ref:.4g} >= 0.05",
                cause="forward-mismatch",
            )

        # the TRAIN step differentiates through whichever path is
        # active, and a backward-pass Mosaic failure would otherwise
        # surface unguarded inside the train trace — so the gate also
        # compiles and compares gradients w.r.t. q/k/v
        def loss_of(fn):
            return lambda *a: jnp.sum(
                fn(*a, rh, rw, (gh, gw), scale).astype(jnp.float32) ** 2
            )

        if not one_program:
            g_got = jax.jit(jax.grad(loss_of(attn_fn), argnums=(0, 1, 2)))(
                q, k, v
            )
            g_want = jax.jit(
                jax.grad(
                    loss_of(blockwise_decomposed_attention),
                    argnums=(0, 1, 2)
                )
            )(q, k, v)
        for i, (a, b) in enumerate(zip(g_got, g_want)):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-6)
            if not (rel < 0.05):
                return _refused(
                    f"grad arg {i} rel err {rel:.4g} >= 0.05",
                    cause="grad-mismatch",
                )
        return True

    try:
        return run_outside_trace(check, gate=gate_name)
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        return _refused(f"{type(e).__name__}: {e}", cause="exception",
                        exception=type(e).__name__)


@mosaic_gate
def flash_window_ok(gh: int, gw: int, head_dim: int) -> bool:
    """Per-geometry compiled self-check of the windowed flash path at the
    window grid and head dim given.

    Kept for the benchmark's driver alone: nothing in the program asks
    this gate since the windowed blocks lost their ``flash`` formulation,
    but ``benchmarks/drivers/offline_predict.py:_say_gates`` calls it in
    every SAM set-up and ``benchmarks/compile_check.py`` patches it by
    name. When a ``benchmark`` PR drops those two reads, this gate and
    ``flash_windowed_attention`` go (ROADMAP.md Design 3a)."""
    return _self_check(flash_windowed_attention, 2, 2, gh, gw, head_dim,
                       gate="flash_window_ok")


@mosaic_gate
def flash_attention_ok(
    gh: int = 64, gw: int = 64, head_dim: int = 64
) -> bool:
    """Per-geometry compiled self-check of the global-attention flash path.

    Callers pass the ACTUAL token grid and head dim about to run — vit_b @
    1024 is (64, 64, 64) (S=4096, 8 key blocks of 512, d_aug 192 lane-padded
    to 256), vit_h differs in head_dim (80), the 1536 bucket in grid (96x96)
    — and each geometry gets its own checked cache entry, reduced only in
    batch/heads (grid/blocks/d are what Mosaic failures key on). A
    config-specific failure must trip inside the check, not in the model
    trace."""
    return _self_check(flash_decomposed_attention, 1, 2, gh, gw, head_dim,
                       gate="flash_attention_ok")

"""SAM image-encoder ViT in Flax (ViTDet-style windowed attention).

A TPU-first re-implementation of the reference encoder
(models/backbone/sam/sam_ViT.py + sam.py):

- NHWC end to end (TPU-native layout); tokens keep their (H, W) grid.
- Windowed attention (window 14) with 4 global-attention blocks; window
  padding shapes are static under jit.
- Decomposed relative position bias (sam_ViT.py:292-361) with the index
  tables precomputed at trace time (static shapes), and linear interpolation
  of the tables for non-native grids (the 1536-input bucket).
- Absolute position embeddings bilinearly resized for non-64 grids
  (sam.py:72-76).
- Configurable compute dtype: params stay f32, activations/matmuls can run
  bf16 (MXU-native); softmax runs f32.

Weight layout intentionally mirrors the reference module tree so the
``.pth -> params`` converter (utils/convert.py) is a mechanical transpose.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tmr_tpu.diagnostics import FormulationFallbackWarning  # noqa: F401
from tmr_tpu.models.common import LayerNorm2d, MLPBlock


def window_partition(x: jnp.ndarray, window: int):
    """(B, H, W, C) -> (B*nW, window, window, C), padding to multiples.

    Mirrors sam_ViT.py:243-264; all shapes static under jit.
    """
    b, h, w, c = x.shape
    pad_h = (window - h % window) % window
    pad_w = (window - w % window) % window
    if pad_h or pad_w:
        x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    hp, wp = h + pad_h, w + pad_w
    x = x.reshape(b, hp // window, window, wp // window, window, c)
    windows = x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, window, window, c)
    return windows, (hp, wp)


def window_unpartition(
    windows: jnp.ndarray, window: int, pad_hw: Tuple[int, int], hw: Tuple[int, int]
) -> jnp.ndarray:
    """Inverse of window_partition (sam_ViT.py:267-289)."""
    hp, wp = pad_hw
    h, w = hw
    b = windows.shape[0] // (hp * wp // window // window)
    x = windows.reshape(b, hp // window, wp // window, window, window, -1)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, hp, wp, -1)
    return x[:, :h, :w, :]


def _interp_rel_pos(rel_pos: jnp.ndarray, target_len: int) -> jnp.ndarray:
    """Linear resize of a (L, C) rel-pos table to (target_len, C).

    Matches F.interpolate(mode='linear', align_corners=False)
    (sam_ViT.py:306-313); identity when lengths agree.
    """
    if rel_pos.shape[0] == target_len:
        return rel_pos
    return jax.image.resize(
        rel_pos, (target_len, rel_pos.shape[1]), method="linear", antialias=False
    )


def get_rel_pos(q_size: int, k_size: int, rel_pos: jnp.ndarray) -> jnp.ndarray:
    """(Lq= q_size, Lk= k_size) table lookup of sam_ViT.py:292-322."""
    max_rel_dist = int(2 * max(q_size, k_size) - 1)
    rel = _interp_rel_pos(rel_pos, max_rel_dist)
    # static integer index matrix (shapes are static under jit)
    q_coords = np.arange(q_size)[:, None] * max(k_size / q_size, 1.0)
    k_coords = np.arange(k_size)[None, :] * max(q_size / k_size, 1.0)
    rel_coords = (q_coords - k_coords) + (k_size - 1) * max(q_size / k_size, 1.0)
    return rel[rel_coords.astype(np.int64)]


def _scores_dtype() -> str:
    """TMR_GLOBAL_SCORES_DTYPE: materialization dtype for the folded global
    attention score tiles — 'f32' (default, exact) or 'bf16' (half the
    HBM traffic of the bandwidth-bound stage; numerics-gated). Read at
    trace time like every formulation knob."""
    val = os.environ.get("TMR_GLOBAL_SCORES_DTYPE", "f32")
    if val not in ("f32", "bf16"):
        raise ValueError(
            f"TMR_GLOBAL_SCORES_DTYPE={val!r}: expected f32|bf16"
        )
    return val


def _q_block_rows(h: int, w: int, target_tokens: int = 512) -> int:
    """Largest divisor of ``h`` whose row-band holds <= target_tokens."""
    best = 1
    for rows in range(1, h + 1):
        if h % rows == 0 and rows * w <= target_tokens:
            best = rows
    return best


def blockwise_decomposed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    grid_hw: Tuple[int, int],
    scale: float,
    scores_dtype: Optional[str] = None,
) -> jnp.ndarray:
    """Attention with decomposed rel-pos bias, scanned over query row-bands.

    q/k/v: (B, H, S, D) with S = h*w tokens on a (h, w) grid; rh: (h, h, D),
    rw: (w, w, D) get_rel_pos tables (None to skip the bias). Semantics match
    the reference's dense path (sam_ViT.py:224-240, 325-361): f32 softmax
    over the full key axis, bias[q=(y,x), k=(ky,kx)] = q.rh[y,ky] + q.rw[x,kx].

    The S x S scores (3.2 GB f32 at ViT's 4096-token grid, batch 4) and the
    (B, H, h, w, h, w) bias are never materialized: each scan step computes
    one (rows*w, S) f32 tile, softmaxes it (full key axis present, so the
    numerics equal dense attention exactly — no online-softmax rescaling),
    applies it to V, and emits its output band. HBM high-water drops from
    O(S^2) to O(S * rows * w).
    """
    B, H, S, D = q.shape
    gh, gw = grid_hw
    rows = _q_block_rows(gh, gw)
    nb = gh // rows
    work = q.dtype
    # scores_dtype="bf16" (EXPLICIT parameter — this parity oracle never
    # reads the env knob itself, so the default blockwise path and the
    # pallas custom_vjp's backward oracle stay exact): materialize each
    # band's score tile in bf16 instead of f32, halving the dominant HBM
    # traffic of this bandwidth-bound stage. Only the gated folded
    # formulations pass it (bias already inside q/k — the einsum output IS
    # the final logits). The MXU still accumulates in f32
    # (preferred_element_type only rounds the OUTPUT) and softmax upcasts
    # to f32 — a fused convert on the read path. Rounds logits to bf16
    # (~0.4% rel), gated by flash_attn.blockfolded_ok/densefolded_ok,
    # which key on the dtype.
    score_pet = jnp.float32
    if rh is None and work == jnp.bfloat16 and scores_dtype == "bf16":
        score_pet = jnp.bfloat16

    q_g = q.reshape(B, H, nb, rows, gw, D)
    q_blocks = jnp.moveaxis(q_g, 2, 0)  # (nb, B, H, rows, gw, D)
    if rh is not None:
        rh_blocks = rh.reshape(nb, rows, gh, D)
    else:
        rh_blocks = jnp.zeros((nb, 0), q.dtype)  # unused placeholder

    def one_band(args):
        qb, rhb = args  # (B, H, rows, gw, D), (rows, gh, D)
        s = jnp.einsum(
            "bhrwd,bhkd->bhrwk", qb, k,
            preferred_element_type=score_pet,
        ) * scale  # (B, H, rows, gw, S); python scale is weakly typed —
        # the tile keeps score_pet (and the folded calls pass scale=1.0)
        if rh is not None:
            qf = qb.astype(jnp.float32)
            rel_h = jnp.einsum(
                "bhrwd,rkd->bhrwk", qf, rhb.astype(jnp.float32)
            )  # (B, H, rows, gw, gh)
            rel_w = jnp.einsum(
                "bhrwd,wkd->bhrwk", qf, rw.astype(jnp.float32)
            )  # (B, H, rows, gw, gw)
            s = s.reshape(B, H, rows, gw, gh, gw)
            s = s + rel_h[..., :, None] + rel_w[..., None, :]
            s = s.reshape(B, H, rows, gw, S)
        # softmax always in f32: under bf16 score tiles the upcast is a
        # convert fused into the softmax's read of the tile
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        ob = jnp.einsum(
            "bhrwk,bhkd->bhrwd", p.astype(work), v,
            preferred_element_type=jnp.float32,
        )
        return ob.astype(work)

    # Band schedule: lax.map == scan(unroll=1). TMR_GLOBAL_BANDS_UNROLL
    # (trace-time, default 1 = the parity schedule) unrolls N bands per
    # loop step so XLA can software-pipeline the next band's K/V and
    # score-tile HBM traffic behind the current band's compute — same ops
    # per band, same numerics, different schedule. Autotune measures it
    # via the profile's sub-knob rows, like the Pallas tile sizes.
    raw_unroll = os.environ.get("TMR_GLOBAL_BANDS_UNROLL", "1")
    if (
        not (raw_unroll.isascii() and raw_unroll.isdigit())
        or int(raw_unroll) == 0
    ):
        # "0" is rejected, not clamped: the documented contract is a
        # positive integer, and silently running unroll=1 under a zero pin
        # would mislabel any A/B evidence recorded against it
        raise ValueError(
            f"TMR_GLOBAL_BANDS_UNROLL={raw_unroll!r}: expected a positive "
            "integer unroll factor"
        )
    unroll = int(raw_unroll)
    out = jax.lax.scan(
        lambda c, x: (c, one_band(x)), (), (q_blocks, rh_blocks),
        unroll=min(unroll, nb),
    )[1]  # (nb, B, H, rows, gw, Dv)
    # output width comes from v: under the folded-QK variant q/k are
    # augmented past v's head dim
    return jnp.moveaxis(out, 0, 2).reshape(B, H, S, v.shape[-1])


def blockfolded_decomposed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    grid_hw: Tuple[int, int],
    scale: float,
) -> jnp.ndarray:
    """The blockwise band scan with the bias folded into the QK contraction.

    Same banded schedule as :func:`blockwise_decomposed_attention`, but q/k
    are first augmented (ops/flash_attn.fold_rel_pos_into_qk: q' carries
    [q*scale | q.RH | q.RW], k' carries [k | row one-hots | col one-hots]) so
    each band's (rows*gw, S) f32 score tile arrives from ONE einsum with the
    bias already inside. The two bias einsums and — the expensive part — the
    two f32 broadcast-add passes over the score tile disappear; per-band HBM
    traffic drops by roughly a third at ~2x the (tiny relative to bandwidth)
    QK FLOPs. Algebraically exact in f32; under bf16 inputs the bias terms
    round to bf16 before the f32-accumulated matmul, where the blockwise
    path keeps them f32 — so this is an autotune-selected variant
    (TMR_GLOBAL_ATTN=blockfolded), never the parity default.
    """
    if rh is None:
        return blockwise_decomposed_attention(q, k, v, None, None, grid_hw, scale)
    from tmr_tpu.ops.flash_attn import fold_rel_pos_into_qk

    q_aug, k_aug = fold_rel_pos_into_qk(q, k, rh, rw, grid_hw, scale)
    # v keeps the original head dim: the band einsum takes its output width
    # from v, so the augmented contraction never widens the result.
    # scores_dtype is resolved HERE (the gated formulation), not inside the
    # blockwise oracle — the env knob must never touch the parity path.
    return blockwise_decomposed_attention(
        q_aug, k_aug, v, None, None, grid_hw, 1.0,
        scores_dtype=_scores_dtype(),
    )


def densefolded_decomposed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    rh: Optional[jnp.ndarray],
    rw: Optional[jnp.ndarray],
    grid_hw: Tuple[int, int],
    scale: float,
) -> jnp.ndarray:
    """Folded-QK attention with NO band scan: one (B, H, S, S) einsum,
    f32 softmax, one AV einsum, and XLA free to pick its own tiling.

    The band scan exists to bound HBM high-water, but it also serializes
    the schedule and hides the whole attention from XLA's fusion/tiling
    autotuner. At the 4096-token global blocks the full f32 score tensor
    is 3.2 GB per batch-4, 12-head block (4*12*4096^2*4 B) — it fits a
    v5e's 16 GB for inference-shaped programs but is NOT free; selection
    is by measurement only (TMR_GLOBAL_ATTN=densefolded, autotune-swept
    like every formulation), and an OOM during the sweep's compile simply
    loses the A/B to the banded variants.
    Same math as blockfolded (identical fold; softmax over the full key
    axis), so the same bf16 numerics gate applies.
    """
    if rh is None:
        q_aug, k_aug = q * scale, k
    else:
        from tmr_tpu.ops.flash_attn import fold_rel_pos_into_qk

        q_aug, k_aug = fold_rel_pos_into_qk(q, k, rh, rw, grid_hw, scale)
    score_pet = (
        jnp.bfloat16
        if q.dtype == jnp.bfloat16 and _scores_dtype() == "bf16"
        else jnp.float32
    )
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q_aug, k_aug,
        preferred_element_type=score_pet,
    )
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


class Attention(nn.Module):
    """Multi-head attention with decomposed rel-pos (sam_ViT.py:185-240).

    ``rel_pos_size`` fixes the rel-pos *parameter* shapes at the pretrain
    grid (window size for windowed blocks, native image grid for global
    blocks); get_rel_pos interpolates the tables whenever the runtime grid
    differs (the 1536 bucket).

    ``seq_mesh`` (global-attention blocks only) turns the quadratic
    attention core into a ring-attention shard_map island over the mesh's
    'seq' axis: q/k/v reshard to contiguous token-row bands, K/V rotate via
    ppermute over ICI, and no device ever materializes more than an
    (S/n x S/n) score block. This is the long-context path — the reference
    has nothing like it (SURVEY §5.7); it makes the 1536/9216-token (and
    larger) buckets scale past one chip's HBM.
    """

    num_heads: int
    use_rel_pos: bool = True
    rel_pos_size: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.float32
    seq_mesh: Optional[object] = None  # jax.sharding.Mesh with a 'seq' axis
    seq_axis: str = "seq"
    batch_axis: Optional[str] = "data"
    # set by Block for its windowed blocks: their traces are counted by
    # formulation (obs counter ``vit.win_attn.<formulation>``)
    windowed: bool = False

    def _window_formulation(self, h: int, w: int, head_dim: int) -> str:
        """The formulation a block under 1024 tokens traces with
        (ops/pallas_attn.window_formulation), counted where the block is
        one of the windowed ones."""
        from tmr_tpu.ops.pallas_attn import window_formulation

        got = window_formulation(
            (h, w), self.num_heads, head_dim, self.dtype, self.use_rel_pos)
        if self.windowed:
            from tmr_tpu.obs import metrics

            metrics.counter(f"vit.win_attn.{got}").inc()
        return got

    def _global_formulation(self, h: int, w: int, head_dim: int):
        """(name, attention function) a block of 1024 tokens or more
        traces with, counted under ``vit.global_attn.<name>``; the function
        takes head-major operands and is None for ``packed``, which takes
        ``qkv`` itself."""
        # global-attention blocks (4096+ tokens): never materialize the
        # S x S scores or the (B, H, h, w, h, w) bias. TMR_GLOBAL_ATTN
        # (trace-time A/B knob, measured by the autotune sweep) picks
        # the formulation:
        #   blockwise    exact XLA band scan (the f32-parity default)
        #   blockfolded  band scan, bias folded into the QK contraction
        #                (exact in f32; bf16 is numerics-self-checked
        #                with blockwise fallback)
        #   densefolded  folded QK with NO band scan — one dense
        #                einsum/softmax/einsum, XLA picks the tiling
        #                (same fold, same bf16 gate as blockfolded)
        #   flash        stock Pallas flash over the 256-padded folded
        #                QK (bf16 only; self-check gate -> blockwise)
        #   pallas       custom decomposed-bias kernel, VMEM-resident
        #                tiles at native head dim (ops/pallas_attn.py;
        #                self-check gate -> blockwise)
        #   fused        the rewritten fused-bias kernel: row+lane-
        #                aligned v5e tiles, bias rebuilt per tile from
        #                the (q, k) block offsets by broadcast alone —
        #                no selector matmuls (ops/pallas_attn.py;
        #                self-check gate -> blockwise)
        #   xlaflash     pure-XLA online-softmax flash with the same
        #                fused-bias tiling (ops/flash_attn.py) — the
        #                Mosaic-independent form; largest live score
        #                tile is (band, block_k), not (band, S)
        #   packed       the kernel on ``qkv`` where the product wrote
        #                it: no per-head operand between the two products
        #                (ops/pallas_attn.py; bf16 with rel-pos tables;
        #                self-check gate -> blockwise)
        #   auto         what ops/pallas_attn.global_formulation observes:
        #                packed, else flash, else blockwise
        impl = os.environ.get("TMR_GLOBAL_ATTN", "auto")
        if impl not in (
            "auto", "blockwise", "flash", "blockfolded", "densefolded",
            "pallas", "fused", "xlaflash", "packed",
        ):
            raise ValueError(
                f"TMR_GLOBAL_ATTN={impl!r}: expected "
                "auto|blockwise|flash|blockfolded|densefolded|pallas|"
                "fused|xlaflash|packed"
            )
        attn_fn = blockwise_decomposed_attention
        if impl in ("blockfolded", "densefolded"):
            # exact in f32; under bf16 the folded bias rounds to bf16,
            # so the selection is self-check-gated like every other
            # formulation (PARITY.md contract). The gate is pure XLA
            # (runs on any backend, Pallas kill-switch exempt).
            attn_fn = (
                blockfolded_decomposed_attention
                if impl == "blockfolded"
                else densefolded_decomposed_attention
            )
            if self.dtype == jnp.bfloat16:
                from tmr_tpu.ops.flash_attn import (
                    blockfolded_ok,
                    densefolded_ok,
                )

                ok = (
                    blockfolded_ok
                    if impl == "blockfolded"
                    else densefolded_ok
                )
                if not ok(h, w, head_dim, _scores_dtype()):
                    import warnings

                    warnings.warn(FormulationFallbackWarning(
                        "TMR_GLOBAL_ATTN",
                        f"TMR_GLOBAL_ATTN={impl}: bf16 numerics "
                        f"self-check failed at grid ({h}, {w}, "
                        f"head_dim {head_dim}); running blockwise "
                        "fallback"
                    ))
                    attn_fn = blockwise_decomposed_attention
        elif impl == "pallas":
            # the custom decomposed-bias kernel (ops/pallas_attn.py):
            # VMEM-resident online-softmax tiles, native head-dim
            # contraction; self-checked per geometry with fallback
            from tmr_tpu.ops.pallas_attn import (
                effective_global_tiles,
                pallas_decomposed_attention,
                pallas_global_ok,
                pallas_supported,
            )

            bq, bk = effective_global_tiles(h * w)
            if pallas_supported(h * w) and pallas_global_ok(
                h, w, head_dim, bq, bk
            ):
                attn_fn = pallas_decomposed_attention
            else:
                # explicit request refused by the gate: an A/B number
                # measured now would silently be blockwise — say so
                # once, at trace time
                import warnings

                warnings.warn(FormulationFallbackWarning(
                    "TMR_GLOBAL_ATTN",
                    "TMR_GLOBAL_ATTN=pallas: self-check gate refused "
                    f"grid ({h}, {w}, head_dim {head_dim}); running "
                    "blockwise fallback"
                ))
        elif impl == "fused":
            # the fused-bias kernel: row+lane-aligned tiles, bias
            # rebuilt per tile from the (q, k) block offsets —
            # self-checked per (geometry, tile config) with fallback
            from tmr_tpu.ops.pallas_attn import (
                effective_fused_tiles,
                fused_supported,
                pallas_fused_attention,
                pallas_fused_ok,
            )

            bq, bk = effective_fused_tiles(h * w, w)
            if fused_supported(h * w, w) and pallas_fused_ok(
                h, w, head_dim, bq, bk
            ):
                attn_fn = pallas_fused_attention
            else:
                import warnings

                warnings.warn(FormulationFallbackWarning(
                    "TMR_GLOBAL_ATTN",
                    "TMR_GLOBAL_ATTN=fused: self-check gate refused "
                    f"grid ({h}, {w}, head_dim {head_dim}); running "
                    "blockwise fallback"
                ))
        elif impl == "xlaflash":
            # pure-XLA online-softmax flash, fused bias tiles: exact
            # in f32 up to reassociation (ungated there, like the
            # folded formulations); bf16 is numerics-self-checked
            # with blockwise fallback
            from tmr_tpu.ops.flash_attn import (
                xla_flash_decomposed_attention,
                xlaflash_ok,
            )

            attn_fn = xla_flash_decomposed_attention
            if self.dtype == jnp.bfloat16 and not xlaflash_ok(
                h, w, head_dim
            ):
                import warnings

                warnings.warn(FormulationFallbackWarning(
                    "TMR_GLOBAL_ATTN",
                    "TMR_GLOBAL_ATTN=xlaflash: bf16 numerics "
                    f"self-check failed at grid ({h}, {w}, head_dim "
                    f"{head_dim}); running blockwise fallback"
                ))
                attn_fn = blockwise_decomposed_attention
        elif impl == "packed":
            # the kernel on ``qkv`` where the product wrote it
            # (ops/pallas_attn.packed_global_attention): bf16 with rel-pos
            # tables on a TPU, self-check gate -> blockwise
            from tmr_tpu.ops.pallas_attn import (
                packed_global_ok,
                packed_global_supported,
            )

            if (self.use_rel_pos and self.dtype == jnp.bfloat16
                    and packed_global_supported(
                        (h, w), self.num_heads, head_dim)
                    and packed_global_ok(h, w, head_dim, self.num_heads)):
                attn_fn = None
            else:
                import warnings

                warnings.warn(FormulationFallbackWarning(
                    "TMR_GLOBAL_ATTN",
                    "TMR_GLOBAL_ATTN=packed: no packed layout or gate "
                    f"refused grid ({h}, {w}, head_dim {head_dim}, dtype "
                    f"{jnp.dtype(self.dtype).name}); running blockwise "
                    "fallback"
                ))
        elif impl == "auto":
            from tmr_tpu.ops.pallas_attn import global_formulation

            impl = global_formulation(
                (h, w), self.num_heads, head_dim, self.dtype,
                self.use_rel_pos)
            if impl == "packed":
                attn_fn = None
            elif impl == "flash":
                from tmr_tpu.ops.flash_attn import flash_decomposed_attention

                attn_fn = flash_decomposed_attention
        elif impl == "flash" and self.dtype == jnp.bfloat16:
            from tmr_tpu.ops.flash_attn import (
                flash_attention_ok,
                flash_decomposed_attention,
                flash_supported,
            )

            if flash_supported(h * w) and flash_attention_ok(
                h, w, head_dim
            ):
                attn_fn = flash_decomposed_attention
            else:
                import warnings

                warnings.warn(FormulationFallbackWarning(
                    "TMR_GLOBAL_ATTN",
                    "TMR_GLOBAL_ATTN=flash: gate refused grid "
                    f"({h}, {w}, head_dim {head_dim}); running "
                    "blockwise fallback"
                ))
        elif impl == "flash":
            # explicit flash on a non-bf16 model: the kernel is
            # bf16-only, so the request silently lands on blockwise —
            # say so or an A/B records blockwise timings labeled flash
            import warnings

            warnings.warn(FormulationFallbackWarning(
                "TMR_GLOBAL_ATTN",
                f"TMR_GLOBAL_ATTN=flash needs bf16 (model dtype "
                f"{self.dtype}); running blockwise fallback"
            ))
        if attn_fn is blockwise_decomposed_attention:
            impl = "blockwise"
        from tmr_tpu.obs import metrics

        metrics.counter(f"vit.global_attn.{impl}").inc()
        return impl, attn_fn

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, w, dim = x.shape
        head_dim = dim // self.num_heads
        scale = head_dim**-0.5

        rh = rw = None
        if self.use_rel_pos:
            rel_pos_h = self.param(
                "rel_pos_h",
                nn.initializers.zeros,
                (2 * self.rel_pos_size[0] - 1, head_dim),
            )
            rel_pos_w = self.param(
                "rel_pos_w",
                nn.initializers.zeros,
                (2 * self.rel_pos_size[1] - 1, head_dim),
            )
            rh = get_rel_pos(h, h, rel_pos_h)  # (h, h, hd) f32
            rw = get_rel_pos(w, w, rel_pos_w)  # (w, w, hd) f32

        win = glob = attn_fn = None
        if self.seq_mesh is None and h * w < 1024:
            win = self._window_formulation(h, w, head_dim)
        elif self.seq_mesh is None:
            glob, attn_fn = self._global_formulation(h, w, head_dim)
        if win == "packed":
            # the windowed blocks' TPU bf16 path: the kernel takes qkv as
            # the product wrote it and writes what proj reads — no
            # per-head operand, concatenate or transpose between the two
            # products, which run on rows of tokens (a window's row padded
            # 14 -> 16: ops/pallas_attn._padded_width has the why)
            from tmr_tpu.ops.pallas_attn import (
                drop_window_pad,
                packed_windowed_attention,
                pad_window_rows,
            )

            qkv = nn.Dense(dim * 3, dtype=self.dtype, name="qkv")(
                pad_window_rows(x))
            x = packed_windowed_attention(
                qkv, rh, rw, (h, w), self.num_heads, scale)
            x = nn.Dense(dim, dtype=self.dtype, name="proj")(x)
            return drop_window_pad(x, (h, w))

        if glob == "packed":
            # the global blocks' TPU bf16 path, the same way: 4,096 tokens
            # an image are whole tiles, so the rows need no pad either
            from tmr_tpu.ops.pallas_attn import packed_global_attention

            qkv = nn.Dense(dim * 3, dtype=self.dtype, name="qkv")(
                x.reshape(b * h * w, dim))
            x = packed_global_attention(
                qkv, rh, rw, (h, w), self.num_heads, scale)
            x = nn.Dense(dim, dtype=self.dtype, name="proj")(x)
            return x.reshape(b, h, w, dim)

        qkv = nn.Dense(dim * 3, dtype=self.dtype, name="qkv")(x)
        qkv = qkv.reshape(b, h * w, 3, self.num_heads, head_dim)
        q, k, v = jnp.moveaxis(qkv, 2, 0)  # each (b, hw, heads, hd)
        q = q.transpose(0, 2, 1, 3)  # (b, heads, hw, hd)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)

        if self.seq_mesh is not None:
            x = self._ring_attn(q, k, v, rh, rw, (b, h, w, dim), head_dim)
        elif h * w >= 1024:
            # global-attention blocks (4096+ tokens): the formulation
            # ``_global_formulation`` chose, on head-major operands
            x = attn_fn(
                q, k, v,
                rh if self.use_rel_pos else None,
                rw if self.use_rel_pos else None,
                (h, w), scale,
            )
            x = x.transpose(0, 2, 1, 3).reshape(b, h, w, dim)
        else:
            # everything else under 1024 tokens: the dense einsums, the
            # only windowed path in float32, off a TPU and inside a
            # partitioned trace, and the tests' oracle
            attn = jnp.einsum(
                "bnqc,bnkc->bnqk", q, k, preferred_element_type=jnp.float32
            ) * scale
            if self.use_rel_pos:
                r_q = q.astype(jnp.float32).reshape(
                    b, self.num_heads, h, w, head_dim
                )
                rel_h = jnp.einsum(
                    "bnhwc,hkc->bnhwk", r_q, rh.astype(jnp.float32)
                )
                rel_w = jnp.einsum(
                    "bnhwc,wkc->bnhwk", r_q, rw.astype(jnp.float32)
                )
                attn = attn.reshape(b, self.num_heads, h, w, h, w)
                attn = attn + rel_h[..., :, None] + rel_w[..., None, :]
                attn = attn.reshape(b, self.num_heads, h * w, h * w)
            attn = jax.nn.softmax(attn, axis=-1).astype(self.dtype)
            x = jnp.einsum(
                "bnqk,bnkc->bnqc", attn, v,
                preferred_element_type=jnp.float32,
            ).astype(self.dtype)
            x = x.transpose(0, 2, 1, 3).reshape(b, h, w, dim)
        return nn.Dense(dim, dtype=self.dtype, name="proj")(x)

    def _ring_attn(self, q, k, v, rh, rw, bhwd, head_dim):
        """Sequence-parallel attention core (ring over token-row bands)."""
        from tmr_tpu.parallel.ring import make_ring_attention_fn

        b, h, w, dim = bhwd
        mesh = self.seq_mesh
        axis_names = getattr(mesh, "axis_names", ())
        n = mesh.shape[self.seq_axis]
        if h % n:
            raise ValueError(
                f"token rows {h} not divisible by seq axis size {n}"
            )
        # shard batch over 'data' when it divides; heads over 'model' so the
        # island composes with TP instead of re-gathering head shards
        batch_axis = self.batch_axis if self.batch_axis in axis_names else None
        if batch_axis and b % mesh.shape[batch_axis]:
            batch_axis = None  # e.g. eval batch 1 on a dp>1 mesh
        head_axis = "model" if "model" in axis_names else None
        if head_axis and self.num_heads % mesh.shape[head_axis]:
            head_axis = None

        fn = make_ring_attention_fn(
            mesh, self.seq_axis, batch_axis=batch_axis, head_axis=head_axis,
            decomposed=self.use_rel_pos, grid_w=w, scale=head_dim**-0.5,
        )
        out = fn(q, k, v, rh, rw) if self.use_rel_pos else fn(q, k, v)
        return out.transpose(0, 2, 1, 3).reshape(b, h, w, dim)


class Block(nn.Module):
    """Transformer block with optional window attention (sam_ViT.py:119-182)."""

    num_heads: int
    mlp_ratio: float = 4.0
    window_size: int = 0
    rel_pos_size: Optional[Tuple[int, int]] = None  # native grid for global attn
    dtype: jnp.dtype = jnp.float32
    seq_mesh: Optional[object] = None  # sequence parallelism (global attn only)
    batch_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dim = x.shape[-1]
        shortcut = x
        x = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name="norm1")(x)
        if self.window_size > 0:
            h, w = x.shape[1], x.shape[2]
            x, pad_hw = window_partition(x, self.window_size)
        attn_size = (
            (self.window_size, self.window_size)
            if self.window_size > 0
            else self.rel_pos_size
        )
        x = Attention(
            num_heads=self.num_heads,
            rel_pos_size=attn_size,
            dtype=self.dtype,
            # windowed attention is local (196-token windows) — sequence
            # parallelism applies to the quadratic global blocks only
            seq_mesh=self.seq_mesh if self.window_size == 0 else None,
            batch_axis=self.batch_axis,
            windowed=self.window_size > 0,
            name="attn",
        )(x)
        if self.window_size > 0:
            x = window_unpartition(x, self.window_size, pad_hw, (h, w))
        x = shortcut + x
        y = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name="norm2")(x)
        y = MLPBlock(mlp_dim=int(dim * self.mlp_ratio), dtype=self.dtype, name="mlp")(y)
        return x + y


def patch_embed_conv(embed_dim: int, patch_size: int, dtype) -> nn.Conv:
    """The patch embedding every backbone built on SAM's stem declares
    (``SamViT``, ``models/lm_trunk.py``): one definition, one leaf name."""
    return nn.Conv(
        embed_dim,
        (patch_size, patch_size),
        strides=(patch_size, patch_size),
        padding="VALID",
        dtype=dtype,
        name="patch_embed",
    )


def neck_modules(out_chans: int, dtype) -> tuple:
    """SAM's neck (sam_ViT.py:88-104) as its four submodules, to be bound
    in a module's ``setup`` and run by :func:`apply_neck`."""
    return (
        nn.Conv(out_chans, (1, 1), use_bias=False, dtype=dtype,
                name="neck_0"),
        LayerNorm2d(name="neck_1"),
        nn.Conv(out_chans, (3, 3), padding=1, use_bias=False, dtype=dtype,
                name="neck_2"),
        LayerNorm2d(name="neck_3"),
    )


def apply_neck(neck: tuple, x: jnp.ndarray, dtype) -> jnp.ndarray:
    """1x1 conv -> LN2d -> 3x3 conv -> LN2d."""
    conv_0, norm_1, conv_2, norm_3 = neck
    x = conv_0(x)
    x = norm_1(x.astype(jnp.float32))
    x = conv_2(x.astype(dtype))
    return norm_3(x.astype(jnp.float32))


class SamViT(nn.Module):
    """SAM image encoder (sam_ViT.py:17-116 + the pos-embed interpolation of
    sam.py:70-95). Input (B, S, S, 3) NHWC -> (B, S/16, S/16, 256)."""

    embed_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    global_attn_indexes: Sequence[int] = (2, 5, 8, 11)
    patch_size: int = 16
    window_size: int = 14
    out_chans: int = 256
    mlp_ratio: float = 4.0
    pretrain_img_size: int = 1024  # pos_embed native grid = 1024/16 = 64
    dtype: jnp.dtype = jnp.float32
    # sequence/context parallelism: a Mesh with a 'seq' axis turns every
    # global-attention block into a ring-attention shard_map island
    seq_mesh: Optional[object] = None
    batch_axis: Optional[str] = "data"
    # rematerialize each transformer block on the backward pass
    # (jax.checkpoint): trades ~1 extra forward of FLOPs for activation
    # memory, the standard lever for bigger batches / longer token grids
    remat: bool = False

    def setup(self):
        # setup-style (not @nn.compact) so ``embed``/``neck`` are callable
        # via apply(method=...) by the pipeline-parallel path
        # (parallel/pipeline.py) — ONE definition of the pre/post stages for
        # both the dense and the pipelined forward. Explicit ``name=`` keeps
        # the param tree identical to the original compact layout (the
        # convert.py / golden-test contract).
        grid = self.pretrain_img_size // self.patch_size
        self._grid = grid
        self._patch = patch_embed_conv(self.embed_dim, self.patch_size,
                                       self.dtype)
        self._pos_embed = self.param(
            "pos_embed", nn.initializers.zeros, (1, grid, grid, self.embed_dim)
        )
        block_cls = nn.remat(Block) if self.remat else Block
        self._blocks = [
            block_cls(
                num_heads=self.num_heads,
                mlp_ratio=self.mlp_ratio,
                window_size=(
                    0 if i in self.global_attn_indexes else self.window_size
                ),
                rel_pos_size=(grid, grid),
                dtype=self.dtype,
                seq_mesh=self.seq_mesh,
                batch_axis=self.batch_axis,
                name=f"blocks_{i}",
            )
            for i in range(self.depth)
        ]
        self._neck = neck_modules(self.out_chans, self.dtype)

    def embed(self, x: jnp.ndarray) -> jnp.ndarray:
        """Patch embed + (interpolated) absolute pos embed -> (B, h, w, D)
        tokens. The pos embed bilinearly re-interpolates for non-native
        grids — the 1536 bucket (sam.py:72-76)."""
        x = self._patch(x)
        h, w = x.shape[1], x.shape[2]
        pos_embed = self._pos_embed
        if (h, w) != (self._grid, self._grid):
            pos_embed = jax.image.resize(
                pos_embed, (1, h, w, self.embed_dim), method="bilinear",
                antialias=False,
            )
        return x + pos_embed.astype(x.dtype)

    def neck(self, x: jnp.ndarray) -> jnp.ndarray:
        """1x1 conv -> LN2d -> 3x3 conv -> LN2d (sam_ViT.py:88-104)."""
        return apply_neck(self._neck, x, self.dtype)

    def __call__(
        self, x: jnp.ndarray, return_interm: bool = False
    ) -> jnp.ndarray:
        """``return_interm=True`` additionally returns the per-block token
        embeddings (B, h, w, embed_dim) — the reference's ``forward_interm``
        (sam.py:97-113), used by SAM-HQ-style consumers."""
        x = self.embed(x)
        interm = []
        for i, blk in enumerate(self._blocks):
            x = blk(x)
            # the reference's forward_interm (sam.py:97-113) collects only the
            # global-attention blocks' embeddings, not every block
            if return_interm and i in self.global_attn_indexes:
                interm.append(x)
        x = self.neck(x)
        if return_interm:
            return x, interm
        return x


# Configurations of sam.py:20-30. `backbone='sam'` in the reference always
# builds vit_h for train/eval (models/backbone/__init__.py:22); vit_b is the
# ONNX/mapper path (export_onnx.py:27).
VIT_CONFIGS = {
    "vit_b": dict(
        embed_dim=768, depth=12, num_heads=12, global_attn_indexes=(2, 5, 8, 11)
    ),
    "vit_h": dict(
        embed_dim=1280, depth=32, num_heads=16, global_attn_indexes=(7, 15, 23, 31)
    ),
}


def build_sam_vit(
    model_type: str = "vit_h", dtype=jnp.float32, seq_mesh=None,
    remat: bool = False,
) -> SamViT:
    return SamViT(dtype=dtype, seq_mesh=seq_mesh, remat=remat,
                  **VIT_CONFIGS[model_type])

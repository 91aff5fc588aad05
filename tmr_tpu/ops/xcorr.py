"""Cross-correlation template matching — the north-star kernel.

Reference semantics (models/template_matching.py):
- ``extract_template`` (:55-76): RoIAlign the exemplar region of the feature
  map into an odd-sized (Ht, Wt) template.
- ``extract_prototype`` (:43-53): adaptive-avg-pool the integer exemplar crop
  to a (1, 1) prototype.
- ``cross_correlation`` (:23-41): depthwise VALID conv of the feature map with
  the template as kernel, / (Ht*Wt + 1e-14), optional channel-sum squeeze,
  then zero-pad the output back to (H, W).

TPU-first design: templates have *dynamic* odd sizes per image, which is
jit-hostile. We give the template a static odd capacity T (bucketed by the
caller), place the true (ht, wt) template centered inside the (T, T) kernel
(zero elsewhere — zeros contribute nothing to the correlation), and run ONE
``lax.conv_general_dilated`` with ``feature_group_count = B*C`` (depthwise,
per-image kernels) at SAME padding. Interior pixels then equal the reference's
VALID conv exactly; the (ht//2, wt//2) border band — zero in the reference by
construction — is zeroed with an iota mask. Template extraction itself is two
MXU matmuls (see ops/roi_align.py sampling matrices), so the whole matcher
fuses into the surrounding jitted model with no host sync.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from tmr_tpu.ops.roi_align import sampling_matrix


def template_geometry(exemplar: jnp.ndarray, feat_h: int, feat_w: int):
    """Exemplar box -> template geometry, mirroring template_matching.py:55-73.

    exemplar: (4,) normalized [x1, y1, x2, y2]. Returns a dict of traced
    scalars: clipped feature-space coords x1,y1,x2,y2 (float) and odd template
    size ht, wt (int32, >= 1).
    """
    x1 = jnp.clip(exemplar[0], 0.0, 1.0) * feat_w
    y1 = jnp.clip(exemplar[1], 0.0, 1.0) * feat_h
    x2 = jnp.clip(exemplar[2], 0.0, 1.0) * feat_w
    y2 = jnp.clip(exemplar[3], 0.0, 1.0) * feat_h

    wt = jnp.ceil(x2).astype(jnp.int32) - jnp.floor(x1).astype(jnp.int32)
    ht = jnp.ceil(y2).astype(jnp.int32) - jnp.floor(y1).astype(jnp.int32)
    wt = wt - (wt % 2 == 0)  # odd-ify (template_matching.py:72-73)
    ht = ht - (ht % 2 == 0)
    wt = jnp.maximum(wt, 1)
    ht = jnp.maximum(ht, 1)
    return {"x1": x1, "y1": y1, "x2": x2, "y2": y2, "ht": ht, "wt": wt}


def extract_template(
    feature: jnp.ndarray, exemplar: jnp.ndarray, capacity: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """RoIAlign the exemplar into a centered (C, T, T) padded template.

    feature: (C, H, W) single image. Returns (template (C, T, T), thw (2,)
    int32 actual (ht, wt)). Equivalent to roi_align(..., (ht, wt),
    aligned=True, sampling_ratio=-1) placed centered in the T x T kernel.

    When the odd-ified exemplar span exceeds ``capacity`` (the caller picked
    too small a bucket), ht/wt are clamped to ``capacity``: the template is
    then a coarser ``capacity``-bin RoIAlign of the full exemplar — a
    well-defined approximation rather than a silent misaligned truncation.
    The adaptive sampling ratio is exact (<= 2 per axis) whenever the bucket
    fits, since the output size is the odd-ified ceil-span of the ROI.
    """
    C, H, W = feature.shape
    g = template_geometry(exemplar, H, W)
    ht = jnp.minimum(g["ht"], capacity)
    wt = jnp.minimum(g["wt"], capacity)
    ay = sampling_matrix(
        g["y1"] - 0.5, g["y2"] - g["y1"], ht, capacity, H,
        offset=(capacity - ht) // 2, sampling_ratio=-1, max_ratio=2,
    )
    ax = sampling_matrix(
        g["x1"] - 0.5, g["x2"] - g["x1"], wt, capacity, W,
        offset=(capacity - wt) // 2, sampling_ratio=-1, max_ratio=2,
    )
    template = jnp.einsum(
        "yh,chw,xw->cyx", ay, feature, ax, precision=jax.lax.Precision.HIGHEST
    )
    return template, jnp.stack([ht, wt])


def extract_prototype(
    feature: jnp.ndarray, exemplar: jnp.ndarray, capacity: int = 1
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Adaptive-avg-pool prototype (template_matching.py:43-53).

    Means the feature over the integer crop [floor(x1*W):ceil(x2*W)] x
    [floor(y1*H):ceil(y2*H)], returned centered in a (C, T, T) kernel with
    actual size (1, 1).
    """
    C, H, W = feature.shape
    g = template_geometry(exemplar, H, W)
    xs = jnp.arange(W)
    ys = jnp.arange(H)
    mx = (xs >= jnp.floor(g["x1"]).astype(jnp.int32)) & (
        xs < jnp.ceil(g["x2"]).astype(jnp.int32)
    )
    my = (ys >= jnp.floor(g["y1"]).astype(jnp.int32)) & (
        ys < jnp.ceil(g["y2"]).astype(jnp.int32)
    )
    mask = (my[:, None] & mx[None, :]).astype(feature.dtype)
    denom = jnp.maximum(mask.sum(), 1.0)
    proto = (feature * mask).sum(axis=(1, 2)) / denom  # (C,)
    template = jnp.zeros((C, capacity, capacity), feature.dtype)
    template = template.at[:, capacity // 2, capacity // 2].set(proto)
    ones = jnp.ones((), jnp.int32)
    return template, jnp.stack([ones, ones])


def small_impl_default() -> str:
    """Backend-dependent default for the SMALL-bucket correlation impl when
    TMR_XCORR_IMPL_SMALL is unset: "vmap" on TPU — measured, not assumed
    (the on-device autotune sweep picked vmap at the production matcher
    shapes on TPU v5 lite; BENCH_LIVE.json, 2026-07-31, the VERDICT r3
    "measured winners become the defaults" mandate) — "conv" elsewhere.
    Identical semantics either way (tests/test_ops.py variant agreement).
    Single source of truth: utils/autotune.py's active-impl resolution for
    the precision cache mirrors dispatch THROUGH this function."""
    return "vmap" if jax.default_backend() == "tpu" else "conv"


#: capacities above this run the FFT correlation path: a depthwise SAME conv
#: at T in the 100s costs O(H^2 T^2 C) on the MXU (petaFLOPs at T=191), while
#: the FFT correlation is O(H'^2 log H' C) regardless of template size.
FFT_CAPACITY_THRESHOLD = 65


def _fft_size(n: int) -> int:
    """Smallest 2^a * 3^b >= n (sizes XLA's TPU FFT handles efficiently)."""
    best = 1 << (n - 1).bit_length()
    for b in (1, 3, 9):
        m = b
        while m < n:
            m *= 2
        if n <= m < best:
            best = m
    return best


def _xcorr_fft(feature: jnp.ndarray, template: jnp.ndarray) -> jnp.ndarray:
    """Exact linear cross-correlation via the correlation theorem.

    feature: (B, C, H, W); template: (B, C, T, T), T odd. Returns the same
    (B, C, H, W) map the SAME-padded depthwise conv produces: out[y, x] =
    sum_{i,j} feature[y - T//2 + i, x - T//2 + j] * template[i, j] with
    zero padding. Zero-padding both signals to L >= H + T - 1 makes the
    circular correlation equal the linear one; the template's zero capacity
    ring contributes nothing, so this is bit-compatible (up to f32 FFT
    rounding ~1e-5 relative) with the direct path for any template size.
    """
    B, C, H, W = feature.shape
    T = template.shape[-1]
    c = T // 2
    L = _fft_size(max(H, W) + T - 1)
    ff = jnp.fft.rfft2(feature.astype(jnp.float32), s=(L, L))
    ft = jnp.fft.rfft2(template.astype(jnp.float32), s=(L, L))
    corr = jnp.fft.irfft2(ff * jnp.conj(ft), s=(L, L))
    ys = (jnp.arange(H) - c) % L
    xs = (jnp.arange(W) - c) % L
    return corr[:, :, ys][:, :, :, xs]


def _xcorr_int8dot(feature: jnp.ndarray,
                   template: jnp.ndarray) -> jnp.ndarray:
    """Both-operand-int8 depthwise correlation (TMR_QUANT_KERNEL int8dot
    arm): feature dynamically quantized per (image, channel), template on
    the same int8 grid the fake-quant arm uses (ops/quant), ONE grouped
    integer conv with ``preferred_element_type=int32``, and the
    per-(image, channel) dequant fused into the f32 epilogue. The
    depthwise correlation has no channel contraction to feed the MXU, so
    unlike the decoder matmuls there is no Mosaic arm here — the win is
    halved operand traffic through the integer conv; admitted by
    quant_xcorr_ok(kernel="int8dot")'s tolerance tier.

    feature: (B, C, H, W) f32/bf16; template: (B, C, T, T). Returns the
    SAME-padded (B, C, H, W) f32 map the other arms produce.
    """
    from tmr_tpu.ops.quant import quantize_int8, quantize_int8_template

    B, C, H, W = feature.shape
    T = template.shape[-1]
    ff = feature.astype(jnp.float32)
    fq, fs = quantize_int8(ff.reshape(B, C, H * W), axis=-1)
    fq = fq.reshape(B, C, H, W)
    fs = fs.reshape(B, C, 1, 1)
    tq, ts = quantize_int8_template(template)
    acc = lax.conv_general_dilated(
        fq.reshape(1, B * C, H, W),
        tq.reshape(B * C, 1, T, T),
        window_strides=(1, 1),
        padding=[(T // 2, T // 2), (T // 2, T // 2)],
        feature_group_count=B * C,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.int32,
    ).reshape(B, C, H, W)
    return acc.astype(jnp.float32) * (fs * ts)


def _data_shard_map(fn, mesh):
    """Wrap the correlation compute in a per-device island over 'data'.

    The matcher is embarrassingly data-parallel — per-IMAGE kernels — but its
    group-merge reshape (B, C, T, T) -> (B*C, 1, T, T) (and the reversed-
    kernel transpose conv in the backward pass) folds the batch dim into
    channels, a transition XLA's spmd partitioner cannot shard efficiently:
    MULTICHIP_r03 carried two "[SPMD] Involuntary full rematerialization"
    warnings on exactly these ops. shard_map over 'data' makes each device
    run the conv on its local images with local shapes — the partitioner
    never sees the merge, and the model/seq axes simply replicate the tiny
    per-image kernels. Requires tracing under ``jax.sharding.set_mesh`` (the
    Trainer and dryrun do; a bare ``with mesh:`` is invisible here) and
    'data' dividing the batch; otherwise the caller falls back to the global
    formulation.
    """
    from tmr_tpu.parallel.compat import shard_map

    P = jax.sharding.PartitionSpec
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )


def cross_correlation(
    feature: jnp.ndarray,
    template: jnp.ndarray,
    template_hw: jnp.ndarray,
    squeeze: bool = False,
) -> jnp.ndarray:
    """Depthwise cross-correlation with per-image kernels.

    feature: (B, C, H, W); template: (B, C, T, T) centered-padded (T odd
    static); template_hw: (B, 2) int32 true (ht, wt). Returns (B, C, H, W),
    or (B, 1, H, W) when squeeze (channel sum, template_matching.py:34-35).
    Matches template_matching.py:23-41: interior = VALID conv / (ht*wt+1e-14),
    border band of (ht//2, wt//2) zeroed.

    Small capacities (T <= FFT_CAPACITY_THRESHOLD) run one depthwise grouped
    conv on the MXU; larger ones switch to the FFT path, whose cost is
    independent of T — this is what makes the 127/191 buckets (exemplars up
    to the full image at 1024/1536) affordable, where a direct SAME conv
    would do O(H^2 T^2) work mostly on positions the reference zeroes.
    """
    B, C, H, W = feature.shape
    T = template.shape[-1]
    # TMR_XCORR_IMPL selects the correlation formulation for A/B profiling on
    # hardware (read at trace time): "conv" = one grouped conv over B*C,
    # "vmap" = per-image depthwise conv vmapped over the batch, "fft" = the
    # correlation-theorem path. Default "auto" = conv below the FFT
    # threshold, fft above. All are exactness-tested against each other
    # (tests/test_ops.py).
    impl = os.environ.get("TMR_XCORR_IMPL", "auto")
    # TMR_XCORR_PRECISION selects the conv/vmap paths' MXU precision (read
    # at trace time, A/B-measurable like the impl knobs): "highest" = the
    # parity default (f32 via multi-pass bf16 emulation on TPU — 3-6 MXU
    # passes per conv); "default" = single-pass; "bf16" = cast the operands
    # to bfloat16 and accumulate in f32 (one MXU pass, f32 result). The
    # reference's torch conv2d is true f32 (template_matching.py:23-41), so
    # "highest" stays the default until hardware measurement justifies the
    # flip; scores feed ranking/thresholding, where bf16 input rounding
    # (~1e-2 rel) is far below the NMS/threshold decision scale. The FFT
    # path is f32 either way.
    prec_name = os.environ.get("TMR_XCORR_PRECISION", "highest")
    if prec_name not in ("highest", "default", "bf16"):
        raise ValueError(
            f"TMR_XCORR_PRECISION={prec_name!r}: expected highest|default|bf16"
        )
    conv_prec = (
        lax.Precision.HIGHEST if prec_name == "highest"
        else lax.Precision.DEFAULT
    )
    # TMR_XCORR_IMPL_SMALL: the autotuner's measured winner for SMALL
    # buckets only (utils/autotune.py) — scoped below the threshold so a
    # capacity-17 winner can never drag the 127/191 buckets off the FFT
    # path (a direct conv there is O(H^2 T^2 C), documented above).
    small = os.environ.get("TMR_XCORR_IMPL_SMALL", small_impl_default())
    for name, val in (
        ("TMR_XCORR_IMPL", impl), ("TMR_XCORR_IMPL_SMALL", small)
    ):
        if val not in ("auto", "conv", "vmap", "fft", "convnhwc", "pallas"):
            raise ValueError(
                f"{name}={val!r}: expected auto|conv|vmap|fft|convnhwc|pallas"
            )
    # remember which knob supplied the resolved impl so a gate refusal
    # below can name it (FormulationFallbackWarning carries the env var —
    # the autotune sweeps annotate mislabeled timings structurally)
    impl_source = "TMR_XCORR_IMPL"
    if impl == "auto":
        if T > FFT_CAPACITY_THRESHOLD:
            impl = "fft"
        else:
            impl = small
            impl_source = "TMR_XCORR_IMPL_SMALL"
    if impl == "auto":  # "auto" as the small-bucket value = backend default
        impl = small_impl_default()

    # TMR_QUANT (ops/quant.py): the matcher arm of the quantized inner
    # loop — dynamic int8 per-(image, channel) template + bf16 feature,
    # f32 accumulation. Admitted per geometry by quant_xcorr_ok's
    # output-tier oracle; refusals warn (FormulationFallbackWarning, so
    # sweeps annotate mislabeled timings) and record a gate_probe/v1
    # cause. Inert on the FFT path (f32 end to end; no MXU operand to
    # shrink) and under TMR_QUANT=off/auto-unelected.
    quant = False
    quant_arm = "dequant"
    if impl != "fft":
        from tmr_tpu.ops.quant import quant_kernel, quant_mode, quant_xcorr_ok

        if quant_mode() == "int8":
            if quant_xcorr_ok(C, H, W, T):
                quant = True
            else:
                import warnings

                from tmr_tpu.diagnostics import FormulationFallbackWarning

                warnings.warn(FormulationFallbackWarning(
                    "TMR_QUANT",
                    f"TMR_QUANT=int8: xcorr oracle refused (C={C}, H={H}, "
                    f"W={W}, T={T}); running the exact correlation"
                ))
        if quant:
            # TMR_QUANT_KERNEL routing for the matcher arm: the depthwise
            # correlation has no channel contraction, so there is no
            # Mosaic int8 MXU kernel here — a "pallas" request demotes to
            # the XLA integer conv (int8dot) with a recorded cause, and
            # int8dot itself is admitted by its own tolerance tier
            # (feature quantization is rounding the dequant arm never
            # pays). Every demotion warns so sweeps annotate timings.
            arm = quant_kernel()
            if arm == "pallas":
                import warnings

                from tmr_tpu.diagnostics import (
                    FormulationFallbackWarning,
                    gate_refused,
                )

                gate_refused(
                    "pallas_int8_ok",
                    "depthwise correlation has no MXU contraction; the "
                    "matcher int8 arm rides the XLA integer conv",
                    "unsupported-shape",
                    config={"C": C, "H": H, "W": W, "T": T},
                )
                warnings.warn(FormulationFallbackWarning(
                    "TMR_QUANT_KERNEL",
                    "TMR_QUANT_KERNEL=pallas: no Mosaic arm for the "
                    "depthwise correlation; riding the XLA int8dot "
                    "integer conv"
                ))
                arm = "int8dot"
            if arm == "int8dot":
                if quant_xcorr_ok(C, H, W, T, kernel="int8dot"):
                    quant_arm = "int8dot"
                else:
                    import warnings

                    from tmr_tpu.diagnostics import (
                        FormulationFallbackWarning,
                    )

                    warnings.warn(FormulationFallbackWarning(
                        "TMR_QUANT_KERNEL",
                        "TMR_QUANT_KERNEL int8dot arm: xcorr tolerance "
                        f"gate refused (C={C}, H={H}, W={W}, T={T}); "
                        "running the dequant arm"
                    ))

    def _compute(f, t):
        # local-shape island: b == B globally, or B/n_data under shard_map
        b = f.shape[0]
        use = impl
        if use == "pallas":
            from tmr_tpu.ops.pallas_xcorr import pallas_xcorr_ok

            if not pallas_xcorr_ok(C, H, W, T):
                # self-check refused or capacity too big: fall back the
                # way the auto dispatch would — a direct SAME conv at T in
                # the 100s is O(H^2 T^2 C) (module docstring), so big
                # buckets go to FFT. Say so at trace time: an A/B row (or
                # cached autotune winner) labeled "pallas" must never
                # silently record conv/FFT timings (the same contract as
                # the attention formulations in vit.py). Resolved BEFORE
                # the quant/bf16 casts below so an FFT fallback runs the
                # exact f32 correlation those knobs are contractually
                # inert on — never int8/bf16 operands through a numerics
                # path no oracle validated.
                import warnings

                from tmr_tpu.diagnostics import FormulationFallbackWarning

                fb = "fft" if T > FFT_CAPACITY_THRESHOLD else "conv"
                warnings.warn(FormulationFallbackWarning(
                    impl_source,
                    f"{impl_source}=pallas: kernel self-check refused "
                    f"(C={C}, H={H}, W={W}, T={T}); running {fb} fallback"
                ))
                use = fb
        if use == "fft":
            return _xcorr_fft(f, t)
        in_dtype = f.dtype
        if quant and quant_arm == "int8dot":
            # both operands on the int8 grid through one integer conv;
            # admitted above by quant_xcorr_ok(kernel="int8dot")
            return _xcorr_int8dot(f, t).astype(in_dtype)
        if quant:
            from tmr_tpu.ops.quant import quantize_template

            f = f.astype(jnp.bfloat16)
            t = quantize_template(t, dtype=jnp.bfloat16)
        elif prec_name == "bf16":
            f = f.astype(jnp.bfloat16)
            t = t.astype(jnp.bfloat16)
        # keep the f32 MXU accumulator in the result (the codebase's bf16-
        # matmul convention, e.g. models/vit.py): without this the conv
        # output would round to bf16 before the upcast below
        acc = jnp.float32 if (prec_name == "bf16" or quant) else None
        prec = lax.Precision.DEFAULT if quant else conv_prec
        if use == "pallas":
            from tmr_tpu.ops.pallas_xcorr import xcorr_pallas

            # the kernel upcasts to f32 and accumulates in f32, so it
            # satisfies every TMR_XCORR_PRECISION contract: with f32
            # inputs it equals the HIGHEST conv path (the VPU is true
            # f32), and under the bf16/quant knobs the inputs above
            # already carry the rounding
            return xcorr_pallas(f, t).astype(in_dtype)
        if use == "convnhwc":
            # same grouped conv in the TPU-native activation layout: XLA:TPU
            # canonicalizes NCHW convs by inserting layout transposes, so
            # expressing the op as NHWC/HWIO directly lets the compiler skip
            # them (the surrounding model is NHWC anyway; the matcher's NCHW
            # is inherited from the reference's torch layout). Semantics
            # identical to "conv" — A/B-measured, never assumed.
            lhs = f.reshape(1, b * C, H, W).transpose(0, 2, 3, 1)
            rhs = t.reshape(b * C, 1, T, T).transpose(2, 3, 1, 0)
            return lax.conv_general_dilated(
                lhs,
                rhs,
                window_strides=(1, 1),
                padding=[(T // 2, T // 2), (T // 2, T // 2)],
                feature_group_count=b * C,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=prec,
                preferred_element_type=acc,
            ).transpose(0, 3, 1, 2).reshape(b, C, H, W).astype(in_dtype)
        if use == "vmap":
            def one(fi, ti):  # fi: (C, H, W), ti: (C, T, T)
                return lax.conv_general_dilated(
                    fi[None],
                    ti.reshape(C, 1, T, T),
                    window_strides=(1, 1),
                    padding=[(T // 2, T // 2), (T // 2, T // 2)],
                    feature_group_count=C,
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    precision=prec,
                    preferred_element_type=acc,
                )[0]

            return jax.vmap(one)(f, t).astype(in_dtype)
        lhs = f.reshape(1, b * C, H, W)
        rhs = t.reshape(b * C, 1, T, T)
        return lax.conv_general_dilated(
            lhs,
            rhs,
            window_strides=(1, 1),
            padding=[(T // 2, T // 2), (T // 2, T // 2)],
            feature_group_count=b * C,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=prec,
            preferred_element_type=acc,
        ).reshape(b, C, H, W).astype(in_dtype)

    am = jax.sharding.get_abstract_mesh()
    if (
        impl != "fft"  # the FFT path has no group-merge; partitions cleanly
        and am is not None
        and not am.empty
        and "data" in am.axis_names
        and am.shape["data"] > 1
        and B % am.shape["data"] == 0
    ):
        out = _data_shard_map(_compute, am)(feature, template)
    else:
        out = _compute(feature, template)

    ht = template_hw[:, 0]
    wt = template_hw[:, 1]
    out = out / (ht * wt + 1e-14).astype(out.dtype)[:, None, None, None]

    ph = (ht // 2)[:, None]  # (B, 1)
    pw = (wt // 2)[:, None]
    ys = jnp.arange(H)[None, :]
    xs = jnp.arange(W)[None, :]
    row_ok = (ys >= ph) & (ys < H - ph)  # (B, H)
    col_ok = (xs >= pw) & (xs < W - pw)  # (B, W)
    mask = row_ok[:, None, :, None] & col_ok[:, None, None, :]
    out = jnp.where(mask, out, 0.0)
    if squeeze:
        out = out.sum(axis=1, keepdims=True)
    return out


#: sketch width of the coarse prefilter: the C feature channels project
#: onto this many fixed ±1 sketch channels before the low-res
#: correlation — the Johnson-Lindenstrauss estimate of the full C-channel
#: correlation at ~G/C of its cost
COARSE_SKETCH_CHANNELS = 32


def coarse_prefilter_scores(
    feature: jnp.ndarray,
    exemplars: jnp.ndarray,
    k_real: jnp.ndarray,
    n_real: jnp.ndarray,
    pool: int = 2,
    sketch: int = COARSE_SKETCH_CHANNELS,
) -> jnp.ndarray:
    """Channel-sketched, low-resolution correlation score per gallery
    bank entry — the gallery tier's coarse prefilter (serve/gallery.py).

    The full match runs the depthwise correlation over every (entry,
    channel) pair at the upsampled grid; this ranking stage reuses the
    SAME normalized-cross-correlation scoring at a fraction of the cost
    (the coarse-to-fine lesson of PAPERS.md's semi-dense matching paper
    + the NCC-scoring paper): the C feature channels project onto
    ``sketch`` fixed ±1 Rademacher channels (a deterministic
    Johnson-Lindenstrauss sketch — the sketch-space correlation is an
    unbiased estimator of the full-channel correlation with variance
    ~1/sketch, where a plain channel mean would be exactly ZERO after
    the backbone neck's per-position LayerNorm), average-pool ``pool``x
    spatially, and each entry's boxes extract tiny sketch-channel
    templates whose summed correlation peak — normalized by template
    energy, the NCC form at reduced resolution — is the entry's score.
    An entry's score is the max over its real exemplar rows.

    feature: (1, H, W, C) NHWC backbone features; exemplars
    (N, K, 4) normalized xyxy; k_real (N,) int32 real rows per entry;
    n_real () int32 real entries. Returns (N,) float32 scores with
    padded entries at ``-inf``. A RANKING heuristic only: the gallery
    tier's exactness contract is prefilter-off = exact, and the
    gallery_report/v1 bench measures recall-vs-full-match at the
    elected top-k rather than assuming it.
    """
    c = int(feature.shape[-1])
    g = max(min(int(sketch), c), 1)
    # fixed seeded Rademacher sketch: a trace-time constant (folded by
    # XLA), deterministic across processes/platforms by construction
    signs = jnp.where(
        jax.random.bernoulli(jax.random.key(20260804), 0.5, (c, g)),
        1.0, -1.0,
    ) / jnp.sqrt(float(g))
    f = jnp.einsum(
        "bhwc,cg->bghw", feature.astype(jnp.float32), signs
    )  # (1, G, H, W)
    # adaptive pooling: keep at least 8 coarse cells per axis — tiny
    # probe grids (a 128px frame's 8x8 backbone grid) would otherwise
    # pool below the resolution a box-sized template needs to rank
    if min(int(feature.shape[1]), int(feature.shape[2])) < 8 * pool:
        pool = 1
    if pool > 1:
        H, W = f.shape[2], f.shape[3]
        f = f[:, :, : H - H % pool, : W - W % pool]
        f = f.reshape(
            1, g, f.shape[2] // pool, pool, f.shape[3] // pool, pool
        ).mean(axis=(3, 5))
    # NCC zero-mean, per sketch channel per frame: untrained and
    # trained backbones alike carry a large common token component, and
    # without centering every template's correlation is dominated by
    # the shared DC (a featureless region would outrank a true match)
    f = f - f.mean(axis=(2, 3), keepdims=True)
    h, w = int(f.shape[2]), int(f.shape[3])
    m = max(h, w)
    cap = m - (1 - m % 2)  # largest odd capacity the coarse grid holds
    N, K = int(exemplars.shape[0]), int(exemplars.shape[1])
    fm = jnp.broadcast_to(f, (N * K, g, h, w))  # (NK, G, h, w)
    ex = exemplars.reshape(N * K, 4)
    templates, thw = jax.vmap(
        lambda fi, e: extract_template(fi, e, cap)
    )(fm, ex)
    # squeeze=True: the correlation sums over sketch channels — the
    # sketch estimate of the full matcher's channel-summed response.
    # Deliberately NO template-energy normalization beyond the
    # matcher's own 1/(ht*wt): the prefilter predicts the FULL
    # MATCHER's response magnitude, and the matcher is not
    # scale-invariant — an energy-normalized score would rank against
    # exactly the signal the downstream heads consume.
    corr = cross_correlation(fm, templates, thw, squeeze=True)
    scores = corr.reshape(N * K, -1).max(axis=1)
    scores = scores.reshape(N, K)
    row_ok = jnp.arange(K)[None, :] < k_real[:, None]
    scores = jnp.where(row_ok, scores, -jnp.inf).max(axis=1)
    entry_ok = jnp.arange(N) < n_real
    return jnp.where(entry_ok, scores, -jnp.inf)


def match_templates(
    feature: jnp.ndarray,
    exemplars: jnp.ndarray,
    capacity: int,
    template_type: str = "roi_align",
    squeeze: bool = False,
) -> jnp.ndarray:
    """Full matcher (template_matching.py:79-93) without the learnable scale.

    feature: (B, C, H, W); exemplars: (B, 4) normalized first-exemplar boxes.
    The reference's per-image Python loop becomes a vmap'd template extraction
    feeding one grouped conv.
    """
    extract = extract_template if template_type == "roi_align" else extract_prototype
    cap = capacity if template_type == "roi_align" else 1
    templates, thw = jax.vmap(lambda f, e: extract(f, e, cap))(feature, exemplars)
    return cross_correlation(feature, templates, thw, squeeze=squeeze)

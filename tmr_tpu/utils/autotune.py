"""Measured kernel-formulation selection (cuDNN-autotune philosophy, TPU-
style): some ops have several semantically identical lowerings whose relative
speed depends on the hardware/compiler pair — the matcher correlation
(ops/xcorr.py: grouped conv / vmap'd depthwise conv / FFT) and the decoder
tail (ops/fused_heads.py, ops/quant.py). (The ViT's attention is not swept:
ops/pallas_attn.window_formulation and global_formulation decide it from
what they observe.)
Rather than hardcoding a winner, ``autotune(cfg, ...)`` microbenchmarks each
variant ON DEVICE at the production shapes derived from the config and
exports the winners via the env knobs the modules read at trace time:

- ``TMR_XCORR_IMPL_SMALL`` — the small-bucket correlation winner. Scoped:
  ops/xcorr.py consults it only below FFT_CAPACITY_THRESHOLD, so the
  capacity-17 winner can never drag the 127/191 buckets off the FFT path.

The microbenchmarks are small isolated programs (one correlation, one
decoder tail) timed with the bench.py methodology via the shared
helpers in utils/profiling.py (device-staged inputs, scalar-chained
iterations, one closing fetch, RTT floor subtracted). Explicitly set env
knobs are respected and never overridden. Off-TPU the defaults stand
(XLA:CPU relative speeds do not transfer).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

from tmr_tpu.utils.cache import REPO_ROOT, STATE_DIR
from tmr_tpu.utils.profiling import chained_seconds_per_iter, measure_rtt_floor

XCORR_VARIANTS = ("conv", "convnhwc", "vmap", "fft", "pallas")
XCORR_PRECISIONS = ("highest", "default", "bf16")
DECODER_IMPL_VARIANTS = ("xla", "fused")
QUANT_VARIANTS = ("off", "int8")

#: structured gate-refusal causes captured by the LAST sweep of each env
#: knob, keyed {env_var: {annotated_row_label: [cause dicts]}} — populated
#: by the sweep harnesses from diagnostics.drain_gate_refusals() whenever
#: a variant's timing was recorded fallback-annotated, attached by
#: autotune() to the report entry (and from there to bench.py's JSON), so
#: a "(fallback)" row always travels with WHY the requested kernel refused
LAST_SWEEP_REFUSALS: Dict[str, Dict[str, list]] = {}


def _attach_refusals(
    report: Dict[str, object], knob: str, sweep_env: Optional[str] = None
) -> None:
    """Copy the last sweep's structured refusal causes into ``report[knob]``
    (under "refusals") when any fallback-annotated row recorded one.
    ``sweep_env`` names the env var the harness actually swept when it
    differs from the report knob (the xcorr impl sweep pins
    TMR_XCORR_IMPL but reports TMR_XCORR_IMPL_SMALL)."""
    ref = LAST_SWEEP_REFUSALS.get(sweep_env or knob)
    if ref and knob in report:
        report[knob]["refusals"] = {k: list(v) for k, v in ref.items()}

#: suffix marking a sweep entry whose timing measured a gate-refused
#: variant's FALLBACK formulation, not the labeled one. Single source of
#: truth for producers (the _sweep_*_env harnesses), consumer (the winner
#: filter in autotune()), and tests — the three must never desynchronize or
#: fallback rows become electable again.
FALLBACK_SUFFIX = " (fallback)"

#: bumped when a sweep harness changes in a way that invalidates
#: previously cached winners (folded into _variants_sig, so every stale
#: entry re-sweeps at the next hardware window). History: "fallback-label"
#: — pre-revision sweeps could record a gate-refused variant's fallback
#: timing under the requested label and crown it. "fused-relpos" — the
#: off-trace gate repair (flash_attn._self_check) meant every previously
#: refused kernel row might genuinely compile: stale cached winners had to
#: re-record at the next hardware window. "decoder-tail" — the decoder
#: tail joined the swept surface (TMR_DECODER_IMPL fused formulation,
#: TMR_QUANT int8 weights) and the full-program tail changed shape
#: (device decode compaction): formulation winners recorded against the
#: old tail must re-measure at the next hardware window. "int8-storage" —
#: the TMR_QUANT sweep grew the offline-stored arm ("int8+store":
#: TMR_QUANT_STORAGE=int8 hands the program a genuinely int8 param tree,
#: bitwise the fake-quant numerics at 1/4 the weight bytes): every
#: pre-storage TMR_QUANT winner must re-measure with the stored arm in
#: the running.
_SWEEP_REV = "int8-storage"

#: legal TMR_QUANT_STORAGE cache values (the stored arm of the quant
#: sweep; ops/quant.STORAGE_MODES is the consuming contract)
STORAGE_VARIANTS = ("off", "int8")


def _sweep_xcorr_env(
    env_var: str, variants, batch: int, emb_dim: int, hw: int, capacity: int,
    rtt: Optional[float], log: Callable[[str], None],
    skip=(), train: bool = False,
) -> Dict[str, float]:
    """Shared microbenchmark harness for the trace-time xcorr knobs: pin
    ``env_var`` to each variant, jit one correlation at the production
    matcher shape, time it chained. One harness for both sweeps so the step
    function / staging / failure handling can never diverge between them.
    ``train=True`` times forward + gradient w.r.t. the feature map (the
    matcher sits in the training grad path; backward cost ratios differ
    per lowering, so a fwd-only rank could mis-pick for training)."""
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tmr_tpu.diagnostics import (
        FormulationFallbackWarning,
        drain_gate_refusals,
    )
    from tmr_tpu.ops.xcorr import match_templates

    rng = np.random.default_rng(0)
    feat = jnp.asarray(
        rng.standard_normal((batch, emb_dim, hw, hw)), jnp.float32
    )
    ex = jnp.tile(jnp.asarray([[0.45, 0.45, 0.53, 0.55]], jnp.float32),
                  (batch, 1))
    rtt = measure_rtt_floor() if rtt is None else rtt
    times: Dict[str, float] = {}
    refusals = LAST_SWEEP_REFUSALS.setdefault(env_var, {})
    refusals.clear()
    prev = os.environ.get(env_var)
    try:
        for variant in variants:
            if variant in skip:
                continue
            os.environ[env_var] = variant
            drain_gate_refusals()  # discard causes from earlier traces

            if train:
                def loss_fn(f, e):
                    y = match_templates(f, e, capacity=capacity)
                    return jnp.sum(y.astype(jnp.float32) ** 2)

                @jax.jit
                def step(f, e, fb):
                    l, g = jax.value_and_grad(loss_fn)(f + fb, e)
                    return g, l * 0.0
            else:
                @jax.jit
                def step(f, e, fb):
                    y = match_templates(f + fb, e, capacity=capacity)
                    return y, jnp.sum(y) * 0.0

            # same fallback-labeling contract as _sweep_tail_env: a
            # gate-refused variant (pallas off-gate -> conv/fft) warns at
            # trace time and its timing is recorded annotated
            t = None
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                try:
                    t = chained_seconds_per_iter(step, feat, ex, rtt=rtt)
                except Exception as e:  # failed variant = not chosen
                    log(f"autotune: {env_var}[{variant}] failed: "
                        f"{type(e).__name__}: {e}")
            _reemit_unrelated(caught, env_var)
            caused = drain_gate_refusals()
            if t is None:
                continue
            if any(
                isinstance(w.message, FormulationFallbackWarning)
                and w.message.env_var == env_var
                for w in caught
            ):
                log(f"autotune: {env_var}[{variant}] gate-refused; timed "
                    "the fallback formulation — recording annotated")
                times[variant + FALLBACK_SUFFIX] = t
                if caused:
                    refusals[variant + FALLBACK_SUFFIX] = caused
            else:
                times[variant] = t
    finally:
        _restore(prev, env_var)
    return times


def _electable(times: Dict[str, float]) -> Dict[str, float]:
    """Drop FALLBACK_SUFFIX-annotated sweep entries from winner selection:
    they measured a DIFFERENT formulation than their label requested (gate
    refusal) — kept in the report as evidence, but exporting one as the
    winner would set an invalid env value whose timing belongs to another
    variant. Shared by every knob's selection so no sweep can diverge."""
    return {
        k: v for k, v in times.items() if not k.endswith(FALLBACK_SUFFIX)
    }


def _decisive_pick(
    times: Dict[str, float], baseline: str, log: Callable[[str], None],
    knob: str,
) -> str:
    """Relaxed-numerics selection policy, single-sourced for the
    TMR_XCORR_PRECISION and TMR_QUANT stages: pick the
    fastest electable row, but keep the exact ``baseline`` unless the win
    is decisive (>10%) — only a clear speedup justifies changed numerics —
    and fall back to the baseline when no exact row was measured (gate
    refusals/failures must never export unverified numerics)."""
    pickable = _electable(times)
    base = pickable.get(baseline)
    if not pickable or base is None:
        log(f"autotune: {knob}={baseline} "
            f"(no {baseline!r} baseline in {times})")
        return baseline
    best = min(pickable, key=pickable.get)
    if pickable[best] > 0.9 * base:
        best = baseline
    log(f"autotune: {knob}={best} {times}")
    return best


def _reemit_unrelated(caught, env_var: str,
                      also: tuple = ()) -> None:
    """Re-emit warnings the sweep's record=True capture swallowed, except
    the fallback markers for THE KNOB BEING SWEPT (those become the
    FALLBACK_SUFFIX annotation). Everything else must still reach the
    operator: a JAX transfer/deprecation warning that explains an anomalous
    timing, and fallback markers for a DIFFERENT knob (e.g. the user's
    pinned TMR_XCORR_IMPL=pallas falling back during the precision sweep).
    ``also`` names additional knobs whose fallbacks the sweep already
    accounted for (the quant sweep annotates TMR_DECODER_IMPL refusals)."""
    import warnings

    from tmr_tpu.diagnostics import FormulationFallbackWarning

    for w in caught:
        if (
            isinstance(w.message, FormulationFallbackWarning)
            and w.message.env_var in (env_var,) + tuple(also)
        ):
            continue
        warnings.warn_explicit(w.message, w.category, w.filename, w.lineno)


def pick_xcorr_impl(
    batch: int, emb_dim: int, hw: int, capacity: int,
    rtt: Optional[float] = None,
    log: Callable[[str], None] = lambda s: None,
    train: bool = False,
) -> Dict[str, float]:
    """Time every correlation lowering at the production matcher shape.
    Returns {variant: sec/iter}; caller picks min."""
    return _sweep_xcorr_env(
        "TMR_XCORR_IMPL", XCORR_VARIANTS, batch, emb_dim, hw, capacity,
        rtt, log, train=train,
    )


def pick_xcorr_precision(
    batch: int, emb_dim: int, hw: int, capacity: int,
    rtt: Optional[float] = None,
    log: Callable[[str], None] = lambda s: None,
    seed_highest: Optional[float] = None,
) -> Dict[str, float]:
    """Time the small-bucket correlation at each TMR_XCORR_PRECISION value
    under the CURRENTLY exported impl knobs (run after the impl sweep so the
    precision is measured on the winning formulation). "highest" is f32 via
    multi-pass bf16 emulation on the MXU (ops/xcorr.py) — on TPU the other
    two can win big; semantics differ only by f32/bf16 rounding.
    ``seed_highest`` injects the impl sweep's timing of the winner (the
    identical program at the default "highest" precision) instead of
    re-measuring it. Returns {precision: sec/iter}; caller picks min."""
    times = _sweep_xcorr_env(
        "TMR_XCORR_PRECISION", XCORR_PRECISIONS, batch, emb_dim, hw,
        capacity, rtt, log,
        skip=("highest",) if seed_highest is not None else (),
    )
    if seed_highest is not None:
        times["highest"] = seed_highest
    return times


def _sweep_tail_env(
    env_var: str, variants, batch: int, hw: int, c_cat: int,
    num_layers: int, kernel_size: int, dtype_name: str,
    rtt: Optional[float], log: Callable[[str], None],
    also_fallback_envs: tuple = (),
) -> Dict[str, float]:
    """Shared microbenchmark harness for the decoder-tail knobs
    (TMR_DECODER_IMPL, TMR_QUANT): pin ``env_var`` to each variant,
    rebuild the tail stage program (utils/stage_bench — the SAME program
    profile_breakdown and bench.py's stage_breakdown time), time it
    chained. Fallback labeling matches _sweep_xcorr_env: a gate-refused
    variant's timing is recorded annotated with its structured causes."""
    import warnings

    from tmr_tpu.diagnostics import (
        FormulationFallbackWarning,
        drain_gate_refusals,
    )
    from tmr_tpu.utils.stage_bench import build_decoder_tail_step

    rtt = measure_rtt_floor() if rtt is None else rtt
    times: Dict[str, float] = {}
    refusals = LAST_SWEEP_REFUSALS.setdefault(env_var, {})
    refusals.clear()
    prev = os.environ.get(env_var)
    try:
        for variant in variants:
            os.environ[env_var] = variant
            drain_gate_refusals()  # discard causes from earlier traces
            t = None
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                try:
                    step, inputs = build_decoder_tail_step(
                        batch, hw, c_cat, num_layers, kernel_size,
                        dtype_name,
                    )
                    t = chained_seconds_per_iter(step, *inputs, rtt=rtt)
                except Exception as e:
                    log(f"autotune: {env_var}[{variant}] failed: "
                        f"{type(e).__name__}: {e}")
            _reemit_unrelated(caught, env_var, also=also_fallback_envs)
            caused = drain_gate_refusals()
            if t is None:
                continue
            fell_back = any(
                isinstance(w.message, FormulationFallbackWarning)
                and w.message.env_var in (env_var,) + tuple(also_fallback_envs)
                for w in caught
            )
            if fell_back:
                log(f"autotune: {env_var}[{variant}] gate-refused; timed "
                    "the fallback formulation — recording annotated")
                times[variant + FALLBACK_SUFFIX] = t
                if caused:
                    refusals[variant + FALLBACK_SUFFIX] = caused
            else:
                times[variant] = t
    finally:
        _restore(prev, env_var)
    return times


def pick_decoder_impl(
    batch: int, hw: int, c_cat: int, num_layers: int, kernel_size: int,
    dtype_name: str = "bfloat16",
    rtt: Optional[float] = None,
    log: Callable[[str], None] = lambda s: None,
) -> Dict[str, float]:
    """Time the decoder_heads stage (both conv stacks + heads at the
    production (hw, c_cat) geometry, in the model's ``dtype_name`` so the
    evidence is about the program production traces) per TMR_DECODER_IMPL
    formulation. Both are oracle-pinned identical numerics
    (fused_heads_ok), so the caller elects plain-min.
    Returns {variant: sec/iter}."""
    return _sweep_tail_env(
        "TMR_DECODER_IMPL", DECODER_IMPL_VARIANTS, batch, hw, c_cat,
        num_layers, kernel_size, dtype_name, rtt, log,
    )


def pick_quant(
    batch: int, hw: int, c_cat: int, num_layers: int, kernel_size: int,
    dtype_name: str = "bfloat16",
    emb_dim: Optional[int] = None, capacity: int = 17,
    rtt: Optional[float] = None,
    log: Callable[[str], None] = lambda s: None,
) -> Dict[str, float]:
    """Time BOTH surfaces the TMR_QUANT export flips — the decoder_heads
    stage and the matcher correlation — at each mode under the CURRENTLY
    exported decoder/xcorr impls (run after those sweeps, the
    precision-stage pattern), returning their per-variant SUM: the
    decisive-win policy must judge the knob's whole flipped workload, not
    just the decoder arm. int8 changes numerics, so the caller elects
    against the exact "off" baseline, and a gate refusal in either stage
    (TMR_DECODER_IMPL, TMR_QUANT decoder or xcorr oracle) annotates the
    variant as a fallback row — quantized timings must never masquerade
    as exact-path evidence or vice versa. ``emb_dim=None`` skips the
    matcher arm (decoder-only callers, e.g. box_reg-ablated sweeps)."""
    times = _sweep_tail_env(
        "TMR_QUANT", QUANT_VARIANTS, batch, hw, c_cat,
        num_layers, kernel_size, dtype_name, rtt, log,
        also_fallback_envs=("TMR_DECODER_IMPL",),
    )
    # the STORED arm ("int8+store"): TMR_QUANT pinned to int8 while
    # TMR_QUANT_STORAGE sweeps int8 — the stage program then consumes an
    # offline-quantized tree (utils/stage_bench resolves storage the way
    # the production trace does), so the timing is about genuinely
    # shrunken weight bytes (4x on the quantized leaves), not the
    # fake-quant formulation again. A
    # storage admission refusal annotates the row as a fallback like
    # every other gate.
    prev_q = os.environ.get("TMR_QUANT")
    os.environ["TMR_QUANT"] = "int8"
    try:
        stimes = _sweep_tail_env(
            "TMR_QUANT_STORAGE", ("int8",), batch, hw, c_cat,
            num_layers, kernel_size, dtype_name, rtt, log,
            also_fallback_envs=("TMR_QUANT", "TMR_DECODER_IMPL",
                                "TMR_QUANT_KERNEL"),
        )
    finally:
        _restore(prev_q, "TMR_QUANT")
    store_refusals = {
        label: causes for label, causes in
        LAST_SWEEP_REFUSALS.get("TMR_QUANT_STORAGE", {}).items()
    }

    def _store_label(label: str) -> str:
        return "int8+store" + (
            FALLBACK_SUFFIX if label.endswith(FALLBACK_SUFFIX) else ""
        )

    for label, t in stimes.items():
        times[_store_label(label)] = t
    refusals = LAST_SWEEP_REFUSALS.setdefault("TMR_QUANT", {})
    for label, causes in store_refusals.items():
        refusals.setdefault(_store_label(label), []).extend(causes)
    if emb_dim is None:
        return times
    # both sweeps key LAST_SWEEP_REFUSALS["TMR_QUANT"] and the second
    # clears it on entry: snapshot the tail stage's causes and merge
    tail_refusals = dict(LAST_SWEEP_REFUSALS.get("TMR_QUANT", {}))
    xtimes = _sweep_xcorr_env(
        "TMR_QUANT", QUANT_VARIANTS, batch, emb_dim, hw, capacity,
        rtt, log,
    )
    refusals = LAST_SWEEP_REFUSALS.setdefault("TMR_QUANT", {})
    for label, causes in tail_refusals.items():
        refusals.setdefault(label, []).extend(causes)
    combined: Dict[str, float] = {}
    for v in QUANT_VARIANTS + ("int8+store",):
        # the matcher program is identical between the fake and stored
        # arms (templates are runtime data, storage never touches them):
        # the stored row reuses the int8 correlation timing
        xv = "int8" if v == "int8+store" else v
        t = times.get(v)
        x = xtimes.get(xv)
        if t is not None and x is not None:
            combined[v] = t + x
            continue
        # annotated (or failed) in either stage: the sum is evidence
        # about a fallback formulation somewhere — never electable
        tf = t if t is not None else times.get(v + FALLBACK_SUFFIX)
        xf = x if x is not None else xtimes.get(xv + FALLBACK_SUFFIX)
        if tf is not None and xf is not None:
            combined[v + FALLBACK_SUFFIX] = tf + xf
    log(f"autotune: TMR_QUANT stages decoder={times} xcorr={xtimes}")
    return combined


def _vit_kind(cfg):
    """backbone -> sweep geometry family (None for non-ViT backbones) —
    single source for autotune() and stale_winners(), whose cache keys
    must never diverge."""
    return {"sam": "vit_h", "sam_vit_h": "vit_h",
            "sam_vit_b": "vit_b"}.get(cfg.backbone)


def _cache_key(cfg, image_size: int, batch: int, vit_kind, train: bool) -> str:
    """The per-(device, shape) winner-cache key. up_hw (not image_size
    alone) keys it: the xcorr sweep shape depends on feature_upsample, and
    a winner measured at the wrong map size must never be silently reused.
    Training keys separately — fwd-only winners must never be reused for
    training (the Pallas kernels' recompute backward inverts the ranking)
    and vice versa."""
    import jax

    grid = image_size // 16
    up_hw = 2 * grid if cfg.feature_upsample else grid
    key = "|".join(
        str(p) for p in (
            jax.devices()[0].device_kind, image_size, up_hw, batch,
            cfg.emb_dim, vit_kind,
        )
    )
    if train:
        key += "|train"
    return key


def stale_winners(
    cfg, image_size: int, batch: int, train: bool = False
) -> Dict[str, str]:
    """Cached/seeded winners whose ``_variants_`` stamp is STALE (the
    variant set grew or the harness revision bumped) — still-valid env
    values that a fresh sweep will re-decide, returned so bench.py's
    pre-sweep bank can measure under the last known-good configuration
    instead of the library defaults. Without this, growing a variant set
    silently downgrades the banked wedge-fallback number to whatever the
    ungated default formulation happens to be."""
    key = _cache_key(cfg, image_size, batch, _vit_kind(cfg), train)
    cached = _cache_load().get(key, {})
    out: Dict[str, str] = {}
    for knob in _VERSIONED_KNOBS:
        if (
            knob in cached
            and knob not in os.environ
            and cached.get(f"_variants_{knob}") != _variants_sig(knob)
        ):
            out[knob] = cached[knob]
    return out


def _active_small_impl(cached: Dict[str, str]) -> str:
    """The impl the small-bucket correlation will actually dispatch to,
    resolved the way ops/xcorr.py does: explicit TMR_XCORR_IMPL, else the
    SMALL knob (env now, or the cached winner about to be exported), else
    the backend-dependent default (ops/xcorr.py small_impl_default — the
    single source of truth, so this mirror can never drift from dispatch)."""
    from tmr_tpu.ops.xcorr import small_impl_default

    active = os.environ.get("TMR_XCORR_IMPL", "auto")
    if active == "auto":
        active = os.environ.get(
            "TMR_XCORR_IMPL_SMALL",
            cached.get("TMR_XCORR_IMPL_SMALL", small_impl_default()),
        )
    if active == "auto":
        active = small_impl_default()
    return active


def _restore(prev: Optional[str], name: str) -> None:
    if prev is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = prev


def bench_batch_cache_key(device_kind: str, image_size: int) -> str:
    """Cache key for the measured throughput-optimal headline batch —
    written by scripts/bench_extra.py's batch sweep, read by bench.py; one
    definition so writer and reader can never drift."""
    return f"{device_kind}|bench_batch|{image_size}"


def measured_bench_batch(
    image_size: int, device_kind: Optional[str] = None
) -> Optional[int]:
    """The persisted throughput-optimal batch from bench_extra's batch
    sweep for (device kind, image size), or None when never measured — the
    shared reader behind bench.py's headline default and the serving
    layer's coalescing bound (FastFlow's lesson: measured batch picks over
    static guesses). Best-effort: any backend/cache problem reads as
    "not measured"."""
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    picked = _cache_load().get(
        bench_batch_cache_key(device_kind, int(image_size)), {}
    ).get("TMR_BENCH_BATCH")
    try:
        return int(picked) if picked is not None else None
    except (TypeError, ValueError):
        return None


def gallery_cache_key(device_kind: str, image_size: int) -> str:
    """Cache key for the gallery tier's measured winners (the N-bucket
    ladder cap and the prefilter top-k) — written by
    scripts/gallery_bench.py's sweeps, read by serve/gallery.py; one
    definition so writer and reader can never drift."""
    return f"{device_kind}|gallery|{image_size}"


def _measured_gallery(image_size: int, knob: str,
                      device_kind: Optional[str]) -> Optional[int]:
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    picked = _cache_load().get(
        gallery_cache_key(device_kind, int(image_size)), {}
    ).get(knob)
    try:
        return int(picked) if picked is not None else None
    except (TypeError, ValueError):
        return None


def measured_gallery_nmax(
    image_size: int, device_kind: Optional[str] = None
) -> Optional[int]:
    """The measured fused-gallery N-bucket ladder cap for (device kind,
    image size), or None when never measured — the gallery analog of
    :func:`measured_bench_batch` (bank sizes past the cap chunk into
    multiple program calls). Best-effort like its sibling."""
    return _measured_gallery(image_size, "TMR_GALLERY_NMAX", device_kind)


def measured_gallery_topk(
    image_size: int, device_kind: Optional[str] = None
) -> Optional[int]:
    """The bench-elected coarse-prefilter top-k (smallest rung with
    recall >= 0.99 vs full match and >= 2x invocation cut on the
    gallery_bench workload), or None. Consumed only when the user opts
    in with ``TMR_GALLERY_PREFILTER_TOPK=auto`` — the prefilter stays
    off (exact) by default."""
    return _measured_gallery(image_size, "TMR_GALLERY_PREFILTER_TOPK",
                             device_kind)


def record_gallery_winners(
    image_size: int, nmax: Optional[int] = None,
    topk: Optional[int] = None, device_kind: Optional[str] = None
) -> None:
    """Persist gallery sweep winners (scripts/gallery_bench.py is the
    writer). Best-effort like every cache write."""
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            return
    extra = {}
    if nmax is not None and int(nmax) > 0:
        extra["TMR_GALLERY_NMAX"] = str(int(nmax))
    if topk is not None and int(topk) > 0:
        extra["TMR_GALLERY_PREFILTER_TOPK"] = str(int(topk))
    if extra:
        _cache_store(gallery_cache_key(device_kind, int(image_size)), {},
                     extra=extra)


CACHE_PATH = os.path.join(STATE_DIR, "autotune.json")


#: winners measured on real hardware, committed with the repo: a fresh
#: machine/container (e.g. the driver's round-end bench) starts from these
#: instead of paying the full sweep. The user
#: cache always takes precedence; entries are validated like the cache.
SEED_PATH = os.path.join(REPO_ROOT, "AUTOTUNE_SEED.json")


def _load_validated(path: str) -> Dict[str, dict]:
    import json

    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError):
        return {}
    # best-effort all the way down: a foreign/hand-edited file must degrade
    # to "no cache", not crash the launch
    if not isinstance(obj, dict):
        return {}
    return _validate_cache_obj(obj)


def _cache_load() -> Dict[str, dict]:
    path = os.environ.get("TMR_AUTOTUNE_CACHE", CACHE_PATH)
    seed = _load_validated(os.environ.get("TMR_AUTOTUNE_SEED", SEED_PATH))
    user = _load_validated(path)
    # knob-level merge within each key, user values winning: a partial
    # user entry (written by a run with some knobs env-pinned) must not
    # shadow the seed's winners for knobs it never locally measured
    out = dict(seed)
    for k, v in user.items():
        out[k] = {**out.get(k, {}), **v}
    return out


#: knobs whose cache entries are versioned by the variant tuple the sweep
#: measured against (recorded as ``_variants_<knob>``): a cached winner
#: predating the current tuple is stale — a newly added variant (e.g. a
#: new kernel) must get its chance at the next hardware sweep instead of
#: being silently locked out by an older pick.
_VERSIONED_KNOBS = (
    "TMR_XCORR_IMPL_SMALL", "TMR_XCORR_PRECISION",
    "TMR_DECODER_IMPL", "TMR_QUANT", "TMR_QUANT_STORAGE",
)


def _variants_sig(knob: str) -> str:
    sets = {
        "TMR_XCORR_IMPL_SMALL": XCORR_VARIANTS,
        "TMR_XCORR_PRECISION": XCORR_PRECISIONS,
        "TMR_DECODER_IMPL": DECODER_IMPL_VARIANTS,
        "TMR_QUANT": QUANT_VARIANTS,
        "TMR_QUANT_STORAGE": STORAGE_VARIANTS,
    }
    sig = ",".join(sets[knob])
    if knob in ("TMR_XCORR_IMPL_SMALL", "TMR_DECODER_IMPL", "TMR_QUANT",
                "TMR_QUANT_STORAGE"):
        # formulation-sweep winners are additionally versioned by the
        # harness revision: a winner picked by a pre-revision sweep may be
        # a mislabeled fallback timing (see _SWEEP_REV) and must go stale
        # rather than load as a cached hit. (TMR_XCORR_PRECISION rows are
        # precision labels, valid regardless of which impl dispatched.)
        sig += f"|{_SWEEP_REV}"
    return sig


def _validate_cache_obj(obj: dict) -> Dict[str, dict]:
    valid = {
        "TMR_XCORR_IMPL_SMALL": set(XCORR_VARIANTS) | {"auto"},
        "TMR_XCORR_PRECISION": set(XCORR_PRECISIONS),
        # metadata, not an env knob: which impl the precision winner was
        # measured under (its decisive-win evidence is impl-specific)
        "_precision_impl": set(XCORR_VARIANTS),
        "TMR_DECODER_IMPL": set(DECODER_IMPL_VARIANTS) | {"auto"},
        "TMR_QUANT": set(QUANT_VARIANTS) | {"auto"},
        "TMR_QUANT_STORAGE": set(STORAGE_VARIANTS),
        # metadata: which decoder formulation the quant winner's
        # decisive-win evidence was measured under
        "_quant_decoder_impl": set(DECODER_IMPL_VARIANTS) | {"auto"},
    }
    # measured throughput-optimal eval batch (bench_extra's batch sweep)
    # — positive ints as strings
    digit_keys = {
        "TMR_BENCH_BATCH",
        # gallery sweep winners (scripts/gallery_bench.py writes them,
        # serve/gallery.py reads): the N-bucket ladder cap + the
        # elected prefilter top-k
        "TMR_GALLERY_NMAX", "TMR_GALLERY_PREFILTER_TOPK",
    }
    # per-knob filtering: one invalid/unknown winner drops only itself —
    # the valid sibling survives (and all-or-nothing would let the next
    # _cache_store rewrite erase it from disk permanently)
    out: Dict[str, dict] = {}
    for k, v in obj.items():
        if not isinstance(v, dict):
            continue
        kept = {
            kk: vv for kk, vv in v.items()
            if isinstance(kk, str) and isinstance(vv, str)
            and (
                vv in valid.get(kk, ())
                or (kk in digit_keys and vv.isascii() and vv.isdigit()
                    and int(vv) > 0)
                # variant-set version stamps: free-form comma-joined
                # names, compared verbatim against _variants_sig()
                or kk.startswith("_variants_")
            )
        }
        if kept:
            out[k] = kept
    return out


def seed_load(path: Optional[str] = None) -> Dict[str, dict]:
    """Raw load of the committed seed for WRITER scripts
    (scripts/pick_full_program.py, scripts/promote_cache_to_seed.py).
    Unlike ``_load_validated`` (the READER path, which drops unknown
    keys), writers must keep provenance keys like ``_full_program_ab``
    intact — so this only enforces shape: top-level dict, per-entry
    dicts; anything else degrades to absent, never a crash."""
    import json

    path = path or os.environ.get("TMR_AUTOTUNE_SEED", SEED_PATH)
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(obj, dict):
        return {}
    return {k: v for k, v in obj.items() if isinstance(v, dict)}


def seed_store(seed: Dict[str, dict], path: Optional[str] = None) -> None:
    """Atomic seed write shared by the writer scripts — one protocol
    (tmp + os.replace, stable formatting) so concurrent readers see the
    old seed or the new one, never a truncated file."""
    import json

    from tmr_tpu.utils.atomicio import atomic_write

    path = path or os.environ.get("TMR_AUTOTUNE_SEED", SEED_PATH)

    def _write(f):
        json.dump(seed, f, indent=1, sort_keys=True)
        f.write("\n")

    atomic_write(path, _write)


def _cache_store(
    key: str, report: Dict[str, object], extra: Optional[Dict[str, str]] = None
) -> None:
    import json

    from tmr_tpu.utils.atomicio import atomic_write

    path = os.environ.get("TMR_AUTOTUNE_CACHE", CACHE_PATH)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # read-modify-write the USER cache only — merging the seed here
        # would copy committed seed entries into the user file forever
        cache = _load_validated(path)
        # merge: a partial report (one knob pinned by the user this run)
        # must not wipe the sibling knob's previously cached winner
        cache[key] = {
            **cache.get(key, {}),
            **{k: v["picked"] for k, v in report.items()},
            **(extra or {}),
        }
        # atomic + fsynced (atomicio): a LiveTuner promotion writing the
        # winner bank while an offline sweep commits here must never
        # leave either file torn — readers see old or new, never partial
        atomic_write(path, lambda f: json.dump(
            cache, f, indent=1, sort_keys=True))
    except OSError:
        pass  # caching is best-effort; the measured winners still export


def autotune(
    cfg, image_size: int, batch: int,
    log: Callable[[str], None] = lambda s: None,
    tune_precision: bool = True,
    train: bool = False,
    sweep: bool = True,
) -> Dict[str, object]:
    """Measure the variant sets at the production shapes of ``cfg`` and
    EXPORT the winners via their env knobs (os.environ, read by the modules
    at trace time) so every program compiled afterwards in this process uses
    them.

    Winners persist in ``<repo>/.tmr_cache/autotune.json`` keyed by (device
    kind, shapes): measured once on hardware, they become the default for
    every later process on the machine with no re-sweep — the "measured
    winners become the defaults" mechanism. ``TMR_AUTOTUNE_FORCE=1``
    re-measures; ``TMR_AUTOTUNE_CACHE`` relocates the file.

    Knobs the user already set explicitly are left untouched. Off-TPU this
    is a no-op (returns {}). Returns {knob: {"picked": ..., "times": ...}}
    (cached hits carry {"picked": ..., "cached": True} instead of times).

    ``tune_precision=False`` skips the TMR_XCORR_PRECISION sweep entirely:
    the decisive-win policy justifies relaxed numerics for inference score
    ranking only — training runs (main.py) must not inherit bf16-rounded
    matcher GRADIENTS from an eval-shape microbenchmark.
    """
    import jax

    if jax.default_backend() != "tpu":
        return {}
    vit_kind = _vit_kind(cfg)
    report: Dict[str, object] = {}
    grid = image_size // 16
    up_hw = 2 * grid if cfg.feature_upsample else grid

    key = _cache_key(cfg, image_size, batch, vit_kind, train)
    force = os.environ.get("TMR_AUTOTUNE_FORCE", "") not in ("", "0")
    cached = {} if force else _cache_load().get(key, {})
    for knob in _VERSIONED_KNOBS:
        if knob in cached and cached.get(
            f"_variants_{knob}"
        ) != _variants_sig(knob):
            # the winner predates the current variant set (or carries no
            # stamp): stale — re-measure so new variants get their shot
            cached.pop(knob)
            log(f"autotune: cached {knob} predates the current variant "
                "set; re-measuring")

    # the stored-int8 arm rides the TMR_QUANT sweep's evidence: export it
    # when cached and not user-set, BEFORE the everything-pinned early
    # return below (a fully env-pinned rerun still needs it)
    knob = "TMR_QUANT_STORAGE"
    if knob in cached and knob not in os.environ:
        os.environ[knob] = cached[knob]
        report[knob] = {"picked": cached[knob], "cached": True}
        log(f"autotune: {knob}={cached[knob]} (cached, {key})")

    wanted = set()
    if (
        "TMR_XCORR_IMPL" not in os.environ
        and "TMR_XCORR_IMPL_SMALL" not in os.environ
    ):
        wanted.add("TMR_XCORR_IMPL_SMALL")
    if tune_precision and "TMR_XCORR_PRECISION" not in os.environ:
        wanted.add("TMR_XCORR_PRECISION")
    if not train and "TMR_DECODER_IMPL" not in os.environ and cfg.box_reg:
        # the fused formulation covers the two-stack tail; single-stack
        # (box-regression-ablated) models stay on the module path. The
        # stage sweep times FORWARD only — training runs keep the parity
        # default instead of electing from a fwd-only rank (the
        # _sweep_xcorr_env train=True lesson: backward cost ranks
        # formulations differently)
        wanted.add("TMR_DECODER_IMPL")
    if tune_precision and "TMR_QUANT" not in os.environ and cfg.box_reg:
        # quantized weights are the relaxed-numerics tier below bf16
        # scores: inference sweeps only, decisive-win policy, tiered
        # oracle gate (ops/quant.py) — training must never inherit them
        wanted.add("TMR_QUANT")
    if not wanted:
        return report  # everything pinned: skip even the rtt round trip
    if cached.get("TMR_XCORR_PRECISION", "highest") != "highest" and (
        "TMR_XCORR_IMPL_SMALL" in wanted
        or cached.get("_precision_impl") != _active_small_impl(cached)
    ):
        # a relaxed-precision winner's decisive-win evidence is
        # impl-specific: drop it when it was measured under a different
        # impl (user pinned another one since), AND whenever a fresh impl
        # sweep is about to run — the sweep may pick a different winner,
        # and exported-early bf16 numerics must never outlive the pairing
        # they were validated on (re-measured after the fresh pick instead)
        cached = {k: v for k, v in cached.items()
                  if k != "TMR_XCORR_PRECISION"}
    if cached.get("TMR_QUANT", "off") != "off" and (
        "TMR_DECODER_IMPL" in wanted
        or cached.get("_quant_decoder_impl") != os.environ.get(
            "TMR_DECODER_IMPL", cached.get("TMR_DECODER_IMPL", "auto")
        )
    ):
        # an int8 winner's decisive-win evidence is decoder-impl-specific
        # (the _precision_impl rule applied to the tail): drop it when the
        # formulation it was measured under changes or is about to be
        # re-swept — re-decided after the fresh pick instead. The stored
        # arm's evidence rides the same sweep, so it drops with it.
        cached = {k: v for k, v in cached.items()
                  if k not in ("TMR_QUANT", "TMR_QUANT_STORAGE")}
    # export every cached wanted knob up front; only the remainder is
    # measured. A seed file (AUTOTUNE_SEED.json) typically covers the big
    # knobs, so a fresh container sweeps just the unseeded ones instead of
    # everything.
    for knob in sorted(wanted & set(cached)):
        os.environ[knob] = cached[knob]
        report[knob] = {"picked": cached[knob], "cached": True}
        log(f"autotune: {knob}={cached[knob]} (cached, {key})")
    wanted -= set(cached)
    if not wanted:
        if report:
            extra = {}
            for knob in _VERSIONED_KNOBS:
                if knob in report:
                    extra[f"_variants_{knob}"] = _variants_sig(knob)
            _cache_store(key, report, extra)
        return report
    if not sweep:
        # sweep=False: export-only pass (bench.py's preliminary headline
        # runs BEFORE any sweeping so a mid-sweep failure still
        # leaves a real measurement). Report which knobs a full call
        # would measure; nothing is stored.
        report["_pending"] = sorted(wanted)
        return report

    rtt = measure_rtt_floor()
    if "TMR_XCORR_IMPL_SMALL" in wanted:
        # capacity 17 = the typical FSCD exemplar bucket; the winner is
        # exported through the SMALL-scoped knob (see module docstring)
        times = pick_xcorr_impl(batch, cfg.emb_dim, up_hw, 17, rtt=rtt,
                                log=log, train=train)
        pickable = _electable(times)
        if pickable:
            best = min(pickable, key=pickable.get)
            os.environ["TMR_XCORR_IMPL_SMALL"] = best
            report["TMR_XCORR_IMPL_SMALL"] = {"picked": best, "times": times}
            _attach_refusals(report, "TMR_XCORR_IMPL_SMALL",
                             "TMR_XCORR_IMPL")
            log(f"autotune: TMR_XCORR_IMPL_SMALL={best} {times}")

    if "TMR_XCORR_PRECISION" in wanted:
        # sweep AFTER the impl pick so precision is measured on the winning
        # small-bucket formulation. Resolve the active small-bucket impl
        # exactly the way ops/xcorr.py dispatches it: explicit
        # TMR_XCORR_IMPL, else the SMALL knob (just exported above or
        # user-pinned), else the conv default.
        active = _active_small_impl({})
        if active == "fft":
            # the FFT path is f32 regardless; record the no-op so the cache
            # entry is complete and later runs skip the sweep
            report["TMR_XCORR_PRECISION"] = {"picked": "highest",
                                             "times": {}}
            os.environ["TMR_XCORR_PRECISION"] = "highest"
        else:
            # the impl sweep already timed this exact program at "highest"
            # (the knob was unset during it): reuse that number instead of
            # paying a third compile+timing round
            seed = None
            xc = report.get("TMR_XCORR_IMPL_SMALL")
            if xc and xc.get("times", {}).get(active) is not None:
                seed = xc["times"][active]
            times = pick_xcorr_precision(
                batch, cfg.emb_dim, up_hw, 17, rtt=rtt, log=log,
                seed_highest=seed,
            )
            if times:
                best = _decisive_pick(times, "highest", log,
                                      "TMR_XCORR_PRECISION")
                os.environ["TMR_XCORR_PRECISION"] = best
                report["TMR_XCORR_PRECISION"] = {"picked": best,
                                                 "times": times}
                _attach_refusals(report, "TMR_XCORR_PRECISION")

    c_cat = cfg.emb_dim * 2 if cfg.fusion else cfg.emb_dim
    if "TMR_DECODER_IMPL" in wanted:
        times = pick_decoder_impl(
            batch, up_hw, c_cat, cfg.decoder_num_layer,
            cfg.decoder_kernel_size, cfg.compute_dtype, rtt=rtt, log=log,
        )
        pickable = _electable(times)
        if pickable:
            best = min(pickable, key=pickable.get)
            os.environ["TMR_DECODER_IMPL"] = best
            report["TMR_DECODER_IMPL"] = {"picked": best, "times": times}
            _attach_refusals(report, "TMR_DECODER_IMPL")
            log(f"autotune: TMR_DECODER_IMPL={best} {times}")

    if "TMR_QUANT" in wanted:
        # sweep AFTER the decoder-impl pick (int8 rides the fused
        # formulation; its win is paired to the impl active now)
        if os.environ.get("TMR_DECODER_IMPL", "auto") != "fused":
            # quantized weights only ride the fused path: record the
            # no-op so the cache entry is complete and later runs skip
            os.environ["TMR_QUANT"] = "off"
            report["TMR_QUANT"] = {"picked": "off", "times": {}}
            if "TMR_QUANT_STORAGE" not in os.environ:
                os.environ["TMR_QUANT_STORAGE"] = "off"
                report["TMR_QUANT_STORAGE"] = {"picked": "off",
                                               "times": {}}
        else:
            times = pick_quant(
                batch, up_hw, c_cat, cfg.decoder_num_layer,
                cfg.decoder_kernel_size, cfg.compute_dtype,
                emb_dim=cfg.emb_dim, rtt=rtt, log=log,
            )
            # off / fake / stored elect on one decisive-win ladder vs
            # the exact baseline; the stored row's numerics are bitwise
            # the fake row's, so between the two int8 arms plain-min
            # applies implicitly (whichever is faster wins the min)
            best = _decisive_pick(times, "off", log, "TMR_QUANT")
            picked_quant = "off" if best == "off" else "int8"
            picked_store = "int8" if best == "int8+store" else "off"
            os.environ["TMR_QUANT"] = picked_quant
            report["TMR_QUANT"] = {"picked": picked_quant, "times": times}
            _attach_refusals(report, "TMR_QUANT")
            if "TMR_QUANT_STORAGE" not in os.environ:
                # the stored arm's evidence lives in the TMR_QUANT times
                # ("int8+store" rows); an explicit user pin is respected
                os.environ["TMR_QUANT_STORAGE"] = picked_store
                report["TMR_QUANT_STORAGE"] = {"picked": picked_store,
                                               "times": {}}

    if report:
        extra = {}
        if "TMR_XCORR_PRECISION" in report:
            extra["_precision_impl"] = _active_small_impl({})
        if "TMR_QUANT" in report:
            extra["_quant_decoder_impl"] = os.environ.get(
                "TMR_DECODER_IMPL", "auto"
            )
        for knob in _VERSIONED_KNOBS:
            # stamp every exported winner — fresh sweeps beat the current
            # set by construction, and cached hits passed the staleness
            # check against it; leaving cached knobs unstamped would let a
            # later seed's fresh stamp vouch for a stale user-cache value
            # through the knob-level merge in _cache_load
            if knob in report:
                extra[f"_variants_{knob}"] = _variants_sig(knob)
        _cache_store(key, report, extra)
    return report

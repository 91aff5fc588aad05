"""Per-program device-time attribution and live MFU / roofline accounting.

MFU was only ever computed offline inside bench.py; the serve and map
paths that actually burn device hours had no notion of achieved FLOP/s.
This module closes that gap at the same seam PR 4's compile accounting
uses: every ``Predictor._compiled`` program is wrapped
(:func:`track_devtime`), and with the flight recorder ON
(``TMR_FLIGHT=1``, see obs/flight.py) each execution records

- ``dispatch_s`` — call entry to dispatch return (host trace/dispatch
  share), and
- ``device_s``  — dispatch return to outputs ready
  (``jax.block_until_ready``; execution + device-queue wait).

Blocking per call is the honest price of attribution — the flight
recorder is a measurement mode, not the default serving configuration;
disabled, the wrapper is one bool check (the span-cost contract, pinned
by tests/test_flight.py). The rtt-aware
:func:`attribute_call` harness is the per-stage alternative
scripts/profile_breakdown.py uses.

Each program is paired with a cost model — the compiled executable's own
``cost_analysis()`` (FLOPs + bytes accessed), falling back to the
:func:`forward_tflops_per_image` analytic model (moved here from
bench.py; both agree within the PERF.md-documented 1.17x envelope) —
and :func:`mfu_report` reduces the table to one validated
``mfu_report/v1`` document: per-program achieved FLOP/s, MFU against
the per-platform peak, and a compute- vs memory-bound roofline
classification from arithmetic intensity vs the platform ridge point.

Import-light on purpose: jax is imported inside functions only.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from tmr_tpu.diagnostics import MFU_REPORT_SCHEMA
from tmr_tpu.obs import flight as _flight

# -------------------------------------------------------------- cost model


def forward_tflops_per_image(
    image_size: int = 1024,
    embed_dim: int = 768,
    depth: int = 12,
    num_heads: int = 12,
    n_global: int = 4,
    window: int = 14,
    out_chans: int = 256,
    emb_dim: int = 512,
    template_cap: int = 17,
    fusion: bool = True,
    decoder_layers: int = 1,
    part: str = "full",
) -> float:
    """Analytic forward FLOPs (multiply+add = 2) of the fused eval
    program — bench.py's MFU denominator (it imports this) and the
    devtime layer's fallback when ``cost_analysis()`` is unavailable.

    ``part`` selects the program family: "full" (the fused single
    program), "backbone" (encoder + neck only — the serving layer's
    feature-fill program), "heads" (projection/match/decoders/heads on
    precomputed features — the feature-cache-hit program).

    The windowed blocks' qkv/proj (and rel-pos) terms count PADDED
    tokens: window partition physically pads the grid to a multiple of
    ``window`` and the attention-internal projections run on the padded
    layout — at 128²-class probe geometry the padding is most of the
    work, and counting unpadded tokens put the model 2x under XLA's own
    ``cost_analysis()`` (within ~2% with padding counted; the 1.17x
    acceptance envelope is documented in PERF.md).
    """
    if part not in ("full", "backbone", "heads"):
        raise ValueError(f"unknown part {part!r}")
    grid = image_size // 16
    s = grid * grid
    d = embed_dim

    # patch embed: 16x16x3 conv to D
    bb = s * (16 * 16 * 3) * d * 2
    # transformer blocks: mlp (8D^2/token) runs on the unpadded grid;
    # qkv+proj (4D^2/token) run inside attention — on the PADDED window
    # layout for windowed blocks, the real grid for global blocks
    pad_grid = ((grid + window - 1) // window) * window
    s_pad = pad_grid * pad_grid
    bb += depth * s * 8 * d * d * 2
    bb += n_global * s * 4 * d * d * 2
    bb += (depth - n_global) * s_pad * 4 * d * d * 2
    # attention: windowed blocks see `window^2` keys, global blocks all S
    bb += (depth - n_global) * 2 * s_pad * (window * window) * d * 2
    bb += n_global * 2 * s * s * d * 2
    # decomposed rel-pos: q x rel_h + q x rel_w einsums
    head_dim = d // num_heads
    bb += (depth - n_global) * 2 * s_pad * window * num_heads * head_dim * 2
    bb += n_global * 2 * s * grid * num_heads * head_dim * 2
    # neck: 1x1 D->256 + 3x3 256->256
    bb += s * d * out_chans * 2 + s * 9 * out_chans * out_chans * 2

    # detector on the 2x-upsampled grid
    s_up = (2 * grid) ** 2
    hd = s_up * out_chans * emb_dim * 2  # input_proj 1x1
    hd += s_up * emb_dim * template_cap * template_cap * 2  # depthwise xcorr
    dec_ch = 2 * emb_dim if fusion else emb_dim
    hd += 2 * decoder_layers * s_up * 9 * dec_ch * dec_ch * 2  # 2 stacks
    hd += s_up * dec_ch * 5 * 2  # objectness + ltrb heads

    fl = {"full": bb + hd, "backbone": bb, "heads": hd}[part]
    return fl / 1e12


#: advertised peaks per device kind: (dense bf16 TFLOP/s, HBM GB/s).
#: Substring-matched against ``device.device_kind``. An accelerator whose
#: kind has no row is an error (``platform_peak`` raises), not a default.
PLATFORM_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v6 lite": (918.0, 1640.0),
}

#: the CPU row, and only the CPU's: what the CPU-backend tests divide by
#: so that their MFU plumbing stays finite — a few-core AVX host ballpark,
#: labeled ``peak_source: "nominal"``. Never a device metric.
NOMINAL_PEAK: Tuple[float, float] = (0.5, 50.0)


def platform_peak() -> dict:
    """Peak FLOP/s + bandwidth of the current default backend, with
    provenance: "table" = a device kind of ``PLATFORM_PEAKS``, "nominal" =
    the CPU backend's labeled stand-in. An accelerator of a kind the table
    does not hold raises — a made-up peak would turn into a made-up MFU."""
    import jax

    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    for name, (tf, gbps) in PLATFORM_PEAKS.items():
        if name.lower() in device_kind.lower():
            return {"backend": backend, "device_kind": device_kind,
                    "peak_tflops": tf, "peak_gbps": gbps,
                    "peak_source": "table"}
    if backend != "cpu":
        raise LookupError(
            f"no peak for device kind {device_kind!r} (backend "
            f"{backend!r}): add its row to devtime.PLATFORM_PEAKS"
        )
    return {"backend": backend, "device_kind": device_kind,
            "peak_tflops": NOMINAL_PEAK[0], "peak_gbps": NOMINAL_PEAK[1],
            "peak_source": "nominal"}


# ------------------------------------------------------- program table

_LOCK = threading.Lock()
#: (kind, key_repr) -> program entry; each entry holds per-shape-sig
#: timing sums plus the lazily computed cost record
_PROGRAMS: "Dict[Tuple[str, str], dict]" = {}


def reset() -> None:
    """Drop the attribution table — the drain-before-measure protocol."""
    with _LOCK:
        _PROGRAMS.clear()


def _resolved_items() -> list:
    """Every (entry, sig, rec) with its cost record resolved — one
    ``lower().compile().cost_analysis()`` per (program, shape), cached
    on the record. Called from :func:`totals` and :func:`mfu_report`
    only (report/heartbeat paths), never from the execution wrapper."""
    with _LOCK:
        items = [
            (entry, sig, rec)
            for entry in _PROGRAMS.values()
            for sig, rec in entry["sigs"].items()
        ]
    for entry, sig, rec in items:
        if rec.get("cost") is None:
            cost = _cost_for(entry, sig, rec)
            with _LOCK:
                rec["cost"] = cost
    return items


def totals() -> dict:
    """Running ``{"flops", "device_s"}`` across all measured calls —
    the health watch's MFU-drop input (``ServeEngine.health()`` calls
    this per heartbeat, so pending cost records resolve HERE too; a
    health pass is off the execution hot path by construction)."""
    flops = 0.0
    device_s = 0.0
    for _entry, _sig, rec in _resolved_items():
        device_s += rec["device_s"]
        cost = rec.get("cost")
        if cost and cost.get("flops"):
            flops += cost["flops"] * rec["calls"]
    return {"flops": flops, "device_s": device_s}


def _abstractify(args: tuple):
    """args -> ShapeDtypeStruct pytree for deferred ``lower()`` costing
    (keeps shapes, drops buffers — storing live args would pin every
    batch the program ever saw)."""
    import jax
    import numpy as np

    def to_sds(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        arr = np.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    return jax.tree.map(to_sds, args)


def _sig_of(args: tuple) -> tuple:
    """Cheap per-call shape signature over TOP-LEVEL array args (the
    params pytree has no .shape and is skipped — its shapes never vary
    per program)."""
    return tuple(
        (tuple(a.shape), str(a.dtype))
        for a in args if hasattr(a, "shape") and hasattr(a, "dtype")
    )


def track_devtime(fn, kind: str, key: Any, bucket: Optional[dict] = None,
                  devices: int = 1):
    """Wrap a compiled-program cache entry so every execution attributes
    its wall/dispatch/device seconds (flight recorder ON only; one bool
    check otherwise). The first call per (program, shape) is recorded as
    warmup — it pays trace + XLA compile (obs/compile.py owns that
    accounting) and must not pollute the steady-state device numbers.

    ``devices``: how many chips one execution of this program occupies
    (a mesh-sharded serve program spans its replica group / the full
    mesh). The MFU report divides by it — N chips spending ``device_s``
    wall on F flops achieve F/(N * device_s) per chip, and without the
    division a tensor-parallel program's per-chip MFU reads N×
    inflated."""
    key_repr = repr(key)
    bucket = dict(bucket or {})
    devices = max(int(devices), 1)

    def wrapped(*args, **kw):
        if not _flight.flight_enabled():
            return fn(*args, **kw)
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        t1 = time.perf_counter()
        try:
            import jax

            jax.block_until_ready(out)
        except Exception:
            # tracing (make_jaxpr over the wrapper) or exotic outputs:
            # attribution is best-effort, the call result is not
            return out
        t2 = time.perf_counter()
        _record(kind, key_repr, bucket, fn, args,
                dispatch_s=t1 - t0, device_s=t2 - t1, devices=devices)
        return out

    wrapped.__wrapped__ = fn
    return wrapped


def _record(kind: str, key_repr: str, bucket: dict, fn, args,
            dispatch_s: float, device_s: float,
            devices: int = 1) -> None:
    sig = _sig_of(args)
    # a WEAK reference to the program: the attribution table must never
    # pin a discarded Predictor's executables alive for process
    # lifetime (long-lived TMR_FLIGHT=1 server churning Predictors) —
    # a dead ref just means the cost record falls back to the analytic
    # model when it resolves after the program died
    try:
        fn_ref = weakref.ref(fn)
    except TypeError:  # un-weakref-able callable: hold it (rare)
        fn_ref = lambda fn=fn: fn  # noqa: E731
    with _LOCK:
        entry = _PROGRAMS.get((kind, key_repr))
        if entry is None:
            entry = {"kind": kind, "key": key_repr, "bucket": bucket,
                     "fn_ref": fn_ref, "devices": max(int(devices), 1),
                     "sigs": {}}
            _PROGRAMS[(kind, key_repr)] = entry
        rec = entry["sigs"].get(sig)
        if rec is None:
            rec = {"abstract": None, "calls": 0, "warmup_calls": 0,
                   "dispatch_s": 0.0, "device_s": 0.0, "wall_s": 0.0,
                   "warmup_wall_s": 0.0, "warmup_device_s": 0.0,
                   "cost": None}
            entry["sigs"][sig] = rec
            abstract_pending = True
        else:
            abstract_pending = rec["abstract"] is None
        first = rec["calls"] == 0 and rec["warmup_calls"] == 0
        if first:
            rec["warmup_calls"] += 1
            rec["warmup_wall_s"] += dispatch_s + device_s
            rec["warmup_device_s"] += device_s
        else:
            rec["calls"] += 1
            rec["dispatch_s"] += dispatch_s
            rec["device_s"] += device_s
            rec["wall_s"] += dispatch_s + device_s
    if abstract_pending:
        # abstractify OUTSIDE the lock (it walks the params pytree);
        # a racing double-compute stores the same value twice
        try:
            abstract = _abstractify(args)
        except Exception:
            abstract = ()
        with _LOCK:
            rec["abstract"] = abstract


def _analytic_cost(kind: str, bucket: dict, sig: tuple) -> Optional[dict]:
    """Fallback FLOPs from the analytic model. Needs the image (or
    feature) arg's shape out of the signature; returns None when the
    program shape cannot be recognized. Sharded serve kinds map onto
    their unsharded family — the program computes the same logical
    FLOPs, just spread over the replica group (the per-chip division
    happens in :func:`mfu_report`, not here)."""
    if kind == "single_sharded":
        kind = "single"
    elif kind == "multi_sharded":
        kind = "multi_batched"
    cap = int(bucket.get("capacity", 17) or 17)
    image = next(
        (shape for shape, _ in sig
         if len(shape) == 4 and shape[-1] == 3 and shape[1] == shape[2]),
        None,
    )
    if kind in ("single", "multi", "multi_batched") and image:
        b, s = int(image[0]), int(image[1])
        return {"flops": forward_tflops_per_image(
            s, template_cap=cap, part="full") * b * 1e12,
            "bytes": None, "source": "analytic"}
    if kind == "backbone" and image:
        b, s = int(image[0]), int(image[1])
        return {"flops": forward_tflops_per_image(
            s, part="backbone") * b * 1e12,
            "bytes": None, "source": "analytic"}
    if kind == "heads" and bucket.get("image_size"):
        feat = next((shape for shape, _ in sig if len(shape) == 4), None)
        if feat:
            return {"flops": forward_tflops_per_image(
                int(bucket["image_size"]), template_cap=cap,
                part="heads") * int(feat[0]) * 1e12,
                "bytes": None, "source": "analytic"}
    return None


def _xla_cost(fn, abstract) -> Optional[dict]:
    """FLOPs + bytes accessed from the compiled executable's own
    ``cost_analysis()`` (lower() retraces — trace cost only, the XLA
    compile itself is a compilation-cache hit)."""
    if not abstract:
        return None
    try:
        # unwrap the track_compile/track_devtime layers down to the jit
        # callable — stopping at the first .lower (a jit fn itself has a
        # __wrapped__: the plain python function, one level too deep)
        inner = fn
        while not hasattr(inner, "lower") and hasattr(inner,
                                                      "__wrapped__"):
            inner = inner.__wrapped__
        analysis = inner.lower(*abstract).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = analysis.get("flops")
        byts = analysis.get("bytes accessed")
        if flops and float(flops) > 0:
            return {"flops": float(flops),
                    "bytes": float(byts) if byts else None,
                    "source": "xla"}
    except Exception:
        pass
    return None


def _cost_for(entry: dict, sig: tuple, rec: dict) -> dict:
    fn = entry["fn_ref"]()
    cost = _xla_cost(fn, rec.get("abstract")) if fn is not None else None
    if cost is None:
        cost = _analytic_cost(entry["kind"], entry["bucket"], sig)
    if cost is None:
        cost = {"flops": None, "bytes": None, "source": "none"}
    return cost


def _sig_str(sig: tuple) -> List[str]:
    return [f"{'x'.join(map(str, shape))}:{dtype}" for shape, dtype in sig]


def _weight_stats(abstract) -> Optional[dict]:
    """Weight-tree bytes of a program from its stored abstract args: the
    FIRST argument of every Predictor program is the param tree, so its
    leaf bytes are the per-call HBM weight traffic floor. Under
    TMR_QUANT_STORAGE=int8 the quantized leaves arrive as int8 — the
    figure drops 4x for them, which is how an mfu_report shows the
    storage knob's bytes actually moved (the roofline's bytes-accessed
    figure from cost_analysis() moves with it). Returns
    {"weight_bytes", "int8_weight_bytes", "int8_weights"} or None when
    the program recorded no abstract args."""
    if not abstract:
        return None
    try:
        import jax
        import numpy as np

        leaves = jax.tree.leaves(abstract[0])
        total = 0
        int8 = 0
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(
                dtype
            ).itemsize
            total += nbytes
            if np.dtype(dtype) == np.int8:
                int8 += nbytes
        if total == 0:
            return None
        return {"weight_bytes": total, "int8_weight_bytes": int8,
                "int8_weights": int8 > 0}
    except Exception:
        return None


def mfu_report() -> dict:
    """Reduce the attribution table to one ``mfu_report/v1`` document.

    Cost records resolve lazily HERE (never on the execution path): one
    ``lower().compile().cost_analysis()`` per (program, shape), cached
    on the entry. A program observed only as warmup (single cold call)
    reports its warmup device seconds with ``warmup_only: true`` so its
    MFU is still finite rather than null."""
    platform = platform_peak()
    peak_flops = platform["peak_tflops"] * 1e12
    peak_bytes = platform["peak_gbps"] * 1e9
    ridge = peak_flops / peak_bytes  # flops/byte at the roofline knee
    programs: List[dict] = []
    total_flops = 0.0
    total_device = 0.0
    total_chip = 0.0  # device_s weighted by chips occupied (per-chip MFU)
    for entry, sig, rec in _resolved_items():
        cost = rec["cost"]
        devices = max(int(entry.get("devices", 1)), 1)
        warmup_only = rec["calls"] == 0
        calls = rec["warmup_calls"] if warmup_only else rec["calls"]
        # a warmup-only program reports its warmup window CONSISTENTLY
        # across all three fields — mixing warmup device_s with the
        # (zero) steady-state wall/dispatch accumulators would emit the
        # physically impossible wall < device
        if warmup_only:
            device_s = rec["warmup_device_s"]
            wall_s = rec["warmup_wall_s"]
            dispatch_s = max(wall_s - device_s, 0.0)
        else:
            device_s = rec["device_s"]
            wall_s = rec["wall_s"]
            dispatch_s = rec["dispatch_s"]
        flops = cost["flops"]
        achieved = (flops * calls / device_s
                    if flops and device_s > 0 else None)
        # per-CHIP MFU: a sharded program's flops spread over its
        # replica group, so the denominator is devices × peak — without
        # the division a tp-N program reads N× inflated (satellite pin:
        # tests/test_serve_mesh.py on the forced-8-device mesh)
        mfu = (achieved / (peak_flops * devices)
               if achieved is not None else None)
        intensity = (flops / cost["bytes"]
                     if flops and cost.get("bytes") else None)
        if intensity is None:
            bound = "unknown"
        else:
            bound = "compute" if intensity >= ridge else "memory"
        analytic = _analytic_cost(entry["kind"], entry["bucket"], sig)
        wstats = _weight_stats(rec.get("abstract"))
        prog = {
            "kind": entry["kind"],
            "key": entry["key"],
            "bucket": entry["bucket"],
            "devices": devices,
            "shapes": _sig_str(sig),
            "calls": rec["calls"],
            "warmup_calls": rec["warmup_calls"],
            "warmup_only": warmup_only,
            "dispatch_s": round(dispatch_s, 6),
            "device_s": round(device_s, 6),
            "wall_s": round(wall_s, 6),
            "flops_per_call": flops,
            "bytes_per_call": cost.get("bytes"),
            "cost_source": cost["source"],
            # param-tree bytes per call + whether int8 storage leaves
            # reached this program (TMR_QUANT_STORAGE accounting)
            "weight_bytes": wstats["weight_bytes"] if wstats else None,
            "int8_weights": wstats["int8_weights"] if wstats else False,
            "analytic_flops_per_call": (
                analytic["flops"] if analytic else None
            ),
            "achieved_tflops": (
                round(achieved / 1e12, 6) if achieved is not None else None
            ),
            "mfu": round(mfu, 6) if mfu is not None else None,
            "arithmetic_intensity": (
                round(intensity, 3) if intensity is not None else None
            ),
            "ridge_intensity": round(ridge, 3),
            "bound": bound,
        }
        programs.append(prog)
        if flops and device_s > 0:
            total_flops += flops * calls
            total_device += device_s
            total_chip += device_s * devices
    total_achieved = (total_flops / total_device
                      if total_device > 0 else None)
    # per-chip totals MFU over chip-seconds (multi-chip programs weigh
    # their group size; identical to the old number when every program
    # is single-device)
    total_chip_achieved = (total_flops / total_chip
                           if total_chip > 0 else None)
    return {
        "schema": MFU_REPORT_SCHEMA,
        "platform": platform,
        "programs": sorted(
            programs, key=lambda p: -(p["device_s"] or 0.0)
        ),
        "totals": {
            "device_s": round(total_device, 6),
            "flops": total_flops,
            "achieved_tflops": (
                round(total_achieved / 1e12, 6)
                if total_achieved is not None else None
            ),
            "mfu": (
                round(total_chip_achieved / peak_flops, 6)
                if total_chip_achieved is not None else None
            ),
        },
    }


# ----------------------------------------------- explicit stage harness


def attribute_call(fn, *args, iters: int = 3, rtt: float = 0.0) -> dict:
    """Blocking dispatch/device split of ``fn(*args)`` for explicit
    stage harnesses (scripts/profile_breakdown.py): one warmup call,
    then ``iters`` measured calls, medians reported with the measured
    round-trip floor subtracted from the device share (the same
    correction the chained harness applies)."""
    import jax

    jax.block_until_ready(fn(*args))  # warmup/compile outside the window
    dispatch: List[float] = []
    device: List[float] = []
    for _ in range(max(int(iters), 1)):
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        dispatch.append(t1 - t0)
        device.append(t2 - t1)
    dispatch.sort()
    device.sort()
    mid = len(dispatch) // 2
    return {
        "dispatch_s": dispatch[mid],
        "device_s": max(device[mid] - rtt, 0.0),
        "wall_s": dispatch[mid] + device[mid],
        "iters": len(dispatch),
    }


def measure_once(fn, *args):
    """One SYNCHRONOUS execution of ``fn(*args)``: returns
    ``(out, wall_s)`` with ``block_until_ready`` inside the window.

    The live-autotune shadow-measurement primitive (autotune_live):
    unlike :func:`attribute_call` it pays no warmup iteration — a
    shadow sample is a single production-shaped execution whose whole
    cost counts against the tuner's device-seconds budget, compile
    included (a candidate's first sample IS its warmup, and the tuner
    compares like for like because the incumbent runs through the same
    path)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0

"""Streaming-video benchmark: temporal feature reuse vs the
frame-independent path (tmr_tpu/serve/streams.py).

Drives a StreamRouter over a synthetic BURSTY video workload — S
streams, each a static scene that cuts to new content mid-stream —
and prints ONE ``stream_report/v1`` JSON document (schema + validator
in tmr_tpu/diagnostics.py):

- **Frame-independent baseline** — every frame through
  ``ServeEngine.submit`` the way frame-independent requests pay: one
  fused program (backbone included) per frame. Caches are OFF
  (``feature_cache=0, exemplar_cache=0``) so repeated frames recompute
  honestly and the baseline stays the bitwise-deterministic fused path.
- **Stream phase** — the same frames through
  ``StreamRouter.submit_stream`` with reuse ON: unchanged frames elect
  the heads-only program over the session anchor's cached features and
  SKIP the backbone. Checks, all mechanical:

  * ``backbone_amortized`` — backbone-bearing executions ≪ frames,
    proven from the flight recorder's per-program call table (the
    ``TMR_FLIGHT`` devtime witness, enabled in-process): at most the
    fused pass per non-reused frame plus one feature fill per anchor.
  * ``speedup_ok`` — stream frames/s >= 1.5x the frame-independent
    baseline on the same frames.
  * ``changed_frames_exact`` — every frame the delta check sent down
    the full path ("first"/"changed") is BITWISE-identical to its
    baseline result: reuse off the reuse path costs nothing.
  * ``reuse_labeled`` — every reused frame's result carries
    ``degrade_steps: ["temporal_reuse"]`` and no full-path frame does.
  * ``cross_stream_isolated`` — streams carry DISTINCT content; a
    reused result bitwise-matching another stream's results would be
    cross-stream feature leakage. Zero tolerated.

Usage:  python scripts/stream_bench.py [--tiny] [--out FILE]
        [--streams S] [--frames F] [--delta D] [--seed N]

``--tiny`` (or TMR_BENCH_TINY=1) shrinks geometry so the whole bench
smoke-runs on CPU (tier-1 runs it under JAX_PLATFORMS=cpu); real
numbers use the deployment geometry. Same one-JSON-line contract as
bench.py via the shared bench_guard; ``bench_trend.py --stream``
rc-gates the emitted report (fail closed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: detection fields compared bitwise between the stream phase's
#: full-path frames and the frame-independent baseline
_FIELDS = ("boxes", "scores", "refs", "valid")

#: the one exemplar every stream carries (streams differ by CONTENT;
#: a shared box keeps one capacity bucket → one fused + one heads
#: program for the whole bench)
_BOX = np.asarray([[0.3, 0.3, 0.5, 0.5]], np.float32)


def _progress(msg: str) -> None:
    print(f"[stream_bench] {msg}", file=sys.stderr, flush=True)


def _make_workload(size: int, n_streams: int, n_frames: int, seed: int):
    """(frames, verdicts): the bursty video shape. Each stream is a
    static random scene repeated EXACTLY (delta 0.0 → reuse) that cuts
    to fresh content at the midpoint burst (full-frame content swap —
    block-mean delta far above any sane threshold → "changed"). The
    expected verdict per (stream, frame) rides along so the report's
    label/exactness checks compare against the workload's ground
    truth, not the router's own opinion of itself."""
    frames: dict = {}
    verdicts: dict = {}
    burst_at = n_frames // 2
    for s in range(n_streams):
        rng = np.random.default_rng(1000 * (seed + 1) + s)
        anchor = rng.standard_normal((size, size, 3)).astype(np.float32)
        for f in range(n_frames):
            if f == 0:
                verdicts[(s, f)] = "first"
            elif f == burst_at:
                # the cut: entirely new content becomes the new anchor
                anchor = rng.standard_normal(
                    (size, size, 3)
                ).astype(np.float32)
                verdicts[(s, f)] = "changed"
            else:
                verdicts[(s, f)] = "reused"
            frames[(s, f)] = anchor
    return frames, verdicts


def _program_calls(kinds) -> dict:
    """Executed-call counts per devtime program kind (warmup calls
    included — an execution is an execution)."""
    from tmr_tpu import obs

    out: dict = {}
    for prog in obs.mfu_report()["programs"]:
        if prog["kind"] in kinds:
            out[prog["kind"]] = out.get(prog["kind"], 0) \
                + int(prog["calls"]) + int(prog["warmup_calls"])
    return out


def _np(result: dict) -> dict:
    return {k: np.asarray(result[k]) for k in _FIELDS if k in result}


def _same(a: dict, b: dict) -> bool:
    return all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
        for k in _FIELDS
    )


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    ap.add_argument("--streams", type=int, default=3,
                    help="concurrent stream sessions")
    ap.add_argument("--frames", type=int, default=10,
                    help="frames per stream (one mid-stream burst)")
    ap.add_argument("--delta", type=float, default=0.02,
                    help="block-mean reuse threshold (TMR_STREAM_DELTA "
                         "default)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    tiny = args.tiny or os.environ.get("TMR_BENCH_TINY", "") not in (
        "", "0", "false"
    )
    size = int(os.environ.get("TMR_BENCH_SIZE", 128 if tiny else 1024))
    dtype = "float32" if tiny else "bfloat16"

    import jax

    from tmr_tpu import obs
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        STREAM_REPORT_SCHEMA,
        validate_stream_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import ServeEngine, StreamRouter

    n_streams, n_frames = int(args.streams), int(args.frames)
    total = n_streams * n_frames
    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny} "
              f"streams={n_streams} frames/stream={n_frames}")
    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)

    frames, verdicts = _make_workload(size, n_streams, n_frames,
                                      args.seed)
    wall0 = time.perf_counter()
    # the flight recorder is the backbone-amortization witness: every
    # program execution lands in the devtime call table
    obs.flight_configure(enabled=True)

    # ONE engine for both phases: caches off, so the baseline phase
    # leaves nothing behind for the stream phase to feed on, and both
    # run the byte-identical B=1 programs
    engine = ServeEngine(pred, batch=1, max_wait_ms=5, feature_cache=0,
                         exemplar_cache=0)
    router = StreamRouter(engine, reuse=True, delta=args.delta)

    # ---- warmup: compile the fused program (anchor frame), the local
    # backbone fill, and the heads-only program (reused frame) outside
    # every timed window, on a throwaway stream
    _progress("warmup compiles (fused + backbone fill + heads)")
    warm = np.random.default_rng(991).standard_normal(
        (size, size, 3)
    ).astype(np.float32)
    router.submit_stream("warm", warm, _BOX).result()
    router.submit_stream("warm", warm, _BOX).result()
    router.evict("warm")
    counters0 = router.counters()

    # ---- frame-independent baseline: every frame pays the fused pass
    _progress("phase frame_independent baseline")
    from tmr_tpu.obs import devtime

    devtime.reset()
    base: dict = {}
    t0 = time.perf_counter()
    for f in range(n_frames):
        for s in range(n_streams):
            base[(s, f)] = _np(
                engine.submit(frames[(s, f)], _BOX).result()
            )
    base_dt = time.perf_counter() - t0
    base_fps = total / base_dt
    base_programs = _program_calls(("single", "backbone", "heads",
                                    "multi"))
    _progress(f"baseline: {base_fps:.3f} frames/s "
              f"(by_program {base_programs})")

    # ---- stream phase: the same frames through the router, streams
    # interleaved round-robin the way live sessions arrive
    _progress("phase stream (reuse on)")
    devtime.reset()
    stream: dict = {}
    t0 = time.perf_counter()
    for f in range(n_frames):
        for s in range(n_streams):
            stream[(s, f)] = router.submit_stream(
                f"s{s}", frames[(s, f)], _BOX
            )
    results = {key: fut.result() for key, fut in stream.items()}
    stream_dt = time.perf_counter() - t0
    stream_fps = total / stream_dt
    by_program = _program_calls(("single", "backbone", "heads", "multi"))
    # backbone-bearing executions: the fused program runs the backbone
    # inline; "backbone" is the router's per-anchor feature fill
    backbone_execs = by_program.get("single", 0) \
        + by_program.get("multi", 0) + by_program.get("backbone", 0)
    counters = {
        k: v - counters0.get(k, 0) for k, v in router.counters().items()
    }
    _progress(f"stream: {stream_fps:.3f} frames/s "
              f"({stream_fps / base_fps:.2f}x baseline), backbone "
              f"executions {backbone_execs} for {total} frames "
              f"(by_program {by_program})")

    # ---- label + exactness + isolation audit against the workload's
    # ground-truth verdicts
    n_reused = sum(1 for v in verdicts.values() if v == "reused")
    n_changed = sum(1 for v in verdicts.values() if v == "changed")
    n_first = sum(1 for v in verdicts.values() if v == "first")
    mismatches = 0
    checked = 0
    label_errors = 0
    cross_hits = 0
    for key, verdict in verdicts.items():
        got = results[key]
        labeled = "temporal_reuse" in got.get("degrade_steps", ())
        if verdict == "reused":
            if not labeled:
                label_errors += 1
            # distinct per-stream content: this result matching ANY
            # other stream's baseline would be cross-stream leakage
            s = key[0]
            for (s2, f2), want in base.items():
                if s2 != s and _same(got, want):
                    cross_hits += 1
                    break
        else:
            if labeled:
                label_errors += 1
            checked += 1
            if not _same(got, base[key]):
                mismatches += 1
    _progress(f"exactness: {mismatches} mismatching full-path frames "
              f"of {checked}; {label_errors} label errors; "
              f"{cross_hits} cross-stream hits; router {counters}")

    report = {
        "schema": STREAM_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "streams": n_streams,
            "frames_per_stream": n_frames,
            "frames": total,
            "delta": float(args.delta),
            "seed": int(args.seed),
            "dtype": dtype,
        },
        "throughput": {
            "stream_frames_per_sec": round(stream_fps, 3),
            "independent_frames_per_sec": round(base_fps, 3),
            "speedup": round(stream_fps / base_fps, 3),
        },
        "backbone": {
            "frames": total,
            "executions": int(backbone_execs),
            "baseline_by_program": base_programs,
            "by_program": by_program,
        },
        "reuse": {
            "reused_frames": int(counters.get("reused_frames", 0)),
            "changed_frames": int(counters.get("changed_frames", 0)),
            "first_frames": int(counters.get("first_frames", 0)),
            "expected": {"reused": n_reused, "changed": n_changed,
                         "first": n_first},
        },
        "exactness": {
            "changed_frames_checked": int(checked),
            "mismatches": int(mismatches),
            "label_errors": int(label_errors),
        },
        "isolation": {
            "cross_stream_hits": int(cross_hits),
            "sessions": len(router.sessions()),
        },
        "counters": router.stats(),
        "checks": {
            # ≪ frames, mechanically: at most the fused pass per
            # non-reused frame plus one feature fill per anchor
            "backbone_amortized": bool(
                backbone_execs <= 2 * (n_first + n_changed)
                and backbone_execs < total
            ),
            "speedup_ok": bool(stream_fps >= 1.5 * base_fps),
            "changed_frames_exact": bool(
                mismatches == 0 and checked == n_first + n_changed
            ),
            "cross_stream_isolated": bool(cross_hits == 0),
            "reuse_labeled": bool(label_errors == 0 and n_reused > 0),
            "verdicts_as_expected": bool(
                counters.get("reused_frames", 0) == n_reused
                and counters.get("changed_frames", 0) == n_changed
                and counters.get("first_frames", 0) == n_first
            ),
        },
    }
    report["wall_s"] = round(time.perf_counter() - wall0, 1)
    problems = validate_stream_report(report)
    if problems:  # self-check: the emitted document must validate
        report["validator_problems"] = problems
    engine.close()

    cancel_watchdog()  # before the success print: no success-then-watchdog
    line = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


def main(argv=None) -> int:
    """One stream_report/v1 JSON line on stdout, success or not: the
    shared bench_guard (same watchdog bench.py runs under) funnels
    wedges and crashes into a contractual error record."""
    from tmr_tpu.diagnostics import STREAM_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": STREAM_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

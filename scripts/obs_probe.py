"""Telemetry probe: proves the obs subsystem end to end and prints ONE
``trace_report/v1`` JSON document (schema + validator in
tmr_tpu/diagnostics.py).

What it runs and what it asserts:

- **serve pipeline tracing** — a tiny ServeEngine workload with
  ``TMR_TRACE`` off (the overhead baseline) and then on: every request
  must show all seven pipeline stages as spans (submit -> queue wait ->
  batch assembly -> staging -> execute -> postprocess -> resolution)
  carrying one consistent per-request trace ID.
- **compile-event accounting** — the workload's program compiles must
  each record an event (kind, compile key, wall seconds, cold vs
  key-change) in the process registry.
- **map-phase tracing** — a 3-shard synthetic extraction with one
  injected transient fault: attempt/backoff spans, retry counters, and a
  ``map_report/v1`` document carrying the registry snapshot.
- **overhead** — the disabled-mode cost of span enter/exit, measured in
  ns and projected against the workload's per-request latency; the check
  requires < 1% (the "truly zero-cost when TMR_TRACE=0" contract).
- **export** — the Chrome trace JSON (Perfetto-loadable) must round-trip
  ``json.loads`` with every span present.

What it does not run, because the program reports it for itself in every
process: on the offline path (``Predictor.__call__`` ->
``detections_to_numpy``) a batch whose answer or whose copy home took 1.5
times the smallest its program has shown is one ``[WARNING] predict: batch
<id> <program>(<bucket>) stalled ...`` line on standard error, one count of
``predict.batches_stalled`` in the registry this probe's reports attach, and
``stalled`` / ``excess_s`` on that batch's ``predict.fetch`` span
(QUICKSTART_RUN.md "Observability").

Usage:  python scripts/obs_probe.py [--tiny] [--out FILE] [--trace-out FILE]

``--tiny`` (or TMR_BENCH_TINY=1) runs the CPU smoke geometry tier-1 uses
(tests/test_obs_probe.py); real numbers use the deployment geometry.
Same one-JSON-line contract as bench.py via the shared bench_guard.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import tarfile
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _progress(msg: str) -> None:
    print(f"[obs_probe] {msg}", file=sys.stderr, flush=True)


def _percentiles_ms(durs_s) -> dict:
    if not durs_s:
        return {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
    arr = np.asarray(durs_s) * 1000.0
    return {
        "p50_ms": round(float(np.percentile(arr, 50)), 3),
        "p95_ms": round(float(np.percentile(arr, 95)), 3),
        "p99_ms": round(float(np.percentile(arr, 99)), 3),
    }


def _stage_table(spans, prefix: str) -> dict:
    """{stage name: {count, p50/p95/p99 ms}} over span durations."""
    by_name: dict = {}
    for rec in spans:
        if rec["name"].startswith(prefix):
            by_name.setdefault(rec["name"], []).append(rec["dur"])
    return {
        name: {"count": len(durs), **_percentiles_ms(durs)}
        for name, durs in sorted(by_name.items())
    }


def _measure_disabled_span_ns(iters: int = 50_000) -> float:
    """Amortized enter/exit cost of a span with TMR_TRACE=0 (ns)."""
    from tmr_tpu import obs

    assert not obs.tracing_enabled()
    span = obs.span
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            with span("overhead_probe"):
                pass
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e9


def _make_tar(dirpath: str, name: str, n_images: int, seed: int) -> str:
    from PIL import Image

    rng = np.random.default_rng(seed)
    path = os.path.join(dirpath, name)
    with tarfile.open(path, "w") as tar:
        for i in range(n_images):
            img = Image.fromarray(
                rng.integers(0, 255, (24, 24, 3), dtype=np.uint8)
            )
            buf = io.BytesIO()
            img.save(buf, format="PNG")
            data = buf.getvalue()
            info = tarfile.TarInfo(f"img_{i}.png")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
    return path


def _serve_closed_loop(engine, requests):
    """Submit all, await all; returns elapsed seconds."""
    t0 = time.perf_counter()
    futs = [engine.submit(img, ex) for img, ex in requests]
    for f in futs:
        f.result(timeout=600)
    return time.perf_counter() - t0


def _run_map_workload(size: int) -> dict:
    """3 synthetic shards + one injected transient fault through
    run_stream; returns the map_report/v1 document (metrics attached)."""
    import jax

    from tmr_tpu.parallel.mapreduce import (
        MapReport,
        RetryPolicy,
        feature_stats,
        run_stream,
    )
    from tmr_tpu.utils import faults

    @jax.jit
    def encode(images):  # stand-in encoder: the probe measures telemetry,
        feats = images[:, ::4, ::4, :] - 0.5  # not the model
        return feats, feature_stats(feats)

    with tempfile.TemporaryDirectory(prefix="obs_probe_") as work:
        paths = [
            _make_tar(work, name, n, seed=i)
            for i, (name, n) in enumerate(
                (("Easy_0.tar", 3), ("Normal_0.tar", 2), ("Hard_0.tar", 2))
            )
        ]
        report = MapReport()
        # one transient fault: shard 1's first load attempt dies, the
        # retry succeeds — exercising the attempt/backoff spans and the
        # map.retries counter deterministically
        faults.configure("tar.open:shard=1:attempts=1:raise=OSError")
        try:
            run_stream(
                paths, encode, batch_size=2, image_size=size,
                feeder_threads=2,
                retry=RetryPolicy(max_attempts=3, shard_timeout=5.0,
                                  backoff_base=0.01, backoff_jitter=0.0),
                report=report,
            )
        finally:
            faults.clear()
    return report.document()


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    ap.add_argument("--trace-out", default=None,
                    help="write the Chrome trace JSON (Perfetto) here")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=None)
    args = ap.parse_args(argv)

    tiny = args.tiny or os.environ.get("TMR_BENCH_TINY", "") not in (
        "", "0", "false"
    )
    size = int(os.environ.get("TMR_BENCH_SIZE", 128 if tiny else 1024))
    dtype = "float32" if tiny else "bfloat16"
    n_req = args.requests or (2 * args.batch + 2)

    import jax

    from tmr_tpu import obs
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        TRACE_REPORT_SCHEMA,
        TRACE_SERVE_STAGES,
        validate_map_report,
        validate_trace_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import ServeEngine

    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny}")

    # ---- disabled-mode overhead first, before anything enables tracing
    obs.configure(enabled=False)
    disabled_ns = _measure_disabled_span_ns()
    _progress(f"disabled span enter/exit: {disabled_ns:.0f} ns")

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)

    ex = np.asarray([[0.45, 0.45, 0.53, 0.55]], np.float32)

    def _requests(n, seed):
        r = np.random.default_rng(seed)
        return [(r.standard_normal((size, size, 3)).astype(np.float32), ex)
                for _ in range(n)]

    # ---- untraced baseline: compiles happen here (recording compile
    # events), and the per-request latency anchors the overhead check.
    # caches off: every request must ride the full pipeline.
    _progress("serve baseline (TMR_TRACE=0; warmup + timed pass)")
    with ServeEngine(pred, batch=args.batch, max_wait_ms=10,
                     exemplar_cache=0, feature_cache=0) as engine:
        _serve_closed_loop(engine, _requests(n_req, seed=1))  # warmup
        base_s = _serve_closed_loop(engine, _requests(n_req, seed=2))
    base_req_ms = base_s / n_req * 1000.0

    # ---- traced run: same workload shape, tracing on, fresh engine
    _progress("serve traced run (TMR_TRACE=1)")
    obs.configure(enabled=True)
    obs.clear()
    with ServeEngine(pred, batch=args.batch, max_wait_ms=10,
                     exemplar_cache=0, feature_cache=0) as engine:
        traced_s = _serve_closed_loop(engine, _requests(n_req, seed=3))
        serve_counters = engine.counters
        serve_metrics = engine.metrics_snapshot()
    # the per-request view: the always-on once-a-batch records of the
    # four batch stages (scope "batch") would count each stage twice
    serve_spans = [r for r in obs.spans() if r["scope"] == "request"]

    # per-request completeness: every stage name present under one trace id
    by_trace: dict = {}
    for rec in serve_spans:
        if rec["name"].startswith("serve.") and rec["trace"]:
            by_trace.setdefault(rec["trace"], set()).add(rec["name"])
    complete = [t for t, names in by_trace.items()
                if set(TRACE_SERVE_STAGES) <= names]
    _progress(
        f"traced: {len(serve_spans)} spans, {len(by_trace)} request traces, "
        f"{len(complete)} with all {len(TRACE_SERVE_STAGES)} stages"
    )

    # ---- map workload (still traced)
    _progress("map workload (3 shards, 1 injected transient fault)")
    map_doc = _run_map_workload(64)
    map_spans = [r for r in obs.spans() if r["name"].startswith("map.")]
    obs.configure(enabled=False)

    # ---- export round-trip
    chrome = obs.chrome_trace()
    chrome_line = json.dumps(chrome)
    reparsed = json.loads(chrome_line)
    n_events = len([e for e in reparsed["traceEvents"] if e["ph"] == "X"])
    roundtrip_ok = n_events == len(obs.spans())
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            f.write(chrome_line)

    events = obs.compile_events()
    overhead_pct = (
        disabled_ns * (len(TRACE_SERVE_STAGES) + 1)
        / (base_req_ms * 1e6) * 100.0
    )
    enabled_pct = (traced_s - base_s) / base_s * 100.0

    report = {
        "schema": TRACE_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "batch": args.batch,
            "requests": n_req,
            "trace_ring": int(os.environ.get("TMR_TRACE_RING", "8192")
                              or 8192),
        },
        "serve": {
            "stages": _stage_table(serve_spans, "serve."),
            "requests": n_req,
            "request_traces": len(by_trace),
            "complete_request_traces": len(complete),
            "counters": serve_counters,
            "metrics": serve_metrics,
        },
        "map": {
            "stages": _stage_table(map_spans, "map."),
            "report_totals": map_doc["totals"],
            "report_valid": validate_map_report(map_doc) == [],
        },
        "compile_events": events,
        "metrics": obs.get_registry().snapshot(),
        "overhead": {
            "disabled_ns_per_span": round(disabled_ns, 1),
            "span_sites_per_request": len(TRACE_SERVE_STAGES) + 1,
            "baseline_request_ms": round(base_req_ms, 3),
            "overhead_disabled_pct": round(overhead_pct, 6),
            "enabled_overhead_pct": round(enabled_pct, 2),
        },
        "dropped_spans": obs.dropped_spans(),
    }
    report["checks"] = {
        "stages_complete": bool(len(complete) >= 1),
        "compile_event_recorded": bool(
            any(e.get("key") for e in events)
        ),
        "map_retry_observed": bool(
            report["metrics"]["counters"].get("map.retries", 0) >= 1
        ),
        "trace_roundtrip": bool(roundtrip_ok),
        "overhead_ok": bool(overhead_pct < 1.0),
    }
    problems = validate_trace_report(report)
    if problems:  # self-check: the emitted document must validate
        report["validator_problems"] = problems

    cancel_watchdog()  # before the success print: no success-then-watchdog
    line = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


def main(argv=None) -> int:
    """One trace_report/v1 JSON line on stdout, success or not: the shared
    bench_guard (same watchdog bench.py runs under) funnels wedges and
    crashes into a contractual error record."""
    from tmr_tpu.diagnostics import TRACE_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": TRACE_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

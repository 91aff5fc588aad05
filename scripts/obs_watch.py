"""Flight-recorder probe: proves the performance-accounting layer end to
end and prints ONE ``flight_report/v1`` JSON document (schema + validator
in tmr_tpu/diagnostics.py).

What it runs and what it asserts:

- **device-time attribution + MFU** — a tiny ServeEngine workload with
  ``TMR_FLIGHT`` off (the overhead baseline) and then on: every executed
  program must appear in ``mfu_report/v1`` with finite per-program MFU,
  a roofline classification, and analytic FLOPs agreeing with the
  compiled program's own ``cost_analysis()`` within the
  PERF.md-documented 1.17x envelope.
- **health introspection** — ``ServeEngine.health()`` must validate as
  ``health_report/v1``, and the heartbeat writer's JSONL file must
  round-trip (every appended line re-validates).
- **anomaly detection** — an injected recompile storm (key-change
  compile events over threshold) and a queue-saturation burst must each
  fire EXACTLY their one anomaly, with structured gate_refused-style
  causes; a calm pass must fire none.
- **overhead** — the disabled-mode cost of the flight layer's per-site
  bool check, projected against the workload's per-request latency; the
  check requires < 1% (the TMR_FLIGHT=0 zero-cost contract, same shape
  as PR 4's span pin).

Usage:  python scripts/obs_watch.py [--tiny] [--out FILE]

``--tiny`` (or TMR_BENCH_TINY=1) runs the CPU smoke geometry tier-1
uses (tests/test_obs_watch.py); real numbers use the deployment
geometry. Same one-JSON-line contract as bench.py via the shared
bench_guard.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: flight-layer touch points on one request's path: the devtime wrapper
#: at program execution, the engine's _finish record guard, and the
#: mapreduce-style per-summary guard — the sites the disabled bool
#: check is paid at
_FLIGHT_SITES_PER_REQUEST = 3


def _progress(msg: str) -> None:
    print(f"[obs_watch] {msg}", file=sys.stderr, flush=True)


def _measure_disabled_check_ns(iters: int = 50_000) -> float:
    """Amortized cost of one flight-disabled instrumented call (the
    track_devtime wrapper around a trivial callable), in ns."""
    from tmr_tpu.obs import devtime, flight

    assert not flight.flight_enabled()
    wrapped = devtime.track_devtime(lambda: 0, "probe", ("overhead",))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            wrapped()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e9


def _serve_closed_loop(engine, requests):
    t0 = time.perf_counter()
    futs = [engine.submit(img, ex) for img, ex in requests]
    for f in futs:
        f.result(timeout=600)
    return time.perf_counter() - t0


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
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
        FLIGHT_REPORT_SCHEMA,
        validate_flight_report,
        validate_health_report,
        validate_mfu_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.obs import devtime, flight
    from tmr_tpu.serve import ServeEngine

    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny}")

    # ---- disabled-mode overhead first, before anything enables flight
    flight.configure(enabled=False)
    disabled_ns = _measure_disabled_check_ns()
    _progress(f"disabled flight check: {disabled_ns:.0f} ns")

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

    # ---- baseline: flight OFF, per-request latency anchors the
    # overhead check; compiles happen here. caches off: every request
    # must ride the full pipeline.
    _progress("serve baseline (TMR_FLIGHT=0; warmup + timed pass)")
    with ServeEngine(pred, batch=args.batch, max_wait_ms=10,
                     exemplar_cache=0, feature_cache=0) as engine:
        _serve_closed_loop(engine, _requests(n_req, seed=1))  # warmup
        base_s = _serve_closed_loop(engine, _requests(n_req, seed=2))
    base_req_ms = base_s / n_req * 1000.0
    overhead_pct = (
        disabled_ns * _FLIGHT_SITES_PER_REQUEST
        / (base_req_ms * 1e6) * 100.0
    )

    # ---- flight ON: attribution + health + heartbeat on a fresh engine
    _progress("flight run (TMR_FLIGHT=1)")
    flight.configure(enabled=True)
    devtime.reset()
    flight.get_recorder().clear()
    hb_path = (args.out or "obs_watch") + ".heartbeat.jsonl"
    try:
        os.remove(hb_path)
    except OSError:
        pass
    with ServeEngine(pred, batch=args.batch, max_wait_ms=10,
                     exemplar_cache=0, feature_cache=0) as engine:
        engine.start_heartbeat(hb_path, interval_s=30.0)
        flight_s = _serve_closed_loop(engine, _requests(n_req, seed=3))
        health = engine.health()
    # engine.close() stopped the heartbeat and appended its final beat
    health_problems = validate_health_report(health)
    hb_lines = []
    with open(hb_path) as f:
        for line in f:
            if line.strip():
                hb_lines.append(json.loads(line))
    hb_ok = len(hb_lines) >= 2 and all(
        validate_health_report(doc) == [] for doc in hb_lines
    )
    if not args.out:
        os.remove(hb_path)
    ring = flight.get_recorder().snapshot()
    req_records = [r for r in ring if r["kind"] == "serve.request"]

    _progress("mfu_report (cost_analysis per program)")
    mfu = devtime.mfu_report()
    mfu_problems = validate_mfu_report(mfu)
    measured = [p for p in mfu["programs"]
                if p["calls"] > 0 or p["warmup_only"]]
    mfu_finite = bool(measured) and all(
        p["mfu"] is not None and np.isfinite(p["mfu"]) and p["mfu"] > 0
        for p in measured
    )
    # analytic vs cost_analysis envelope over the fused single programs
    # (the modeled family; PERF.md documents the 1.17x envelope)
    ratios = [
        max(p["flops_per_call"], p["analytic_flops_per_call"])
        / min(p["flops_per_call"], p["analytic_flops_per_call"])
        for p in mfu["programs"]
        if p["kind"] == "single" and p["cost_source"] == "xla"
        and p["analytic_flops_per_call"]
    ]
    envelope_max = max(ratios) if ratios else None
    envelope_ok = bool(ratios) and envelope_max <= 1.17
    flight.configure(enabled=False)

    # ---- anomaly detection: a calm pass, then an injected recompile
    # storm and a queue-saturation burst against tight thresholds —
    # each must fire EXACTLY its one structured anomaly
    _progress("anomaly injection (storm + queue burst)")
    watch = obs.HealthWatch(recompile_storm_threshold=3,
                            queue_depth_threshold=8)
    reg = obs.MetricsRegistry()
    calm = watch.observe(reg.snapshot(), compile_events=(), pending=0)
    t0 = time.perf_counter()
    storm_events = [
        obs.record_compile_event("storm_probe", ("key", i), t0,
                                 t0 + 0.05)
        for i in range(4)
    ]  # first is cold, the 3 after are key-change: exactly threshold
    storm = watch.observe(reg.snapshot(), compile_events=storm_events,
                          pending=0)
    queue = watch.observe(reg.snapshot(), compile_events=(), pending=32)
    storm_exact = [a["anomaly"] for a in storm] == ["recompile_storm"]
    queue_exact = [a["anomaly"] for a in queue] == ["queue_saturation"]

    report = {
        "schema": FLIGHT_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "batch": args.batch,
            "requests": n_req,
            "flight_ring": flight.get_recorder().capacity,
        },
        "mfu": mfu,
        "health": health,
        "heartbeat": {
            "path": hb_path if args.out else None,
            "beats": len(hb_lines),
            "interval_s": 30.0,
        },
        "ring": {
            "records": len(ring),
            "serve_requests": len(req_records),
            "dropped": flight.get_recorder().dropped(),
        },
        "anomalies": {
            "calm": calm,
            "recompile_storm": storm,
            "queue_saturation": queue,
        },
        "overhead": {
            "disabled_ns_per_check": round(disabled_ns, 1),
            "check_sites_per_request": _FLIGHT_SITES_PER_REQUEST,
            "baseline_request_ms": round(base_req_ms, 3),
            "overhead_disabled_pct": round(overhead_pct, 6),
            "enabled_wall_s": round(flight_s, 3),
            "baseline_wall_s": round(base_s, 3),
        },
    }
    report["checks"] = {
        "mfu_valid": mfu_problems == [],
        "mfu_finite": mfu_finite,
        "flops_envelope_ok": envelope_ok,
        "flops_envelope_max_ratio": (
            round(envelope_max, 4) if envelope_max else None
        ),
        "health_valid": health_problems == [],
        "heartbeat_roundtrip": bool(hb_ok),
        "ring_recorded": bool(len(req_records) >= n_req),
        "calm_quiet": calm == [],
        "storm_exact": bool(storm_exact),
        "queue_exact": bool(queue_exact),
        "overhead_ok": bool(overhead_pct < 1.0),
    }
    problems = validate_flight_report(report)
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
    """One flight_report/v1 JSON line on stdout, success or not: the
    shared bench_guard (same watchdog bench.py runs under) funnels
    wedges and crashes into a contractual error record."""
    from tmr_tpu.diagnostics import FLIGHT_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": FLIGHT_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

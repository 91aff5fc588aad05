"""Overload-robustness probe for the serving layer (tmr_tpu/serve).

The chaos_probe pattern applied to traffic instead of faults: drive
ServeEngine far past its measured capacity and prove the admission /
priority / deadline / degradation machinery holds the line. Prints ONE
``overload_report/v1`` JSON document (schema + validator in
tmr_tpu/diagnostics.py):

- **capacity** — closed-loop throughput of a plain engine on unique
  images: the denominator every overload factor is measured against.
- **overload** — a fresh engine with bounded admission
  (``max_pending = 3 x batch``) offered >= 5x capacity, open-loop.
  Checks: admitted-traffic p99 bounded by
  ``max_wait + (1 + max_pending/batch) x batch_time + slack`` (the
  whole point of bounding admission: the backlog an admitted request
  can wait behind is capped), rejections carry structured causes, and
  the probe-side future tally reconciles EXACTLY with the engine's
  counters: ``offered == rejected + completed + shed + errors``.
- **shed burst** — requests submitted with a 1 ms deadline against a
  60 ms batching window: every one must shed BEFORE staging (zero
  batches formed, zero device work — the deadline contract).
- **degrade** — a forced-level ladder records its steps on every
  result (``degrade_steps``: truncate_k / downscale here), and the
  auto controller escalates on injected queue-saturation anomalies and
  steps back down after its cooldown — deterministically, no timing.
- **close mid-overload** — close() with a backlog still queued returns
  within its drain bound and leaves every future terminal: no wedge.

Usage:  python scripts/overload_probe.py [--tiny] [--out FILE]
        [--batch N] [--requests N] [--factor F]

``--tiny`` (or TMR_BENCH_TINY=1) shrinks geometry/counts for the CPU
smoke that rides tier-1 (tests/test_overload_probe.py); real numbers
use the deployment geometry. One-JSON-line contract via bench_guard.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _progress(msg: str) -> None:
    print(f"[overload_probe] {msg}", file=sys.stderr, flush=True)


def _percentiles(lat_s) -> dict:
    if not lat_s:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    arr = np.asarray(lat_s) * 1000.0
    return {
        "p50": round(float(np.percentile(arr, 50)), 2),
        "p95": round(float(np.percentile(arr, 95)), 2),
        "p99": round(float(np.percentile(arr, 99)), 2),
    }


def _images(n: int, size: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((size, size, 3)).astype(np.float32)
            for _ in range(n)]


SMALL_EX = np.asarray([[0.45, 0.45, 0.53, 0.55]], np.float32)


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None,
                    help="overload-phase offered request count")
    ap.add_argument("--factor", type=float, default=5.0,
                    help="offered load as a multiple of measured capacity")
    args = ap.parse_args(argv)

    tiny = args.tiny or os.environ.get("TMR_BENCH_TINY", "") not in (
        "", "0", "false"
    )
    size = int(os.environ.get("TMR_BENCH_SIZE", 128 if tiny else 1024))
    dtype = "float32" if tiny else "bfloat16"

    import jax
    import jax.numpy as jnp

    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        OVERLOAD_REPORT_SCHEMA,
        validate_overload_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import (
        AdmissionController,
        DegradeController,
        RejectedError,
        ServeEngine,
    )

    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny}")
    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)
    batch = max(int(args.batch), 2)
    wall0 = time.perf_counter()

    # ---- warmup: compile every program shape the timed phases can
    # produce, OUTSIDE every timed window (a cold compile inside the
    # overload round would charge seconds of XLA work to the p99)
    _progress("warmup compiles (single path B in {1,2,batch}; degraded "
              "half-size single + multi)")
    fn = pred._get_fn(9)
    ex1 = jnp.asarray(SMALL_EX[None])
    for b in sorted({1, 2, batch}):
        fn(pred.params, pred.refiner_params,
           jnp.zeros((b, size, size, 3), jnp.float32),
           jnp.tile(ex1, (b, 1, 1)))
    half = size // 2
    fn(pred.params, pred.refiner_params,
       jnp.zeros((1, half, half, 3), jnp.float32), ex1)
    mfn = pred._get_multi_batched_fn(9, 1)
    mfn(pred.params, pred.refiner_params,
        jnp.zeros((1, half, half, 3), jnp.float32),
        jnp.asarray(SMALL_EX[None]), jnp.ones((1,), jnp.int32))

    report = {
        "schema": OVERLOAD_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "batch": batch,
            "factor": float(args.factor),
        },
    }

    # ---- phase 1: measured capacity (plain engine, unique traffic)
    _progress("phase capacity (closed loop)")
    n_cap = 3 * batch
    eng_cap = ServeEngine(pred, batch=batch, max_wait_ms=10,
                          feature_cache=0)
    imgs = _images(n_cap, size, seed=1)
    t0 = time.perf_counter()
    futs = [eng_cap.submit(im, SMALL_EX) for im in imgs]
    for f in futs:
        f.result(timeout=600)
    capacity = n_cap / (time.perf_counter() - t0)
    eng_cap.close()
    report["capacity"] = {"img_per_sec": round(capacity, 3),
                          "requests": n_cap}
    report["config"]["max_wait_ms"] = eng_cap.max_wait_ms
    _progress(f"capacity: {capacity:.3f} img/s")

    # ---- phase 2: >= 5x offered load against bounded admission
    max_pending = 3 * batch
    offered_rate = args.factor * capacity
    n_offer = args.requests or 12 * batch
    _progress(f"phase overload: {n_offer} requests at "
              f"{offered_rate:.2f} img/s (max_pending={max_pending})")
    eng = ServeEngine(
        pred, batch=batch, max_wait_ms=10, feature_cache=0,
        admission=AdmissionController(enabled=True,
                                      max_pending=max_pending),
    )
    report["config"]["max_pending"] = max_pending
    lat: list = []
    outcomes = {"completed": 0, "rejected": 0, "shed": 0, "errors": 0}
    causes: dict = {}
    period = 1.0 / offered_rate
    futs = []
    t0 = time.perf_counter()
    for i, im in enumerate(_images(n_offer, size, seed=2)):
        target = t0 + i * period
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        ts = time.perf_counter()
        f = eng.submit(im, SMALL_EX)
        f.add_done_callback(
            lambda _f, _ts=ts: lat.append(time.perf_counter() - _ts)
            if _f.exception() is None else None
        )
        futs.append(f)
    for f in futs:
        exc = None
        try:
            f.result(timeout=600)
        except Exception as e:  # noqa: BLE001 — tallied below
            exc = e
        if exc is None:
            outcomes["completed"] += 1
        elif isinstance(exc, RejectedError):
            causes[exc.cause] = causes.get(exc.cause, 0) + 1
            if exc.cause in ("deadline", "shutdown"):
                outcomes["shed"] += 1
            else:
                outcomes["rejected"] += 1
        else:
            outcomes["errors"] += 1
    counters = eng.counters
    over_counters = eng.overload_counters()
    retry_hints = [c for c in causes]  # causes observed
    batch_ms = batch / capacity * 1000.0
    slack_ms = 500.0 if jax.default_backend() == "cpu" else 50.0
    # admitted backlog is BOUNDED: a request admitted at the cap waits
    # behind at most max_pending predecessors plus its own batch window
    p99_bound_ms = (eng.max_wait_ms
                    + (1 + max_pending / batch) * batch_ms + slack_ms)
    pct = _percentiles(lat)
    report["overload"] = {
        "offered": n_offer,
        "offered_img_per_sec": round(offered_rate, 3),
        "latency_ms": pct,
        "reject_causes": causes,
        "degraded": over_counters["degraded"],
        **{k: outcomes[k] for k in
           ("completed", "rejected", "shed", "errors")},
    }
    accounting_exact = (
        sum(outcomes.values()) == n_offer
        and outcomes["rejected"] == over_counters["admit_rejected"]
        and outcomes["completed"] == counters["completed"]
        and outcomes["shed"] == over_counters["shed"]
        and counters["submitted"] ==
        n_offer - over_counters["admit_rejected"]
    )
    _progress(f"overload: {outcomes} p99={pct['p99']}ms "
              f"(bound {p99_bound_ms:.0f}ms) exact={accounting_exact}")

    # ---- phase 3: deterministic deadline shed — expired before staging
    _progress("phase shed burst (1 ms deadline vs 60 ms window)")
    eng_shed = ServeEngine(pred, batch=batch, max_wait_ms=60,
                           feature_cache=0)
    # batch-1 requests: the bucket never fills, so release waits the
    # full 60 ms window — by which point every 1 ms deadline is long
    # expired and the stage loop must shed the lot before any staging
    shed_futs = [
        eng_shed.submit(im, SMALL_EX, deadline_ms=1.0)
        for im in _images(batch - 1, size, seed=3)
    ]
    shed_hits = 0
    for f in shed_futs:
        try:
            f.result(timeout=120)
        except RejectedError as e:
            shed_hits += 1 if e.cause == "deadline" else 0
        except Exception:
            pass
    shed_stats = eng_shed.stats()
    eng_shed.close()
    # zero batches formed == zero stagings == zero device_put/execute
    shed_before_device = bool(
        shed_hits == len(shed_futs) and shed_stats["batches"] == 0
        and shed_stats["completed"] == 0
    )
    report["shed_phase"] = {
        "offered": len(shed_futs),
        "shed": shed_hits,
        "batches": shed_stats["batches"],
    }

    # ---- phase 4: degrade ladder — forced steps recorded exactly, and
    # the auto controller's escalation/cooldown trajectory
    _progress("phase degrade (forced level 3 + auto trajectory)")
    eng_deg = ServeEngine(
        pred, batch=1, max_wait_ms=5, feature_cache=0,
        degrade=DegradeController(mode="3", min_size=half),
    )
    img = _images(1, size, seed=4)[0]
    r_single = eng_deg.submit(img, SMALL_EX).result(timeout=600)
    multi_ex = np.asarray(
        [[0.45, 0.45, 0.53, 0.55], [0.2, 0.2, 0.28, 0.3],
         [0.6, 0.55, 0.68, 0.66]], np.float32,
    )
    r_multi = eng_deg.submit(img, multi_ex, multi=True).result(timeout=600)
    deg_counters = eng_deg.overload_counters()
    eng_deg.close()
    steps_single = tuple(r_single.get("degrade_steps", ()))
    steps_multi = tuple(r_multi.get("degrade_steps", ()))
    degrade_steps_recorded = bool(
        steps_single == ("downscale",)
        and steps_multi == ("downscale", "truncate_k")
        and r_single["boxes"].shape[0] == 1
        and deg_counters["degraded"] == 2
    )
    auto = DegradeController(mode="auto", cooldown=2, max_level=3)
    storm = [{"anomaly": "queue_saturation", "message": "x",
              "evidence": {}}]
    trajectory = [auto.observe(storm), auto.observe(storm),
                  auto.observe([]), auto.observe([]),
                  auto.observe([]), auto.observe([])]
    degrade_auto_ladder = trajectory == [1, 2, 2, 1, 1, 0]
    report["degrade"] = {
        "forced_level": 3,
        "steps_seen": sorted(set(steps_single) | set(steps_multi)),
        "counters": deg_counters,
        "auto_trajectory": trajectory,
    }

    # ---- phase 5: close() mid-overload — bounded, no wedge
    _progress("phase close mid-overload")
    burst = [eng.submit(im, SMALL_EX)
             for im in _images(6 * batch, size, seed=5)]
    close_timeout = 120.0
    t0 = time.perf_counter()
    eng.close(timeout=close_timeout)
    close_wall = time.perf_counter() - t0
    all_terminal = all(f.done() for f in burst)
    leftover = eng.overload_counters().get("shed.shutdown", 0)
    report["close"] = {
        "wall_s": round(close_wall, 3),
        "timeout_s": close_timeout,
        "leftover_rejected": int(leftover),
        "all_terminal": bool(all_terminal),
    }
    _progress(f"close: {close_wall:.2f}s, all_terminal={all_terminal}, "
              f"leftover={leftover}")

    report["checks"] = {
        "p99_ms": pct["p99"],
        "p99_bound_ms": round(p99_bound_ms, 2),
        "p99_bounded": bool(outcomes["completed"] > 0
                            and pct["p99"] <= p99_bound_ms),
        "accounting_exact": bool(accounting_exact),
        "rejected_nonzero": bool(outcomes["rejected"] > 0),
        "reject_causes_structured": bool(
            retry_hints and all(c in ("queue_full", "class_limit",
                                      "rate_limited", "deadline",
                                      "shutdown") for c in retry_hints)
        ),
        "shed_before_device": shed_before_device,
        "degrade_steps_recorded": degrade_steps_recorded,
        "degrade_auto_ladder": bool(degrade_auto_ladder),
        "close_bounded": bool(close_wall <= close_timeout
                              and all_terminal),
    }
    report["counters"] = {**counters, **over_counters}
    report["wall_s"] = round(time.perf_counter() - wall0, 1)
    problems = validate_overload_report(report)
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
    """One overload_report/v1 JSON line on stdout, success or not: the
    shared bench_guard funnels wedges and crashes into a contractual
    error record."""
    from tmr_tpu.diagnostics import OVERLOAD_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": OVERLOAD_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

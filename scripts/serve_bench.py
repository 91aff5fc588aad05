"""Offered-load benchmark of the serving layer (tmr_tpu/serve).

Drives ServeEngine through closed- and open-loop workloads and prints ONE
``serve_report/v1`` JSON document (schema + validator in
tmr_tpu/diagnostics.py):

- ``exact_closed`` — unique-image closed loop at the coalescing bound vs
  the sequential ``Predictor.__call__`` loop on the identical requests;
  proves batched results are BITWISE-identical to sequential and measures
  pure batching speedup (no cache involvement by construction).
- ``mixed_closed`` — the interactive mix (repeated exemplars on repeated
  images, submitted in waves so repeats can land after their first copy
  completes): result-cache and feature-cache hits happen here, and the
  headline ≥1.5x speedup check compares this workload's serve throughput
  against the same requests through the sequential loop.
- ``open_rate_*`` — open-loop arrivals at fractions of the measured
  closed-loop throughput; p50/p95/p99 latency and the batch-occupancy
  histogram per rate. The p99-bound check runs at the LOW rate, where a
  request's worst case is max_wait_ms + one padded-batch execution (the
  latency contract of the micro-batcher).

Usage:  python scripts/serve_bench.py [--tiny] [--out FILE]
        [--batch N] [--max-wait-ms MS] [--requests N] [--rates r1,r2]

``--tiny`` (or TMR_BENCH_TINY=1) shrinks geometry + counts so the whole
sweep smoke-runs on CPU in minutes (tier-1 runs it under
JAX_PLATFORMS=cpu); real numbers use the 1024^2 deployment geometry.
Same one-JSON-line contract as bench.py via the shared bench_guard.
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
    print(f"[serve_bench] {msg}", file=sys.stderr, flush=True)


def _percentiles(lat_s) -> dict:
    if not lat_s:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    arr = np.asarray(lat_s) * 1000.0
    return {
        "p50": round(float(np.percentile(arr, 50)), 2),
        "p95": round(float(np.percentile(arr, 95)), 2),
        "p99": round(float(np.percentile(arr, 99)), 2),
    }


def _make_requests(size: int, batch: int, seed: int = 0):
    """The workload images/exemplars. Returns (unique, mixed):
    ``unique`` — 2*batch+3 distinct (image, exemplar) pairs spanning a
    ragged tail and two capacity buckets; ``mixed`` — the interactive
    pattern over few images: exact repeats (result-cache) and
    same-image-new-exemplar queries (feature-cache), in waves."""
    rng = np.random.default_rng(seed)
    n_unique = 2 * batch + 3
    small_ex = np.asarray([[0.45, 0.45, 0.53, 0.55]], np.float32)
    big_ex = np.asarray([[0.1, 0.1, 0.9, 0.9]], np.float32)
    unique = []
    for i in range(n_unique):
        img = rng.standard_normal((size, size, 3)).astype(np.float32)
        unique.append((img, big_ex if i % 3 == 2 else small_ex))

    n_imgs = batch  # full first-wave batches: the interactive mix should
    waves = []      # exercise batching AND caching, not padding waste
    imgs = [rng.standard_normal((size, size, 3)).astype(np.float32)
            for _ in range(n_imgs)]
    exs = [small_ex,
           np.asarray([[0.2, 0.2, 0.28, 0.3]], np.float32),
           np.asarray([[0.6, 0.55, 0.68, 0.66]], np.float32)]
    # wave 1: first sighting; waves 2..: exact repeats + fresh exemplars
    waves.append([(im, exs[0]) for im in imgs])
    waves.append([(im, exs[0]) for im in imgs])      # result-cache hits
    waves.append([(im, exs[1]) for im in imgs])      # promotion fills
    waves.append([(im, exs[2]) for im in imgs])      # feature-cache hits
    waves.append([(im, exs[1]) for im in imgs])      # result-cache hits
    return unique, waves


def _sequential_throughput(pred, requests, iters: int = 1) -> float:
    """img/s of the plain one-request-at-a-time Predictor loop (results
    fetched per request, like a naive server would)."""
    t0 = time.perf_counter()
    for _ in range(iters):
        for img, ex in requests:
            dets = pred(img[None], ex[None])
            np.asarray(dets["scores"])  # fetch = the request is done
    dt = time.perf_counter() - t0
    return len(requests) * iters / dt


def _timed_submit(engine, img, ex, lat: list, deadline_ms=None):
    """Submit with resolution-time latency capture: the done-callback
    stamps the clock WHEN the future resolves — awaiting futures in
    submission order afterwards would credit early requests with the whole
    tail of the run. Only successful resolutions enter the latency
    sample: a rejection/shed resolves in microseconds and would
    deflate the percentiles of the traffic that was actually served."""
    ts = time.perf_counter()
    f = engine.submit(img, ex, deadline_ms=deadline_ms)
    f.add_done_callback(
        lambda _f, _ts=ts: lat.append(time.perf_counter() - _ts)
        if _f.exception() is None else None
    )
    return f


def _closed_loop(engine, requests, waves: bool = False):
    """Submit everything (optionally wave-synchronized), await all.
    Returns (throughput img/s, [latency_s], [results])."""
    groups = requests if waves else [requests]
    lat, results = [], []
    t0 = time.perf_counter()
    for group in groups:
        futs = [_timed_submit(engine, img, ex, lat) for img, ex in group]
        for f in futs:
            results.append(f.result(timeout=600))
    dt = time.perf_counter() - t0
    return len(results) / dt, lat, results


def _open_loop(engine, requests, rate: float, deadline_ms=None):
    """Fixed-rate arrivals at ``rate`` img/s; returns (served_tput,
    [latency_s], served_count). Open-loop clients are NOT infinitely
    patient anymore: with admission/deadlines in play a future may
    resolve with a structured RejectedError — tallied by the engine's
    overload counters (attached to the workload record), not a crash."""
    period = 1.0 / rate
    lat: list = []
    futs = []
    t0 = time.perf_counter()
    for i, (img, ex) in enumerate(requests):
        target = t0 + i * period
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        futs.append(_timed_submit(engine, img, ex, lat,
                                  deadline_ms=deadline_ms))
    served = 0
    for f in futs:
        try:
            f.result(timeout=600)
            served += 1
        except Exception:
            pass  # rejection/shed: counted via engine.overload_counters
    dt = time.perf_counter() - t0
    return served / dt, lat, served


def _workload_record(name, mode, n, tput, lat_s, engine, occ0, cache0):
    """One workloads[] entry; occupancy/cache deltas vs the pre-workload
    snapshots so each workload reports only its own traffic."""
    stats = engine.stats()
    occ = {
        k: v - occ0.get(k, 0)
        for k, v in stats["batch_occupancy"].items()
        if v - occ0.get(k, 0) > 0
    }
    cache = {}
    for which in ("result_cache", "feature_cache"):
        now = stats[which]
        base = cache0.get(which, {})
        cache[which] = {
            k: now[k] - base.get(k, 0)
            for k in ("hits", "misses", "evictions", "inserts")
        }
    return {
        "name": name,
        "mode": mode,
        "requests": n,
        "throughput_img_per_sec": round(tput, 3),
        "latency_ms": _percentiles(lat_s),
        "batch_occupancy": occ,
        "cache": cache,
    }


def _snapshots(engine):
    s = engine.stats()
    return s["batch_occupancy"], {
        w: dict(s[w]) for w in ("result_cache", "feature_cache")
    }


def _bitwise_equal(a: dict, b: dict) -> bool:
    return all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
        for k in ("boxes", "scores", "refs", "valid")
    )


def _allclose_equal(a: dict, b: dict, atol: float = 1e-4) -> bool:
    """Tensor-parallel parity: identical keep decisions, floats at
    allclose (TP collectives reorder reductions — the documented
    heads-path-style exception; dp stays bitwise)."""
    if not np.array_equal(np.asarray(a["valid"]), np.asarray(b["valid"])):
        return False
    return all(
        np.allclose(np.asarray(a[k]).astype(np.float64),
                    np.asarray(b[k]).astype(np.float64), atol=atol)
        for k in ("boxes", "scores", "refs")
    )


def _run_mesh_sweep(args, tiny: bool, size: int, dtype: str,
                    cancel_watchdog) -> int:
    """``--mesh dp4,dp2tp2,...``: one serve_report/v1 JSON line PER mesh
    shape, each with a validated ``mesh`` attachment (spec, axis shape,
    replica groups) — closed-loop throughput vs the single-device
    engine on identical requests, per-request parity (bitwise for dp
    meshes, allclose + identical keep decisions for tp), and the
    AOT-warmup zero-cold-compile pin via PR 8's compile-event cursor.

    Scaling expectations are host-aware: a forced-8-device CPU mesh on
    an N-core host can overlap at most min(devices, N) executions, so
    the ``scaling_ok`` check targets 3x only where the host can
    physically deliver it (the acceptance number for real multi-chip
    slices and multi-core CI) and degrades to a bounded-overhead check
    on single-core containers — reported, never fabricated."""
    import jax

    from tmr_tpu import obs
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        SERVE_REPORT_SCHEMA,
        validate_serve_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import ServeEngine

    specs = [s.strip() for s in args.mesh.split(",") if s.strip()]
    _progress(f"mesh sweep {specs}: backend {jax.devices()[0]} "
              f"size={size} tiny={tiny}")
    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)
    batch = args.batch or 1
    unique, _waves = _make_requests(size, batch)
    warmup_buckets = sorted(
        {pred.bucket_key(size, ex) for _img, ex in unique}
    )

    # ---- single-device baseline on the identical requests
    _progress("single-device baseline")
    # mesh="off" EXPLICITLY: the baseline must stay single-device even
    # when TMR_SERVE_MESH is set in the env (otherwise the env spec
    # either crashes against the 1-device list or silently meshes the
    # denominator every scaling number divides by)
    base = ServeEngine(pred, batch=batch, max_wait_ms=args.max_wait_ms,
                       devices=jax.devices()[:1], feature_cache=0,
                       exemplar_cache=0, warmup_buckets=warmup_buckets,
                       aot=True, mesh="off")
    base_tput, _lat, base_results = _closed_loop(base, unique)
    base.close()
    _progress(f"single-device: {base_tput:.3f} img/s")

    host_cores = os.cpu_count() or 1
    lines = []
    rc = 0
    for spec in specs:
        _progress(f"mesh {spec}: engine start (AOT warmup)")
        wall0 = time.perf_counter()
        engine = ServeEngine(pred, batch=batch,
                             max_wait_ms=args.max_wait_ms, mesh=spec,
                             feature_cache=0, exemplar_cache=0,
                             warmup_buckets=warmup_buckets, aot=True)
        stats0 = engine.stats()
        warmup = stats0.get("warmup") or {}
        # the AOT pin: every program the workload can reach compiled at
        # warmup, so steady state records ZERO new compile events
        cursor = obs.compile_event_seq()
        occ0, cache0 = _snapshots(engine)
        tput, lat, results = _closed_loop(engine, unique)
        new_events, _seq = obs.compile_events_since(cursor)
        mesh_desc = stats0.get("mesh") or {}
        tp = int((mesh_desc.get("shape") or {}).get("tp", 1))
        n_dev = sum(len(g) for g in
                    (mesh_desc.get("replica_groups") or []))
        if tp == 1:
            exact = all(_bitwise_equal(a, b)
                        for a, b in zip(base_results, results))
            parity = "bitwise"
        else:
            exact = all(_allclose_equal(a, b)
                        for a, b in zip(base_results, results))
            parity = "allclose"
        scaling = tput / base_tput if base_tput > 0 else 0.0
        expected = min(n_dev, host_cores) if \
            jax.default_backend() == "cpu" else n_dev
        scaling_target = 0.5 if expected <= 1 else min(3.0,
                                                       0.75 * expected)
        batch_global = engine._bound_for(warmup_buckets[0])
        batch_ms = batch_global / tput * 1000.0 if tput > 0 else 0.0
        slack_ms = 500.0 if jax.default_backend() == "cpu" else 50.0
        # closed-loop burst: the last request drains behind the whole
        # backlog, so the p99 envelope is the PR 9 per-batch bound times
        # the batches the burst forms (the open-loop low-rate bound
        # stays with the default serve_bench path)
        n_batches = -(-len(unique) // max(batch_global, 1))
        p99_bound_ms = (engine.max_wait_ms + n_batches * batch_ms
                        + slack_ms)
        rec = _workload_record("mesh_closed", "closed", len(unique),
                               tput, lat, engine, occ0, cache0)
        rec["single_device_img_per_sec"] = round(base_tput, 3)
        p99 = rec["latency_ms"]["p99"]
        report = {
            "schema": SERVE_REPORT_SCHEMA,
            "device": str(jax.devices()[0]),
            "config": {
                "image_size": size,
                "batch": batch,
                "batch_global": batch_global,
                "max_wait_ms": engine.max_wait_ms,
                "devices": n_dev,
                "donate": engine.donate,
                "host_cores": host_cores,
            },
            "mesh": mesh_desc,
            **({"quant": stats0["quant"]} if "quant" in stats0 else {}),
            "aot": {
                "warmup": warmup,
                "compile_events_after_warmup": len(new_events),
                "cold_after_warmup": [
                    {"kind": e["kind"], "cause": e["cause"]}
                    for e in new_events
                ],
            },
            "workloads": [rec],
            "checks": {
                "speedup_vs_sequential": round(scaling, 3),
                "speedup_ok": bool(scaling >= scaling_target),
                "scaling_vs_single_device": round(scaling, 3),
                "scaling_target": round(scaling_target, 3),
                "scaling_ok": bool(scaling >= scaling_target),
                "host_parallelism": int(expected),
                "exact_match": bool(exact),
                "parity": parity,
                "p99_ms": p99,
                "p99_bound_ms": round(p99_bound_ms, 2),
                "p99_bounded": bool(p99 <= p99_bound_ms),
                "no_cold_compiles_after_warmup": bool(
                    len(new_events) == 0
                ),
                "cache_hit": None,  # caches off: not exercised here
                "cache_exercised": False,
            },
            "stats": engine.stats(),
            "metrics": engine.metrics_snapshot(),
        }
        engine.close()
        report["wall_s"] = round(time.perf_counter() - wall0, 1)
        problems = validate_serve_report(report)
        if problems:
            report["validator_problems"] = problems
            rc = 1
        _progress(
            f"mesh {spec}: {tput:.3f} img/s ({scaling:.2f}x single-"
            f"device, target {scaling_target:.2f}x), parity={parity} "
            f"exact={exact}, cold-after-warmup={len(new_events)}"
        )
        lines.append(json.dumps(report))

    cancel_watchdog()
    out_text = "\n".join(lines) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(out_text)
    sys.stdout.write(out_text)
    sys.stdout.flush()
    return rc


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--max-wait-ms", type=float, default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="open-loop request count per rate")
    ap.add_argument("--rates", default=None,
                    help="comma-separated open-loop offered loads (img/s); "
                         "default: 0.4x and 0.8x of measured closed-loop")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for the open-loop sweep "
                         "(finite patience; default: none, the PR 3 "
                         "behavior)")
    ap.add_argument("--mesh", default=None,
                    help="comma-separated serving-mesh specs to sweep "
                         "(e.g. dp4,dp2tp2,tp4): one serve_report/v1 "
                         "line per shape with a mesh attachment, closed-"
                         "loop scaling vs the single-device engine, and "
                         "the AOT zero-cold-compile pin")
    args = ap.parse_args(argv)

    tiny = args.tiny or os.environ.get("TMR_BENCH_TINY", "") not in (
        "", "0", "false"
    )
    size = int(os.environ.get("TMR_BENCH_SIZE", 256 if tiny else 1024))
    dtype = "float32" if tiny else "bfloat16"

    if args.mesh:
        return _run_mesh_sweep(args, tiny, size, dtype, cancel_watchdog)

    import jax

    from tmr_tpu import obs
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        SERVE_REPORT_SCHEMA,
        validate_serve_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import ServeEngine

    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny}")
    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)

    engine = ServeEngine(pred, batch=args.batch,
                         max_wait_ms=args.max_wait_ms)
    batch = engine._bound_for(("single", size, 17, 1))
    wall0 = time.perf_counter()
    unique, waves = _make_requests(size, batch)
    report = {
        "schema": SERVE_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "batch": batch,
            "max_wait_ms": engine.max_wait_ms,
            "devices": len(engine.devices),
            "donate": engine.donate,
            "result_cache": engine.result_cache.capacity,
            "feature_cache": engine.feature_cache.capacity,
        },
        # numerics provenance: a storage-quantized engine's report says
        # so (quant.mode/storage/digest — validator-checked)
        **({"quant": engine.stats()["quant"]}
           if "quant" in engine.stats() else {}),
        "workloads": [],
    }

    # ---- warmup: compile the sequential B=1 program, the batched fused
    # program, and the feature path (backbone fill + heads) at BOTH the
    # lone and the batch-sized shapes, outside every timed window, on
    # throwaway images
    _progress("warmup compiles (sequential + batched + feature path)")
    _sequential_throughput(pred, unique[:1])
    rng_w = np.random.default_rng(99)
    w_imgs = [rng_w.standard_normal((size, size, 3)).astype(np.float32)
              for _ in range(batch)]
    _closed_loop(engine, [(im, unique[0][1]) for im in w_imgs]
                 + unique[:1])  # fused at B=batch and B=1; marks w_imgs seen
    for ex_w in ([[0.2, 0.2, 0.3, 0.31]], [[0.6, 0.6, 0.68, 0.7]]):
        ex_w = np.asarray(ex_w, np.float32)
        # one wave of batch-sized heads traffic (promotion fills first,
        # feature hits second) plus a lone request: the backbone-fill and
        # heads programs compile at every sub-bucket shape the timed
        # workloads can produce
        _closed_loop(engine, [[(im, ex_w) for im in w_imgs]], waves=True)
        engine.submit(w_imgs[0], ex_w + 0.01).result(timeout=600)

    # ---- exact_closed: unique traffic, bitwise check vs sequential
    _progress("workload exact_closed")
    occ0, cache0 = _snapshots(engine)
    seq_results = []
    for img, ex in unique:
        d = pred(img[None], ex[None])
        seq_results.append({k: np.asarray(d[k]) for k in
                            ("boxes", "scores", "refs", "valid")})
    seq_tput_unique = _sequential_throughput(pred, unique)
    # fresh engine state for exactness: the warmup populated caches with
    # some of these images — exactness must measure the fused batch path
    engine2 = ServeEngine(pred, batch=batch,
                          max_wait_ms=engine.max_wait_ms)
    o2, c2 = _snapshots(engine2)
    tput, lat, results = _closed_loop(engine2, unique)
    exact = all(
        _bitwise_equal(a, b) for a, b in zip(seq_results, results)
    )
    report["workloads"].append(
        _workload_record("exact_closed", "closed", len(unique), tput, lat,
                         engine2, o2, c2)
    )
    report["workloads"][-1]["sequential_img_per_sec"] = round(
        seq_tput_unique, 3
    )
    batch_ms = batch / tput * 1000.0
    engine2.close()
    _progress(f"exact_closed: serve {tput:.3f} img/s vs sequential "
              f"{seq_tput_unique:.3f} img/s, exact={exact}")

    # ---- mixed_closed: the interactive repeat mix (cache traffic)
    _progress("workload mixed_closed")
    flat = [r for wave in waves for r in wave]
    seq_tput_mixed = _sequential_throughput(pred, flat)
    occ0, cache0 = _snapshots(engine)
    m_tput, m_lat, _ = _closed_loop(engine, waves, waves=True)
    rec = _workload_record("mixed_closed", "closed", len(flat), m_tput,
                           m_lat, engine, occ0, cache0)
    rec["sequential_img_per_sec"] = round(seq_tput_mixed, 3)
    report["workloads"].append(rec)
    speedup = m_tput / seq_tput_mixed
    mixed_cache = rec["cache"]
    _progress(f"mixed_closed: serve {m_tput:.3f} img/s vs sequential "
              f"{seq_tput_mixed:.3f} img/s ({speedup:.2f}x)")

    # ---- open-loop offered-load sweep
    if args.rates:
        rates = [float(r) for r in args.rates.split(",") if r.strip()]
    else:
        rates = [round(tput * 0.4, 3), round(tput * 0.8, 3)]
    n_open = args.requests or (3 * batch if tiny else 8 * batch)
    rng = np.random.default_rng(7)
    low_rate_p99 = None
    for rate in rates:
        if rate <= 0:
            continue
        _progress(f"workload open_rate_{rate}")
        reqs = []
        small_ex = np.asarray([[0.45, 0.45, 0.53, 0.55]], np.float32)
        for _ in range(n_open):
            reqs.append((
                rng.standard_normal((size, size, 3)).astype(np.float32),
                small_ex,
            ))
        occ0, cache0 = _snapshots(engine)
        ov0 = engine.overload_counters()
        o_tput, o_lat, served = _open_loop(engine, reqs, rate,
                                           deadline_ms=args.deadline_ms)
        rec = _workload_record(f"open_rate_{rate}", "open", n_open, o_tput,
                               o_lat, engine, occ0, cache0)
        rec["offered_img_per_sec"] = rate
        # admission/shed/degrade deltas for THIS round — overload rounds
        # in a trend sweep stay interpretable (zeros with default knobs)
        ov1 = engine.overload_counters()
        rejected = ov1["admit_rejected"] - ov0["admit_rejected"]
        rec["admission"] = {
            "rejected": rejected,
            "shed": ov1["shed"] - ov0["shed"],
            "degraded": ov1["degraded"] - ov0["degraded"],
            "served": served,
            "reject_rate": round(rejected / max(n_open, 1), 4),
        }
        report["workloads"].append(rec)
        if low_rate_p99 is None:
            low_rate_p99 = rec["latency_ms"]["p99"]
        _progress(f"open_rate_{rate}: {rec['latency_ms']}")

    # ---- acceptance checks
    # p99 bound: at low offered load a request waits at most max_wait_ms
    # for batch-mates plus one (padded) batch execution; host-side slack
    # covers staging/fetch scheduling jitter (CPU thread scheduling is the
    # noisy term in the tiny smoke).
    slack_ms = 500.0 if jax.default_backend() == "cpu" else 50.0
    p99_bound_ms = engine.max_wait_ms + batch_ms + slack_ms
    cache_hits = (mixed_cache["result_cache"]["hits"]
                  + mixed_cache["feature_cache"]["hits"])
    report["checks"] = {
        "speedup_vs_sequential": round(speedup, 3),
        "speedup_ok": bool(speedup >= 1.5),
        "exact_match": bool(exact),
        "batch_ms": round(batch_ms, 2),
        "p99_ms": low_rate_p99,
        "p99_bound_ms": round(p99_bound_ms, 2),
        "p99_bounded": bool(
            low_rate_p99 is not None and low_rate_p99 <= p99_bound_ms
        ),
        "cache_hits": cache_hits,
        "cache_hit": bool(cache_hits > 0),
    }
    report["stats"] = engine.stats()
    # the engine's metrics registry as one metrics_report/v1 document —
    # latency AND counter state travel in the same JSON line (validated
    # as part of validate_serve_report)
    report["metrics"] = engine.metrics_snapshot()
    if obs.flight_enabled():
        # TMR_FLIGHT=1: the per-program device-time / MFU attribution
        # for everything this bench executed rides the same line
        # (mfu_report/v1; validate_serve_report checks the attachment)
        report["mfu"] = obs.mfu_report()
    engine.close()
    report["wall_s"] = round(time.perf_counter() - wall0, 1)
    problems = validate_serve_report(report)
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
    """One serve_report/v1 JSON line on stdout, success or not: the shared
    bench_guard (same watchdog bench.py runs under) funnels wedges and
    crashes into a contractual error record."""
    from tmr_tpu.diagnostics import SERVE_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": SERVE_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

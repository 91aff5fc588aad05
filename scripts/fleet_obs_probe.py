"""Measured proof of the TMR_FLEET_OBS fleet observability plane
(tmr_tpu/obs/fleetobs.py): cross-process trace propagation, heartbeat
metrics rollup, the stitched cluster timeline, and the fleet
HealthWatch — against a REAL multi-process stub fleet. Prints ONE
``fleet_obs_report/v1`` JSON document (schema + validator in
tmr_tpu/diagnostics.py):

- **overhead** — with the plane disabled (the default), the per-site
  guard is timed (ns) and a small in-process fleet measures the
  baseline request latency; the projected per-request overhead must be
  under 1%.
- **calm / outlier** — three subprocess workers split the traffic
  partitions, one paced 12x slower than its peers. A balanced warm-up
  window passes the fleet HealthWatch QUIET; the mixed window that
  exercises the slow worker fires EXACTLY ``worker_outlier_latency``,
  naming it. Every submit mints one trace id at the front door and the
  workers' serve spans come home on heartbeats: at least one complete
  frontdoor -> worker span chain must exist under a single trace id.
- **reconciliation** — the workers are stopped CLEANLY (SIGINT ->
  ``bye`` final flush): the coordinator's sum-of-beat-deltas must match
  every worker's final counter totals EXACTLY.
- **stitched timeline** — the merged Chrome trace (one track per
  process, clock offsets estimated from beat round-trips and stamped
  into the track names) must stay monotone after offset correction.
- **beat_gap** — a fresh two-worker fleet has one worker kill -9'd:
  the next HealthWatch pass fires EXACTLY ``beat_gap`` naming it, and
  the pass after stays quiet (the gap latches).

Usage:  python scripts/fleet_obs_probe.py [--out FILE]

Fast (seconds, numpy stub engines, CPU): rides tier-1 via
tests/test_fleetobs.py. One-JSON-line contract via bench_guard.
``scripts/bench_trend.py --fleet-obs`` rc-gates on the report.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 32
EX = np.asarray([[0.4, 0.4, 0.6, 0.6]], np.float32)
#: disabled-plane guard sites on one request's path: submit ctx mint,
#: terminal close, the worker's serve-span check, and the beat fold
_OBS_SITES_PER_REQUEST = 4


def _progress(msg: str) -> None:
    print(f"[fleet_obs_probe] {msg}", file=sys.stderr, flush=True)


def _img(seed: int):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((SIZE, SIZE, 3)).astype(np.float32)


def _poll(predicate, timeout_s: float, interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval_s)
    return predicate()


def _policy(lease_ttl_s: float):
    from tmr_tpu.parallel.leases import LeasePolicy

    return LeasePolicy(
        lease_ttl_s=lease_ttl_s, hb_interval_s=0.2,
        check_interval_s=0.05, straggler_factor=0.0,
        max_reassigns=1_000_000_000,
        resource_fail_workers=1_000_000_000,
    )


def _spawn_worker(wid: str, address, delay_ms: float) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMR_FLEET_OBS="1")
    env.pop("TMR_FAULTS", None)  # the gauntlet runs fault-free
    env.pop("TMR_TRACE", None)  # the plane auto-enables worker tracing
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve_fleet.py"),
         "worker", "--coordinator", f"{address[0]}:{address[1]}",
         "--worker_id", wid, "--engine", "stub",
         "--delay_ms", str(delay_ms), "--batch", "2"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _holder_map(fleet) -> dict:
    """partition key -> holder wid (held partitions only; the state()
    holder field is a (wid, epoch) pair)."""
    out = {}
    for key, rec in fleet.state()["partitions"].items():
        holder = rec["holder"]
        if holder is None:
            continue
        out[key] = holder[0] if isinstance(holder, (tuple, list)) \
            else holder
    return out


def _distinct_holders(fleet, want: int):
    held = _holder_map(fleet)
    return held if (len(held) >= want
                    and len(set(held.values())) >= want) else None


def _await_spread(fleet, wids, timeout_s: float = 30.0):
    """Every partition held AND every worker in ``wids`` holding at
    least one. Spawning workers one at a time against this barrier
    makes the join rebalance deterministic: each hello sees an
    all-leased fleet (so it actually revokes excess), and the lease
    fairness cap hands the freed partition to the recruit — concurrent
    joins can instead settle with an idle worker forever."""
    n_parts = len(fleet.state()["partitions"])

    def ok():
        held = _holder_map(fleet)
        if len(held) < n_parts:
            return None
        holders = set(held.values())
        return held if all(w in holders for w in wids) else None

    return _poll(ok, timeout_s)


def _stable_holders(fleet, want: int, timeout_s: float = 60.0,
                    hold_s: float = 0.6):
    """Wait for ``want`` partitions held by ``want`` DISTINCT workers,
    STABLE across ``hold_s`` — the join rebalance revokes/regrants in
    flight, so a single distinct snapshot can be mid-shuffle."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        held = _poll(lambda: _distinct_holders(fleet, want),
                     max(deadline - time.monotonic(), 0.1))
        if not held:
            return None
        time.sleep(hold_s)
        if _holder_map(fleet) == held:
            return held
    return None


def _submit_wave(fleet, classes, per_class: int, seed: int,
                 paced: bool = False) -> int:
    """Submit ``per_class`` requests to each priority class; wait for
    every future (resolution proves the latency window landed in each
    worker's histogram). ``paced`` waits each round out before the
    next — one request in flight per worker, so a CALM window's p95 is
    the bare service time with no queueing skew between equal peers."""
    pending = []
    n = 0
    for i in range(per_class):
        futs = [fleet.submit(_img(seed + 31 * i + k), EX, priority=k)
                for k in classes]
        n += len(futs)
        if paced:
            for f in futs:
                f.result(timeout=60)
        else:
            pending.extend(futs)
    for f in pending:
        f.result(timeout=60)
    return n


def _await_window(fleet, min_count: int, timeout_s: float = 20.0) -> bool:
    """Wait until the folded per-worker latency histograms cover at
    least ``min_count`` requests (beats every 0.2s carry the deltas)."""
    fo = fleet.fleet_obs

    def landed():
        total = 0
        for acc in fo.metrics.per_worker().values():
            hist = (acc.get("histograms") or {}).get(
                "serve.request_latency_s") or {}
            total += int(hist.get("count") or 0)
        return total >= min_count
    return bool(_poll(landed, timeout_s))


def _complete_chains(chains: dict) -> int:
    """Count trace ids carrying a full cross-process chain: a front-
    door root span (parent 0, coordinator process) plus at least one
    worker span parented directly under it."""
    n = 0
    for recs in chains.values():
        roots = {r["span"] for r in recs
                 if r.get("parent") == 0 and r["proc"] == "coordinator"}
        if roots and any(r.get("parent") in roots
                         and r["proc"] != "coordinator" for r in recs):
            n += 1
    return n


def _measure_disabled_check_ns(iters: int = 50_000) -> float:
    """Amortized cost of one plane-disabled guard site (the ctx mint,
    which embeds the enablement check), in ns."""
    from tmr_tpu.obs import fleetobs

    assert not fleetobs.fleet_obs_enabled()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            fleetobs.make_ctx()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e9


def _baseline_request_ms(n_req: int = 16) -> float:
    """Mean request latency of a tiny DISABLED in-process fleet — the
    denominator of the projected disabled-plane overhead."""
    from tmr_tpu.serve.fleet import FleetWorker, ServeFleet, stub_engine

    fleet = ServeFleet([SIZE], classes=1, policy=_policy(2.0),
                       check_interval_s=0.05)
    addr = fleet.start()
    assert fleet.fleet_obs is None, "plane must be off for the baseline"
    worker = FleetWorker(addr, "w-base", stub_engine()).start()
    try:
        assert _poll(lambda: _holder_map(fleet), 30.0), \
            "baseline fleet never granted its partition"
        for f in [fleet.submit(_img(7 + i), EX) for i in range(4)]:
            f.result(timeout=30)  # warm the batcher
        t0 = time.perf_counter()
        for f in [fleet.submit(_img(100 + i), EX) for i in range(n_req)]:
            f.result(timeout=30)
        return (time.perf_counter() - t0) / n_req * 1000.0
    finally:
        worker.stop()
        fleet.close()


def _run(cancel_watchdog, argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args(argv)
    wall0 = time.perf_counter()

    # deterministic start state: plane off, no fault schedules, and no
    # user TMR_TRACE override (enablement must auto-arm tracing)
    for knob in ("TMR_FLEET_OBS", "TMR_TRACE", "TMR_FAULTS"):
        os.environ.pop(knob, None)

    from tmr_tpu.diagnostics import (
        FLEET_OBS_REPORT_SCHEMA,
        validate_fleet_obs_report,
    )
    from tmr_tpu.obs import fleetobs
    from tmr_tpu.serve.fleet import ServeFleet

    procs: list = []

    def cleanup_workers():
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    # ---- overhead: the disabled plane, measured ----------------------
    _progress("disabled-plane guard micro-benchmark")
    disabled_ns = _measure_disabled_check_ns()
    _progress(f"disabled guard: {disabled_ns:.0f} ns/site")
    base_req_ms = _baseline_request_ms()
    overhead_pct = (disabled_ns * _OBS_SITES_PER_REQUEST
                    / (base_req_ms * 1e6) * 100.0)
    _progress(f"baseline request {base_req_ms:.2f} ms -> projected "
              f"disabled overhead {overhead_pct:.5f}%")

    # ---- plane ON (also auto-arms coordinator tracing) ---------------
    fleetobs.configure(enabled=True)

    try:
        # ---- phase A: calm window, slow-worker window, clean stop ----
        _progress("phase A: 3-worker fleet, one 12x slower")
        fleet_a = ServeFleet([SIZE], classes=3, policy=_policy(2.0),
                             check_interval_s=0.05)
        addr_a = fleet_a.start()
        slow_wid = "w-slow"
        workers_a = {}
        for wid, delay in (("w-a", 10.0), ("w-b", 10.0),
                           (slow_wid, 120.0)):
            workers_a[wid] = _spawn_worker(wid, addr_a, delay_ms=delay)
            procs.append(workers_a[wid])
            if not _await_spread(fleet_a, list(workers_a)):
                raise RuntimeError(
                    f"join rebalance never gave {wid!r} a partition: "
                    f"{_holder_map(fleet_a)}"
                )
        held = _stable_holders(fleet_a, 3)
        if not held:
            raise RuntimeError(
                f"join rebalance never spread 3 partitions across 3 "
                f"workers: {_holder_map(fleet_a)}"
            )
        klass_of = {wid: int(key.rsplit("c", 1)[1])
                    for key, wid in held.items()}
        fast_classes = sorted(k for w, k in klass_of.items()
                              if w != slow_wid)
        per_class = 12

        # calm: balanced traffic on the FAST workers only — the slow
        # worker has no window yet, so a healthy pass must stay quiet
        n_calm = _submit_wave(fleet_a, fast_classes, per_class,
                              seed=10, paced=True)
        assert _await_window(fleet_a, n_calm), \
            "calm-window deltas never folded"
        calm_fired = fleet_a.fleet_obs_pass()
        _progress(f"calm pass: {[a['anomaly'] for a in calm_fired]}")

        # outlier: mixed traffic across all three — the slow worker's
        # window p95 must fire EXACTLY worker_outlier_latency
        n_mixed = _submit_wave(fleet_a, sorted(klass_of.values()),
                               per_class, seed=400)
        assert _await_window(fleet_a, n_calm + n_mixed), \
            "outlier-window deltas never folded"
        outlier_fired = fleet_a.fleet_obs_pass()
        _progress(f"outlier pass: "
                  f"{[a['anomaly'] for a in outlier_fired]}")

        # clean leave: SIGINT -> worker.stop() -> bye final flush
        for p in workers_a.values():
            p.send_signal(signal.SIGINT)
        for p in workers_a.values():
            p.wait(timeout=20)
        fo_a = fleet_a.fleet_obs
        assert _poll(
            lambda: len(
                fo_a.metrics.reconcile()["workers_with_finals"]
            ) >= 3,
            20.0,
        ), "final snapshots never arrived on bye"
        report_a = fo_a.report()
        chains = fo_a.span_chains()
        complete = _complete_chains(chains)
        _progress(
            f"chains: {complete}/{len(chains)} complete, "
            f"reconciliation exact="
            f"{report_a['reconciliation']['exact']}, "
            f"trace monotone={report_a['trace']['monotone']}"
        )
        fleet_a.close()

        # ---- phase B: kill -9 -> beat_gap, exactly once --------------
        _progress("phase B: 2-worker fleet, one kill -9")
        # long lease TTL: the killed worker must still be LIVE (not
        # reaped) when the pass runs, so beat_gap — not the lease
        # machinery — is what notices it
        fleet_b = ServeFleet([SIZE], classes=2, policy=_policy(30.0),
                             check_interval_s=0.05)
        addr_b = fleet_b.start()
        killed_wid = "w-k1"
        workers_b = {}
        for wid in ("w-k0", killed_wid):
            workers_b[wid] = _spawn_worker(wid, addr_b, delay_ms=0.0)
            procs.append(workers_b[wid])
            if not _await_spread(fleet_b, list(workers_b)):
                raise RuntimeError(
                    f"phase B join never gave {wid!r} a partition: "
                    f"{_holder_map(fleet_b)}"
                )
        assert _stable_holders(fleet_b, 2), \
            "phase B fleet never spread 2 partitions"
        fo_b = fleet_b.fleet_obs
        assert _poll(
            lambda: all(
                rec["beats"] >= 2
                for rec in fo_b.worker_state().values()
            ) and len(fo_b.worker_state()) >= 2,
            20.0,
        ), "phase B workers never beat"
        workers_b[killed_wid].kill()
        workers_b[killed_wid].wait(timeout=10)
        time.sleep(1.2)  # > beat_gap bound (4 x 0.2s beat interval)
        gap_fired = fleet_b.fleet_obs_pass()
        gap_repeat = fleet_b.fleet_obs_pass()  # latched: must be quiet
        _progress(f"beat_gap pass: {[a['anomaly'] for a in gap_fired]}"
                  f", repeat: {[a['anomaly'] for a in gap_repeat]}")
        workers_b_state = fo_b.worker_state()
        beat_errors_b = fo_b.metrics.errors
        workers_b["w-k0"].send_signal(signal.SIGINT)
        workers_b["w-k0"].wait(timeout=20)
        fleet_b.close()
    finally:
        cleanup_workers()

    report = {
        "schema": FLEET_OBS_REPORT_SCHEMA,
        "config": {
            "image_size": SIZE,
            "phase_a_workers": 3,
            "phase_b_workers": 2,
            "hb_interval_s": 0.2,
            "requests_per_class": per_class,
            "slow_delay_ms": 120.0,
            "fast_delay_ms": 10.0,
            "slow_worker": slow_wid,
            "killed_worker": killed_wid,
        },
        "workers": {**report_a["workers"], **workers_b_state},
        "merged": report_a["merged"],
        "per_worker": report_a["per_worker"],
        "reconciliation": report_a["reconciliation"],
        "trace": report_a["trace"],
        "chains": {"total": len(chains), "complete": complete},
        "anomalies": {
            "calm": calm_fired,
            "outlier": outlier_fired,
            "beat_gap": gap_fired,
            "beat_gap_repeat": gap_repeat,
        },
        "beat_errors": report_a["beat_errors"] + beat_errors_b,
        "overhead": {
            "disabled_ns_per_check": round(disabled_ns, 1),
            "check_sites_per_request": _OBS_SITES_PER_REQUEST,
            "baseline_request_ms": round(base_req_ms, 3),
            "overhead_disabled_pct": round(overhead_pct, 6),
        },
        "wall_s": round(time.perf_counter() - wall0, 1),
    }
    report["checks"] = {
        "span_chain_complete": bool(complete >= 1),
        "metrics_reconciled": report_a["reconciliation"]["exact"]
        is True,
        "stitched_monotone": bool(
            report_a["trace"]["monotone"] is True
            and report_a["trace"]["events"] > 0
            and report_a["trace"]["tracks"] >= 4
        ),
        "slow_worker_exact": bool(
            [a["anomaly"] for a in outlier_fired]
            == ["worker_outlier_latency"]
            and outlier_fired[0]["evidence"]["worker"] == slow_wid
        ),
        "beat_gap_exact": bool(
            [a["anomaly"] for a in gap_fired] == ["beat_gap"]
            and gap_fired[0]["evidence"]["worker"] == killed_wid
            and gap_repeat == []
        ),
        "calm_quiet": calm_fired == [],
        "overhead_ok": bool(overhead_pct < 1.0),
    }
    problems = validate_fleet_obs_report(report)
    if problems:  # self-check: the emitted document must validate
        report["validator_problems"] = problems

    ok = all(report["checks"].values()) and not problems
    cancel_watchdog()  # before the success print: no success-then-watchdog
    line = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    if not ok:
        failed = [k for k, v in report["checks"].items() if not v]
        _progress(f"FAILED checks: {failed} problems={problems}")
        return 1
    _progress("all checks passed")
    return 0


def main(argv=None) -> int:
    """One fleet_obs_report/v1 JSON line on stdout, success or not:
    the shared bench_guard funnels wedges and crashes into a
    contractual error record."""
    from tmr_tpu.diagnostics import FLEET_OBS_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": FLEET_OBS_REPORT_SCHEMA,
                        "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

"""Chaos probe for the elastic serve fleet (tmr_tpu/serve/fleet.py).

The chaos_probe --elastic story applied to SERVING: drive a fleet of
stub-engine worker processes through the three failure modes the lease
discipline must survive, and prove the exactly-once accounting holds.
Prints ONE ``elastic_serve_report/v1`` JSON document (schema + validator
in tmr_tpu/diagnostics.py):

- **kill** — two workers split the traffic partitions; one is
  kill -9'd MID-BATCH. Its partition reassigns under epoch+1
  (``worker_exit``), the in-flight requests re-submit to the survivor,
  and every future ends terminal: ``offered == completed + rejected +
  shed + errors`` EXACTLY (probe-side future tallies AND fleet-side
  counters), zero double-served request ids, every completed result
  carrying its own image's stub signature (crossed wires would show).
- **fence** — a lone SLOW worker is SIGSTOPped past the lease TTL: the
  partition revokes (``stale_heartbeat``), and on SIGCONT the worker's
  already-running computation finishes and sends a result under the
  REVOKED epoch — the front door's commit fence rejects it (counted
  ``fenced_results``, with a lease-level ``commit`` fence record), the
  re-leased epoch serves the request exactly once.
- **recruit** — one worker at capacity is offered a 3× spike: sustained
  queue saturation RECRUITS a second worker through the spawner
  (``fleet.recruit``), a ``scale_out`` rebalance hands it real
  partitions, the spike is absorbed with zero rejections — and the
  degrade ladder (auto mode) never leaves level 0, because scale-out is
  elected BEFORE degradation sees an anomaly.

Rebalance latency (revocation → re-grant) is recorded per phase and
checked against a bound derived from the lease TTL.

Usage:  python scripts/elastic_serve_probe.py [--tiny] [--out FILE]

Fast (seconds, numpy stub engines, CPU): rides tier-1 via
tests/test_elastic_serve_probe.py. One-JSON-line contract via
bench_guard. ``scripts/bench_trend.py --fleet`` rc-gates on the
report's zero-double-served and reconciliation fields.
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


def _progress(msg: str) -> None:
    print(f"[elastic_serve_probe] {msg}", file=sys.stderr, flush=True)


def _images(n: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((SIZE, SIZE, 3)).astype(np.float32)
            for _ in range(n)]


def _spawn_worker(wid: str, address, delay_ms: float,
                  batch: int = 2) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TMR_FAULTS", None)  # the process gauntlet runs fault-free
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve_fleet.py"),
         "worker", "--coordinator", f"{address[0]}:{address[1]}",
         "--worker_id", wid, "--engine", "stub",
         "--delay_ms", str(delay_ms), "--batch", str(batch)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _poll(predicate, timeout_s: float, interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval_s)
    return predicate()


def _policy():
    from tmr_tpu.parallel.leases import LeasePolicy

    return LeasePolicy(
        lease_ttl_s=1.0, hb_interval_s=0.2, check_interval_s=0.05,
        straggler_factor=0.0, max_reassigns=1_000_000_000,
        resource_fail_workers=1_000_000_000,
    )


def _await_holders(fleet, want: int, timeout_s: float = 30.0) -> bool:
    """Wait until ``want`` partitions have a holder."""
    return bool(_poll(
        lambda: sum(
            1 for rec in fleet.state()["partitions"].values()
            if rec["holder"] is not None
        ) >= want,
        timeout_s,
    ))


def _collect(futs, imgs, timeout_s: float = 120.0):
    """Drain futures into probe-side outcome tallies + signature check."""
    from tmr_tpu.serve.admission import RejectedError
    from tmr_tpu.serve.fleet import stub_signature

    outcomes = {"completed": 0, "rejected": 0, "shed": 0, "errors": 0}
    signatures_ok = True
    terminal = True
    for im, fut in zip(imgs, futs):
        try:
            r = fut.result(timeout=timeout_s)
        except RejectedError as e:
            if e.cause in ("deadline", "shutdown"):
                outcomes["shed"] += 1
            else:
                outcomes["rejected"] += 1
            continue
        except Exception:
            outcomes["errors"] += 1
            continue
        outcomes["completed"] += 1
        if float(r["scores"][0, 0]) != stub_signature(im):
            signatures_ok = False
    terminal = all(f.done() for f in futs)
    return outcomes, signatures_ok, terminal


def _phase_doc(name: str, fleet, offered: int, outcomes: dict,
               extra: dict) -> dict:
    doc = {
        "name": name,
        "offered": offered,
        "outcomes": outcomes,
        "fleet": fleet.report(),
        **extra,
    }
    acc = doc["fleet"]["accounting"]
    doc["accounting_matches_probe"] = bool(
        acc["offered"] == offered
        and all(acc[k] == outcomes[k] for k in outcomes)
    )
    return doc


def _run(cancel_watchdog, argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="accepted for CLI symmetry (the probe is "
                         "already tiny: stub engines, no XLA)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from tmr_tpu.diagnostics import (
        ELASTIC_SERVE_REPORT_SCHEMA,
        validate_elastic_serve_report,
    )
    from tmr_tpu.serve.degrade import DegradeController
    from tmr_tpu.serve.fleet import ServeFleet

    wall0 = time.perf_counter()
    policy = _policy()
    rebalance_bound_s = policy.lease_ttl_s + 4.0
    phases = []
    workers: list = []

    def cleanup_workers():
        for proc in workers:
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
        workers.clear()

    # ---------------- phase 1: kill -9 a serve worker mid-batch
    _progress("phase kill: 2 workers, one kill -9'd mid-batch")
    fleet = ServeFleet([SIZE], classes=2, policy=policy,
                       check_interval_s=0.05, max_resubmits=4)
    address = fleet.start()
    workers[:] = [_spawn_worker(f"k{i}", address, delay_ms=60.0)
                  for i in range(2)]
    both_held = _await_holders(fleet, 2)
    # identify the two holders (post scale-out rebalance both workers
    # hold one partition each)
    holders = {
        rec["holder"][0] for rec in fleet.state()["partitions"].values()
        if rec["holder"]
    }
    imgs = _images(24, seed=1)
    futs = [fleet.submit(im, EX, priority=i % 2)
            for i, im in enumerate(imgs)]
    time.sleep(0.25)  # several requests now mid-batch on each worker
    victim_wid = sorted(holders)[0] if holders else "k0"
    victim = workers[int(victim_wid[1])]
    os.kill(victim.pid, signal.SIGKILL)
    _progress(f"killed worker {victim_wid} (pid {victim.pid})")
    outcomes, sigs_ok, terminal = _collect(futs, imgs)
    reassigned = _poll(
        lambda: any(r["cause"] == "worker_exit"
                    for r in fleet.state()["reassignments"]),
        10.0,
    )
    time.sleep(0.3)  # let any straggling late results commit (fenced)
    kill_doc = _phase_doc("kill", fleet, len(imgs), outcomes, {
        "both_workers_held": bool(both_held),
        "signatures_ok": bool(sigs_ok),
        "futures_terminal": bool(terminal),
        "worker_exit_reassigned": bool(reassigned),
        "resubmitted": fleet.counters()["resubmitted"],
    })
    phases.append(kill_doc)
    fleet.close()
    cleanup_workers()
    _progress(f"kill outcomes: {outcomes}")

    # -------- phase 2: SIGSTOP past the TTL, fenced late result
    _progress("phase fence: lone slow worker SIGSTOPped past the TTL")
    fleet = ServeFleet([SIZE], classes=1, policy=policy,
                       check_interval_s=0.05, max_resubmits=6)
    address = fleet.start()
    workers[:] = [_spawn_worker("f0", address, delay_ms=1500.0, batch=1)]
    _await_holders(fleet, 1)
    imgs = _images(1, seed=2)
    futs = [fleet.submit(imgs[0], EX)]
    time.sleep(0.4)  # routed; the 1.5 s stub call is now running
    os.kill(workers[0].pid, signal.SIGSTOP)
    revoked = _poll(
        lambda: any(r["cause"] == "stale_heartbeat"
                    for r in fleet.state()["reassignments"]),
        10.0,
    )
    os.kill(workers[0].pid, signal.SIGCONT)
    _progress("SIGCONT; awaiting the fenced late result + re-serve")
    outcomes, sigs_ok, terminal = _collect(futs, imgs)
    fenced = _poll(
        lambda: fleet.counters()["fenced_results"] >= 1, 10.0,
    )
    fence_doc = _phase_doc("fence", fleet, len(imgs), outcomes, {
        "stale_heartbeat_revoked": bool(revoked),
        "fenced_late_result": bool(fenced),
        "signatures_ok": bool(sigs_ok),
        "futures_terminal": bool(terminal),
    })
    phases.append(fence_doc)
    fleet.close()
    cleanup_workers()
    _progress(f"fence outcomes: {outcomes} fenced={fenced}")

    # ------------- phase 3: recruitment absorbs a 3x spike
    _progress("phase recruit: 1 worker at capacity, 3x spike")
    spawn_counter = {"n": 0}

    def spawner(i: int) -> None:
        spawn_counter["n"] += 1
        workers.append(
            _spawn_worker(f"r{i + 1}", address, delay_ms=10.0)
        )

    fleet = ServeFleet(
        [SIZE], classes=2, policy=policy, check_interval_s=0.1,
        max_resubmits=4, spawner=spawner, saturation_pending=6,
        recruit_passes=2, recruit_grace=20, max_workers=3,
        degrade=DegradeController(mode="auto"),
    )
    address = fleet.start()
    workers[:] = [_spawn_worker("r0", address, delay_ms=50.0)]
    _await_holders(fleet, 2)
    workers_before = 1
    # capacity with one worker ~ batch/delay = 2/0.05 = 40 req/s;
    # offer ~3x for ~1.5 s
    imgs = _images(90, seed=3)
    futs = []
    period = 1.0 / 120.0
    t0 = time.perf_counter()
    for i, im in enumerate(imgs):
        target = t0 + i * period
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        futs.append(fleet.submit(im, EX, priority=i % 2))
    outcomes, sigs_ok, terminal = _collect(futs, imgs)
    rec = fleet.report()
    recruit_doc = _phase_doc("recruit", fleet, len(imgs), outcomes, {
        "signatures_ok": bool(sigs_ok),
        "futures_terminal": bool(terminal),
        "workers_before": workers_before,
        "workers_after": workers_before + spawn_counter["n"],
        "recruit_rounds": rec["recruitment"]["rounds"],
        "scale_out_rebalanced": any(
            r["cause"] == "scale_out" for r in rec["reassignments"]
        ),
        "degrade_level": rec["degrade"]["level"],
        "degrade_max_seen": rec["degrade"]["max_seen"],
    })
    phases.append(recruit_doc)
    fleet.close()
    cleanup_workers()
    _progress(f"recruit outcomes: {outcomes} "
              f"rounds={rec['recruitment']['rounds']} "
              f"degrade_max={rec['degrade']['max_seen']}")

    # ------------------------------------------------- combined document
    keys = ("offered", "completed", "rejected", "shed", "errors",
            "resubmitted", "fenced_results", "late_results",
            "double_served")
    combined = {
        k: sum(p["fleet"]["accounting"][k] for p in phases)
        for k in keys
    }
    max_rebalance = max(
        p["fleet"]["rebalance"]["max_latency_s"] for p in phases
    )
    rebalance_count = sum(
        p["fleet"]["rebalance"]["count"] for p in phases
    )
    report = {
        "schema": ELASTIC_SERVE_REPORT_SCHEMA,
        "config": {
            "image_size": SIZE,
            "lease_ttl_s": policy.lease_ttl_s,
            "hb_interval_s": policy.hb_interval_s,
            "phases": [p["name"] for p in phases],
        },
        "phases": phases,
        "accounting": combined,
        "rebalance": {
            "count": rebalance_count,
            "max_latency_s": max_rebalance,
            "bound_s": rebalance_bound_s,
            "bounded": bool(max_rebalance <= rebalance_bound_s),
        },
        "recruitment": {
            "rounds": int(recruit_doc["recruit_rounds"]),
            "workers_before": int(recruit_doc["workers_before"]),
            "workers_after": int(recruit_doc["workers_after"]),
            "degrade_level": int(recruit_doc["degrade_level"]),
            "degrade_max_seen": int(recruit_doc["degrade_max_seen"]),
        },
        "checks": {
            "futures_terminal": all(
                p["futures_terminal"] for p in phases
            ),
            "zero_double_served": combined["double_served"] == 0,
            "accounting_exact_probe": all(
                p["offered"] == sum(
                    p["outcomes"][k] for k in
                    ("completed", "rejected", "shed", "errors")
                ) for p in phases
            ),
            "accounting_exact_fleet": all(
                p["accounting_matches_probe"] for p in phases
            ),
            "results_correct": all(
                p["signatures_ok"] for p in phases
            ),
            "worker_exit_reassigned": bool(
                kill_doc["worker_exit_reassigned"]
            ),
            "fenced_late_result": bool(fence_doc["fenced_late_result"]),
            "rebalance_bounded": bool(
                max_rebalance <= rebalance_bound_s
            ),
            "recruitment_absorbed": bool(
                recruit_doc["recruit_rounds"] >= 1
                and recruit_doc["workers_after"]
                > recruit_doc["workers_before"]
                and recruit_doc["outcomes"]["completed"]
                == recruit_doc["offered"]
            ),
            "degrade_level0": bool(
                recruit_doc["degrade_max_seen"] == 0
            ),
        },
        "wall_s": round(time.perf_counter() - wall0, 1),
    }
    problems = validate_elastic_serve_report(report)
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
    """One elastic_serve_report/v1 JSON line on stdout, success or not:
    the shared bench_guard funnels wedges and crashes into a
    contractual error record."""
    from tmr_tpu.diagnostics import ELASTIC_SERVE_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": ELASTIC_SERVE_REPORT_SCHEMA,
                        "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

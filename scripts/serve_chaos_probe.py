"""Chaos gauntlet for the replicated gallery fleet
(tmr_tpu/serve/gallery_fleet.py): prove ZERO pattern loss.

The elastic_serve_probe story applied to gallery STATE: pattern shards
are leased fleet resources (primary + R-1 mirrors, write-ahead journal
on the coordinator), and this probe drives subprocess stub-bank workers
through every serve-tier fault point, checking the ledger closes. One
``serve_chaos_report/v1`` JSON line (schema + validator in
tmr_tpu/diagnostics.py):

- **fanout_parity** — three workers lease four shards; patterns
  register with ``copies == 2`` acknowledged; the fan-out client's
  merged search is BYTE-identical to one StubGalleryBank holding every
  pattern (the stub's detections depend only on (exemplars, frame), so
  crossed shards / stale payloads / codec loss all show as mismatches).
- **kill** — repeated rounds: register a FRESH pattern, then kill -9
  the primary holding its shard before the ink dries. The journal +
  replica copies re-materialize the shard on the promoted holder
  (adopt-or-push) and replication heals back to R; every pattern ever
  acknowledged searches clean and byte-identical afterwards.
- **degrade_label** — a ``serve.link`` fault severs exactly one
  shard's first fan-out: precisely that shard's patterns come back as
  counted ``degrade_steps: ["partition_unavailable"]`` results (all
  other patterns still byte-identical), and the NEXT search heals.
- **replica_corrupt** — a ``gallery.replica:corrupt=1`` schedule
  corrupts the first replica push; the worker's digest check rejects
  it (counted, never installed) and the retry lands clean: the
  registration still acks ``copies == 2``.
- **journal_wal** — a ``journal`` raise refuses the write-ahead marker
  BEFORE the catalog/ack: the pattern is nowhere (no partial state),
  and the retry after clearing registers durably.
- **beat_env** — a worker subprocess is spawned with
  ``TMR_FAULTS="gallery.beat:latency=..."`` in its env (the
  install_from_env contract): its delayed beats blow the lease TTL,
  the shard promotes onto the clean replica (``stale_heartbeat``), and
  the worker's own ``gstate`` shows the schedule active and fired —
  chaos schedules reach lease-held serve processes.
- **bulk_ingest** (``--patterns-per-shard N``, default 0 = skipped) —
  ``N * shards`` patterns stream through the coordinator's bulk-ingest
  sink (``fleet.bulk_sink()`` + ``bulk_register``: journal-first
  feature ops, one ``gflush`` distribution) and must come back from a
  fan-out search byte-identical to the single-bank oracle, fully
  replicated, and survive the final journal-recovery check like any
  register() pattern — the PR 17 gauntlet re-run at catalog scale.
- **final_sweep** — every acknowledged registration (both fleets) must
  search clean + byte-identical, and a cold coordinator restart over
  the same journal directory recovers the exact catalog.

Usage:  python scripts/serve_chaos_probe.py [--tiny] [--out FILE]
        [--patterns-per-shard N]

Fast (seconds, numpy stub banks, CPU): rides tier-1 via
tests/test_serve_chaos_probe.py. One-JSON-line contract via
bench_guard. ``scripts/bench_trend.py --chaos`` rc-gates fail-closed
on the zero-loss / all-faults-accounted invariants.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 16
SHARDS = 4
WORKERS = 3
REPLICAS = 2
BASE_PATTERNS = 8


def _progress(msg: str) -> None:
    print(f"[serve_chaos_probe] {msg}", file=sys.stderr, flush=True)


def _poll(predicate, timeout_s: float, interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval_s)
    return None


def _policy():
    from tmr_tpu.parallel.leases import LeasePolicy

    return LeasePolicy(
        lease_ttl_s=1.0, hb_interval_s=0.2, check_interval_s=0.05,
        straggler_factor=0.0, max_reassigns=1_000_000_000,
        resource_fail_workers=1_000_000_000,
    )


def _frame(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((SIZE, SIZE, 3)).astype(np.float32)


def _exemplars(name: str) -> np.ndarray:
    """Deterministic per-name exemplars (process-stable seed)."""
    seed = int.from_bytes(
        hashlib.sha256(name.encode()).digest()[:4], "big"
    )
    rng = np.random.default_rng(seed)
    return rng.standard_normal((2, 4)).astype(np.float32)


def _pattern_names(n: int, n_shards: int, prefix: str = "pat") -> list:
    """``n`` deterministic names covering EVERY shard at least once
    (shard placement is content-hashed, so names are picked for it)."""
    from tmr_tpu.serve.gallery_fleet import shard_of

    names: list = []
    covered: set = set()
    i = 0
    while len(names) < n or len(covered) < n_shards:
        name = f"{prefix}{i:03d}"
        i += 1
        shard = shard_of(name, n_shards)
        if len(names) < n:
            names.append(name)
            covered.add(shard)
        elif shard not in covered:
            names.append(name)
            covered.add(shard)
    return names


def _spawn_gallery_worker(wid: str, address,
                          env_faults=None) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TMR_FAULTS", None)
    if env_faults:  # the install_from_env delivery path under test
        env["TMR_FAULTS"] = env_faults
        env["TMR_FAULTS_SEED"] = "0"
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve_fleet.py"),
         "gallery-worker", "--coordinator", f"{address[0]}:{address[1]}",
         "--worker_id", wid, "--bank", "stub",
         "--image_size", str(SIZE)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _dets_equal(got: dict, want: dict) -> bool:
    """Byte-exact detection equality (dtype + shape + buffer)."""
    if set(got) != set(want):
        return False
    for key, w in want.items():
        g = got.get(key)
        if isinstance(w, np.ndarray):
            if not (isinstance(g, np.ndarray) and g.dtype == w.dtype
                    and g.shape == w.shape
                    and g.tobytes() == w.tobytes()):
                return False
        elif g != w:
            return False
    return True


def _clean_and_exact(results: dict, reference: dict) -> bool:
    """Every reference pattern present, un-degraded, byte-identical."""
    if set(results) != set(reference):
        return False
    return all(
        "degrade_steps" not in results[name]
        and _dets_equal(results[name], reference[name])
        for name in reference
    )


def _fired_count(point: str) -> int:
    from tmr_tpu.utils import faults

    return sum(1 for rec in faults.fired() if rec["point"] == point)


def _run(cancel_watchdog, argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="fewer kill rounds / frames (tier-1 budget)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--patterns-per-shard", type=int, default=0,
                    help="bulk-ingest this many patterns per shard "
                         "through the streamed sink (0 = skip phase)")
    args = ap.parse_args(argv)

    from tmr_tpu.diagnostics import (
        SERVE_CHAOS_REPORT_SCHEMA,
        validate_serve_chaos_report,
    )
    from tmr_tpu.parallel.leases import oneshot
    from tmr_tpu.serve.gallery_fleet import (
        GalleryFleet,
        StubGalleryBank,
        bulk_register,
    )
    from tmr_tpu.utils import faults

    kill_rounds = 1 if args.tiny else 2
    parity_frames = 2 if args.tiny else 3

    phases = []
    procs = {}  # wid -> Popen
    workers_killed = 0
    reference = StubGalleryBank(image_size=SIZE)  # the single-bank oracle
    ledger = []  # every ACKNOWLEDGED main-fleet registration
    injected = []  # the fault ledger: point/schedule/fired/accounted
    observed = {}

    def cleanup():
        faults.clear()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def spawn(fleet, wid, env_faults=None):
        procs[wid] = _spawn_gallery_worker(wid, fleet.address,
                                           env_faults=env_faults)

    def kill(wid):
        nonlocal workers_killed
        proc = procs.get(wid)
        if proc is not None and proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
            workers_killed += 1

    def register(fleet, name):
        ex = _exemplars(name)
        ack = fleet.register(name, ex)
        reference.register(name, ex)
        ledger.append(name)
        return ack

    def all_held(fleet):
        return all(fleet.holder_for(s) is not None
                   for s in range(fleet.n_shards))

    def search_clean(client) -> bool:
        return _clean_and_exact(client.search(_frame(99)),
                                reference.search(_frame(99)))

    tmp = tempfile.TemporaryDirectory(prefix="serve_chaos_")
    fleet = GalleryFleet(
        SHARDS, policy=_policy(), replicas=REPLICAS,
        journal_dir=os.path.join(tmp.name, "journal"),
    )
    fleet.start()
    mini = None
    try:
        # ---------------------------------------- phase 1: fan-out parity
        _progress(f"spawning {WORKERS} stub gallery workers")
        for i in range(WORKERS):
            spawn(fleet, f"w{i}")
        if not _poll(lambda: all_held(fleet), 30.0):
            raise RuntimeError("gallery workers never leased all shards")
        names = _pattern_names(BASE_PATTERNS, SHARDS)
        acks = [register(fleet, name) for name in names]
        replicated = all(
            a["copies"] >= REPLICAS and not a["under_replicated"]
            for a in acks
        )
        client = fleet.client()
        parity = replicated
        for f in range(parity_frames):
            img = _frame(f)
            if not _clean_and_exact(client.search(img),
                                    reference.search(img)):
                parity = False
        phases.append({
            "name": "fanout_parity", "ok": bool(parity),
            "patterns": len(names), "frames": parity_frames,
            "copies": [a["copies"] for a in acks],
        })
        _progress(f"fanout parity: ok={parity}")

        # ------------------------------- phase 2: repeated primary kills
        kills_ok = True
        for r in range(kill_rounds):
            fresh = f"fresh{r:02d}"
            ack = register(fleet, fresh)
            resolved = fleet.holder_for(ack["shard"])
            victim = resolved[0] if resolved else None
            if victim is None or victim not in procs:
                kills_ok = False
                break
            _progress(f"kill round {r}: registered {fresh!r}, "
                      f"killing primary {victim!r}")
            kill(victim)
            recruit = f"w{WORKERS + r}"
            spawn(fleet, recruit)  # keep the fleet elastic
            healed = _poll(
                lambda: recruit in fleet._svc.live_workers()  # noqa: B023
                and all_held(fleet) and search_clean(client), 30.0,
            )
            if not healed:
                kills_ok = False
                break
        phases.append({
            "name": "kill", "ok": bool(kills_ok),
            "rounds": kill_rounds, "workers_killed": workers_killed,
            "promotions": fleet.counters()["promotions"],
        })
        _progress(f"kill rounds: ok={kills_ok}")

        # ------------------------- phase 3: degrade labeling + healing
        plan = fleet.shard_map()
        target = max(plan, key=lambda s: len(plan[s]))
        schedule = f"serve.link:shard={target}:attempts=1:raise=OSError"
        faults.configure(schedule, seed=0)
        fresh_client = fleet.client()  # attempt counters start at 0
        img = _frame(7)
        want = reference.search(img)
        first = fresh_client.search(img)
        degraded = {
            name for name, dets in first.items()
            if dets.get("degrade_steps") == ["partition_unavailable"]
        }
        exact_label = (
            degraded == set(plan[target])
            and all(_dets_equal(first[n], want[n])
                    for n in want if n not in degraded)
        )
        second = fresh_client.search(img)
        heals = _clean_and_exact(second, want)
        link_fired = _fired_count("serve.link")
        link_accounted = fresh_client.counters()["link_failures"]
        observed["serve.link"] = link_fired
        injected.append({
            "point": "serve.link", "schedule": schedule,
            "fired": int(link_fired), "accounted": int(link_accounted),
        })
        faults.clear()
        phases.append({
            "name": "degrade_label",
            "ok": bool(exact_label and heals and link_fired),
            "target_shard": int(target),
            "degraded_patterns": sorted(degraded),
            "heals": bool(heals),
        })
        _progress(f"degrade labeling: exact={exact_label} heals={heals}")

        # --------------------- phase 4: corrupt replica push, rejected
        schedule = "gallery.replica:corrupt=1:attempts=1"
        faults.configure(schedule, seed=0)
        before = fleet.counters()["replica_corrupt"]
        ack = register(fleet, "healme")
        corrupt_seen = fleet.counters()["replica_corrupt"] - before
        replica_fired = _fired_count("gallery.replica")
        faults.clear()
        rejected = 0
        for wid in fleet._svc.live_workers():
            addr = fleet._addr_of(wid)
            if addr is None:
                continue
            try:
                st = oneshot(addr, {"op": "gstate"}, timeout=10.0)
                rejected += int(st["counters"]["corrupt_rejected"])
            except Exception:
                pass
        replication_recovered = bool(
            ack["copies"] >= REPLICAS and not ack["under_replicated"]
            and search_clean(client)
        )
        observed["gallery.replica"] = replica_fired
        injected.append({
            "point": "gallery.replica", "schedule": schedule,
            "fired": int(replica_fired),
            "accounted": int(min(corrupt_seen, rejected)),
        })
        phases.append({
            "name": "replica_corrupt",
            "ok": bool(replication_recovered and corrupt_seen >= 1
                       and rejected >= 1),
            "coordinator_counted": int(corrupt_seen),
            "worker_rejected": int(rejected),
            "copies": ack["copies"],
        })
        _progress(f"replica corrupt: rejected={rejected} "
                  f"healed_copies={ack['copies']}")

        # ------------------ phase 5: journal write-ahead ordering (WAL)
        schedule = "journal:raise=OSError"
        faults.configure(schedule, seed=0)
        refused = False
        try:
            fleet.register("walprobe", _exemplars("walprobe"))
        except OSError:
            refused = True
        journal_fired = _fired_count("journal")
        nowhere = "walprobe" not in fleet.patterns()
        faults.clear()
        retry = register(fleet, "walprobe")
        wal_ok = bool(refused and nowhere and journal_fired
                      and retry["copies"] >= REPLICAS)
        observed["journal"] = journal_fired
        injected.append({
            "point": "journal", "schedule": schedule,
            "fired": int(journal_fired),
            "accounted": int(refused and nowhere),
        })
        phases.append({
            "name": "journal_wal", "ok": wal_ok,
            "refused": refused, "absent_after_refusal": nowhere,
        })
        _progress(f"journal WAL ordering: ok={wal_ok}")

        # --------- phase 6: env-delivered beat fault on a mini fleet
        # (spawned worker gets TMR_FAULTS via its environment — the
        # install_from_env contract — and its delayed beats blow the
        # lease TTL: stale_heartbeat promotion, zero loss)
        schedule = "gallery.beat:latency=1.5"
        mini = GalleryFleet(
            2, policy=_policy(), replicas=REPLICAS,
            journal_dir=os.path.join(tmp.name, "mini_journal"),
        )
        mini.start()
        mini_reference = StubGalleryBank(image_size=SIZE)
        spawn(mini, "beatw", env_faults=schedule)
        beat_holds = bool(_poll(
            lambda: all(
                (mini.holder_for(s) or (None,))[0] == "beatw"
                for s in range(2)
            ),
            30.0,
        ))
        spawn(mini, "calm")
        mini_names = []
        for name in _pattern_names(2, 2, prefix="mini"):
            ex = _exemplars(name)
            mini.register(name, ex)
            mini_reference.register(name, ex)
            mini_names.append(name)

        def beat_stale():
            return any(
                r["cause"] == "stale_heartbeat"
                for r in mini.state()["reassignments"]
            )

        stale_seen = bool(_poll(beat_stale, 30.0))
        beat_fired = 0
        env_active = False
        addr = mini._addr_of("beatw")
        if addr is not None:
            try:
                st = oneshot(addr, {"op": "gstate"}, timeout=10.0)
                beat_fired = int(st["faults_fired"])
                env_active = bool(st["faults_active"])
            except Exception:
                pass
        kill("beatw")
        mini_client = mini.client()

        def mini_clean():
            if not all((mini.holder_for(s) or (None,))[0] == "calm"
                       for s in range(2)):
                return False
            img = _frame(5)
            return _clean_and_exact(mini_client.search(img),
                                    mini_reference.search(img))

        mini_healed = bool(_poll(mini_clean, 30.0))
        env_delivered = bool(env_active and beat_fired >= 1)
        stale_count = sum(
            1 for r in mini.state()["reassignments"]
            if r["cause"] == "stale_heartbeat"
        )
        observed["gallery.beat"] = beat_fired
        injected.append({
            "point": "gallery.beat", "schedule": schedule,
            "fired": int(beat_fired), "accounted": int(stale_count),
        })
        phases.append({
            "name": "beat_env",
            "ok": bool(beat_holds and stale_seen and env_delivered
                       and mini_healed),
            "stale_reassignments": int(stale_count),
            "worker_faults_fired": int(beat_fired),
            "worker_faults_active": env_active,
            "healed": mini_healed,
        })
        _progress(f"env beat fault: delivered={env_delivered} "
                  f"stale={stale_count} healed={mini_healed}")

        # ------ phase 6.5: streamed bulk ingest at catalog scale
        # (opt-in: the coordinator's feature-sink bulk path — journal
        # -first streaming, one gflush distribution — must land every
        # pattern byte-identical and fully replicated, and those
        # patterns then ride the final sweep + journal recovery like
        # any register() pattern)
        if args.patterns_per_shard > 0:
            total = SHARDS * args.patterns_per_shard
            _progress(f"bulk ingest: streaming {total} patterns")
            t0 = time.perf_counter()
            bulk_pats = [(f"blk{i:06d}", _exemplars(f"blk{i:06d}"))
                         for i in range(total)]
            res = bulk_register(fleet.bulk_sink(), bulk_pats,
                                batch="chaos")
            wall = time.perf_counter() - t0
            bulk_names = []
            for name, ex in bulk_pats:
                reference.register(name, ex)
                ledger.append(name)
                bulk_names.append(name)
            img = _frame(21)
            got = client.search(img)
            want = reference.search(img)
            bulk_parity = all(
                name in got and "degrade_steps" not in got[name]
                and _dets_equal(got[name], want[name])
                for name in bulk_names
            )
            flush = res.get("flush") or {}
            bulk_ok = bool(
                res.get("ok") and res.get("streamed") == total
                and flush.get("under_replicated") == 0 and bulk_parity
            )
            phases.append({
                "name": "bulk_ingest", "ok": bulk_ok,
                "patterns": total,
                "streamed": int(res.get("streamed") or 0),
                "copies": int(flush.get("copies") or 0),
                "parity": bool(bulk_parity),
                "wall_s": round(wall, 3),
            })
            _progress(f"bulk ingest: ok={bulk_ok} "
                      f"wall={wall:.2f}s copies={flush.get('copies')}")

        # -------------------- phase 7: final sweep + journal recovery
        img = _frame(11)
        final = client.search(img)
        want = reference.search(img)
        lost = sorted(
            name for name in ledger
            if name not in final
            or "degrade_steps" in final[name]
            or not _dets_equal(final[name], want[name])
        )
        mini_final = mini_client.search(_frame(12))
        mini_want = mini_reference.search(_frame(12))
        mini_lost = sorted(
            name for name in mini_names
            if name not in mini_final
            or "degrade_steps" in mini_final[name]
            or not _dets_equal(mini_final[name], mini_want[name])
        )
        lost += mini_lost
        # a cold coordinator over the same WAL must recover the catalog
        reborn = GalleryFleet(
            SHARDS, policy=_policy(), replicas=REPLICAS,
            journal_dir=os.path.join(tmp.name, "journal"),
        )
        recovered = set(reborn.patterns()) == set(ledger)
        registered = len(ledger) + len(mini_names)
        survived = registered - len(lost)
        phases.append({
            "name": "final_sweep",
            "ok": bool(not lost and recovered),
            "registered": registered, "survived": survived,
            "journal_recovered": reborn.counters()["journal_recovered"],
        })
        _progress(f"final sweep: {survived}/{registered} survived, "
                  f"journal recovery exact={recovered}")
    finally:
        cleanup()
        if mini is not None:
            mini.close()
        fleet.close()
        tmp.cleanup()

    by_name = {p["name"]: p for p in phases}
    checks = {
        "zero_patterns_lost": bool(not lost),
        "fanout_byte_identical": bool(by_name["fanout_parity"]["ok"]),
        "all_faults_observed": bool(
            injected and all(rec["fired"] >= 1 for rec in injected)
        ),
        "all_faults_accounted": bool(
            injected and all(rec["accounted"] >= 1 for rec in injected)
        ),
        "degraded_exactly_labeled": bool(by_name["degrade_label"]["ok"]),
        "degrade_heals": bool(by_name["degrade_label"]["heals"]),
        "replication_recovered": bool(
            by_name["replica_corrupt"]["ok"] and by_name["kill"]["ok"]
        ),
        "env_schedule_delivered": bool(by_name["beat_env"]["ok"]),
    }
    if "bulk_ingest" in by_name:  # opt-in bulk-scale phase ran
        checks["bulk_ingest_ok"] = bool(by_name["bulk_ingest"]["ok"])
    doc = {
        "schema": SERVE_CHAOS_REPORT_SCHEMA,
        "config": {
            "shards": SHARDS, "workers": WORKERS,
            "replicas": REPLICAS, "patterns": registered,
            "tiny": bool(args.tiny),
            "patterns_per_shard": int(args.patterns_per_shard),
        },
        "phases": phases,
        "patterns": {
            "registered": registered,
            "survived": survived,
            "lost": lost,
        },
        "kills": {
            "rounds": kill_rounds,
            "workers_killed": workers_killed,
        },
        "faults": {
            "injected": injected,
            "observed": {k: int(v) for k, v in observed.items()},
        },
        "checks": checks,
    }
    problems = validate_serve_chaos_report(doc)
    if problems:  # self-check: the emitted document must validate
        doc["validator_problems"] = problems
    cancel_watchdog()  # before the success print: no success-then-watchdog
    line = json.dumps(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0 if (all(checks.values()) and not problems
                 and all(p["ok"] for p in phases)) else 1


def main(argv=None) -> int:
    """One serve_chaos_report/v1 JSON line on stdout, success or not:
    the shared bench_guard funnels wedges and crashes into a
    contractual error record."""
    from tmr_tpu.diagnostics import SERVE_CHAOS_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": SERVE_CHAOS_REPORT_SCHEMA,
                        "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

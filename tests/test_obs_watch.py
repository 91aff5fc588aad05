"""scripts/obs_watch.py: the flight_report/v1 contract.

One lean subprocess run at the obs_probe CPU smoke geometry (identical
program shapes, so the persistent XLA compile cache is shared between
the two probes and the tier-1 time budget pays the compile once):
asserts the ISSUE acceptance checks — finite per-program MFU with the
analytic-vs-cost_analysis FLOPs envelope, exactly-once anomaly firings
for the injected recompile storm and queue burst, a validating
ServeEngine.health() + heartbeat JSONL round-trip, and <1% disabled-mode
overhead. The watchdog error-record path is slow-marked (subprocess
compile time, no new coverage beyond the guard contract).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _probe_env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "TMR_FLIGHT", "TMR_TRACE")
    }
    env.update(
        JAX_PLATFORMS="cpu",
        TMR_BENCH_TINY="1",
        TMR_BENCH_SIZE="128",
        **extra,
    )
    return env


def test_obs_watch_tiny_smoke_meets_acceptance_checks(tmp_path):
    """The acceptance proof, end to end on CPU: one JSON line, valid
    flight_report/v1, finite per-program MFU whose analytic FLOPs agree
    with cost_analysis() within the 1.17x envelope, exactly-once
    anomaly firings, health + heartbeat round-trip, bounded disabled
    overhead."""
    out_file = tmp_path / "flight_report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_watch.py"),
         "--tiny", "--out", str(out_file)],
        env=_probe_env(), capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import validate_flight_report

    assert validate_flight_report(doc) == []
    assert "validator_problems" not in doc
    checks = doc["checks"]
    for name in ("mfu_valid", "mfu_finite", "flops_envelope_ok",
                 "health_valid", "heartbeat_roundtrip", "ring_recorded",
                 "calm_quiet", "storm_exact", "queue_exact",
                 "overhead_ok"):
        assert checks[name] is True, (name, checks)
    assert checks["flops_envelope_max_ratio"] <= 1.17
    assert doc["overhead"]["overhead_disabled_pct"] < 1.0
    # attribution: the serve workload's program appears with measured
    # (non-warmup) calls, a cost source, and a roofline verdict
    progs = doc["mfu"]["programs"]
    assert any(p["kind"] == "single" and p["calls"] >= 1 for p in progs)
    assert all(p["cost_source"] in ("xla", "analytic") for p in progs)
    # the anomaly records carry structured causes (kind + evidence)
    storm = doc["anomalies"]["recompile_storm"]
    assert [a["anomaly"] for a in storm] == ["recompile_storm"]
    assert storm[0]["evidence"]["key_change_events"] >= 3
    queue = doc["anomalies"]["queue_saturation"]
    assert [a["anomaly"] for a in queue] == ["queue_saturation"]
    # health doc: queue/cache/compile sections populated by a live engine
    health = doc["health"]
    assert health["counters"]["completed"] == doc["config"]["requests"]
    assert health["anomalies"] == []  # a healthy tiny run is quiet
    # the flight ring saw every request
    assert doc["ring"]["serve_requests"] >= doc["config"]["requests"]
    # --out wrote the same document; the heartbeat JSONL round-trips
    assert json.loads(out_file.read_text())["checks"] == checks
    hb_path = doc["heartbeat"]["path"]
    from tmr_tpu.diagnostics import validate_health_report

    hb_docs = [json.loads(l) for l in
               open(hb_path).read().splitlines() if l.strip()]
    assert len(hb_docs) >= 2
    assert all(validate_health_report(d) == [] for d in hb_docs)
    # progress goes to stderr, never stdout
    assert "[obs_watch]" in out.stderr


@pytest.mark.slow
def test_obs_watch_watchdog_emits_error_record(tmp_path):
    """A wedge yields the contractual one-line error record — still a
    valid flight_report/v1 document (the bench_guard pattern)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_watch.py"),
         "--tiny"],
        env=_probe_env(
            TMR_BENCH_ALARM="1",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"),
        ),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "watchdog" in rec["error"]

    from tmr_tpu.diagnostics import validate_flight_report

    assert validate_flight_report(rec) == []

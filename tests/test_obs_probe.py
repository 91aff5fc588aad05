"""scripts/obs_probe.py: the trace_report/v1 contract.

The smoke test runs the real probe in a subprocess at tiny CPU shapes in
a CLEAN env (no forced host-device count, like the serve_bench smoke) and
asserts the acceptance checks: all seven serve pipeline stages traced
with a consistent per-request trace ID, at least one compile event with
its key, Chrome-trace JSON round-trip, and disabled-mode overhead < 1%.
The validator tests pin the schema both ways.
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
        if k not in ("XLA_FLAGS", "TMR_TRACE")
    }
    env.update(
        JAX_PLATFORMS="cpu",
        TMR_BENCH_TINY="1",
        TMR_BENCH_SIZE="128",
        **extra,
    )
    return env


def _valid_doc():
    from tmr_tpu import obs
    from tmr_tpu.diagnostics import TRACE_REPORT_SCHEMA, TRACE_SERVE_STAGES

    stage = {"count": 6, "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0}
    return {
        "schema": TRACE_REPORT_SCHEMA,
        "device": "cpu",
        "config": {"image_size": 128, "batch": 2, "requests": 6},
        "serve": {"stages": {name: dict(stage)
                             for name in TRACE_SERVE_STAGES}},
        "map": {"stages": {"map.attempt": dict(stage)}},
        "compile_events": [
            {"kind": "single", "key": "(9, False)", "wall_s": 1.5,
             "cause": "cold"},
        ],
        "metrics": obs.MetricsRegistry().snapshot(),
        "overhead": {"disabled_ns_per_span": 300.0,
                     "overhead_disabled_pct": 0.001},
        "checks": {"stages_complete": True, "compile_event_recorded": True,
                   "trace_roundtrip": True, "overhead_ok": True},
    }


def test_validate_trace_report_accepts_valid_and_error_docs():
    from tmr_tpu.diagnostics import TRACE_REPORT_SCHEMA, validate_trace_report

    assert validate_trace_report(_valid_doc()) == []
    assert validate_trace_report(
        {"schema": TRACE_REPORT_SCHEMA, "error": "watchdog: ..."}
    ) == []


@pytest.mark.parametrize("mutate, fragment", [
    (lambda d: d.update(schema="bogus/v9"), "schema"),
    (lambda d: d.pop("metrics"), "metrics"),
    (lambda d: d["metrics"].update(schema="wrong"), "metrics"),
    (lambda d: d.pop("serve"), "serve"),
    (lambda d: d["serve"]["stages"]["serve.submit"].pop("p99_ms"), "p99_ms"),
    (lambda d: d["compile_events"][0].update(cause="weird"), "cause"),
    (lambda d: d.pop("overhead"), "overhead"),
    (lambda d: d["overhead"].pop("overhead_disabled_pct"),
     "overhead_disabled_pct"),
    (lambda d: d["checks"].pop("stages_complete"), "stages_complete"),
    (lambda d: d.update(error=""), "error"),
])
def test_validate_trace_report_rejects_broken_docs(mutate, fragment):
    from tmr_tpu.diagnostics import validate_trace_report

    doc = _valid_doc()
    mutate(doc)
    problems = validate_trace_report(doc)
    assert problems, f"expected a problem for {fragment}"
    assert any(fragment in p for p in problems), problems


def test_obs_probe_tiny_smoke_meets_acceptance_checks(tmp_path):
    """The acceptance proof, end to end on CPU: one JSON line, valid
    trace_report/v1, all seven serve stages traced under per-request
    trace IDs, a compile event with its key, bounded disabled overhead."""
    out_file = tmp_path / "trace_report.json"
    trace_file = tmp_path / "trace.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_probe.py"),
         "--tiny", "--out", str(out_file), "--trace-out", str(trace_file)],
        env=_probe_env(), capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import TRACE_SERVE_STAGES, validate_trace_report

    assert validate_trace_report(doc) == []
    assert "validator_problems" not in doc
    checks = doc["checks"]
    assert checks["stages_complete"] is True, checks
    assert checks["compile_event_recorded"] is True
    assert checks["map_retry_observed"] is True
    assert checks["trace_roundtrip"] is True
    assert checks["overhead_ok"] is True
    assert doc["overhead"]["overhead_disabled_pct"] < 1.0
    # every stage traced, count >= the workload's request count
    for name in TRACE_SERVE_STAGES:
        assert doc["serve"]["stages"][name]["count"] >= doc["serve"][
            "requests"
        ], name
    assert doc["serve"]["complete_request_traces"] >= 1
    # compile events carry their keys and a closed-vocabulary cause
    assert any(e["key"] for e in doc["compile_events"])
    # map section saw the injected retry
    assert doc["map"]["report_valid"] is True
    assert doc["map"]["stages"]["map.attempt"]["count"] >= 3
    assert "map.backoff" in doc["map"]["stages"]
    # the attached registry snapshot counts the compile events
    assert doc["metrics"]["counters"]["compile.total"] >= 1
    # --out wrote the same document; --trace-out wrote loadable JSON
    assert json.loads(out_file.read_text())["checks"] == checks
    chrome = json.loads(trace_file.read_text())
    assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
    # progress goes to stderr, never stdout
    assert "[obs_probe]" in out.stderr


@pytest.mark.slow
def test_obs_probe_watchdog_emits_error_record(tmp_path):
    """A wedge yields the contractual one-line error record — still a
    valid trace_report/v1 document (the bench_guard pattern)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_probe.py"),
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

    from tmr_tpu.diagnostics import validate_trace_report

    assert validate_trace_report(rec) == []

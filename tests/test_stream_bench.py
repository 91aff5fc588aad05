"""scripts/stream_bench.py: the stream_report/v1 contract.

The smoke test runs the real script in a subprocess at tiny CPU shapes
in a clean env with an ISOLATED autotune cache and asserts the
acceptance checks: backbone executions ≪ frames over the bursty
synthetic workload (the devtime program-table witness), frames/s
>= 1.5x the frame-independent baseline, every "changed" frame bitwise
the ordinary path, zero cross-stream hits, and every reused frame
labeled ``temporal_reuse``. The validator tests pin the schema both
ways, and the bench_trend ``--stream`` gate is pinned fail-closed.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_env(tmp_path, **extra):
    env = {
        k: v for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    env.update(
        JAX_PLATFORMS="cpu",
        TMR_BENCH_TINY="1",
        TMR_BENCH_SIZE="128",
        # isolate any autotune reads/writes from the user's real cache
        TMR_AUTOTUNE_CACHE=str(tmp_path / "autotune.json"),
        TMR_AUTOTUNE_SEED=str(tmp_path / "absent_seed.json"),
        **extra,
    )
    return env


def _valid_doc():
    from tmr_tpu.diagnostics import STREAM_REPORT_SCHEMA

    return {
        "schema": STREAM_REPORT_SCHEMA,
        "device": "cpu",
        "config": {"image_size": 128, "streams": 2,
                   "frames_per_stream": 8, "frames": 16, "delta": 0.02,
                   "seed": 0, "dtype": "float32"},
        "throughput": {"stream_frames_per_sec": 6.0,
                       "independent_frames_per_sec": 2.4,
                       "speedup": 2.5},
        "backbone": {"frames": 16, "executions": 8,
                     "baseline_by_program": {"single": 16},
                     "by_program": {"backbone": 4, "single": 4,
                                    "heads": 4}},
        "reuse": {"reused_frames": 12, "changed_frames": 2,
                  "first_frames": 2,
                  "expected": {"reused": 12, "changed": 2, "first": 2}},
        "exactness": {"changed_frames_checked": 4, "mismatches": 0,
                      "label_errors": 0},
        "isolation": {"cross_stream_hits": 0, "sessions": 2},
        "checks": {"backbone_amortized": True, "speedup_ok": True,
                   "changed_frames_exact": True,
                   "cross_stream_isolated": True, "reuse_labeled": True,
                   "verdicts_as_expected": True},
    }


def test_validate_stream_report_accepts_valid_and_error_docs():
    from tmr_tpu.diagnostics import (
        STREAM_REPORT_SCHEMA,
        validate_stream_report,
    )

    assert validate_stream_report(_valid_doc()) == []
    assert validate_stream_report(
        {"schema": STREAM_REPORT_SCHEMA, "error": "watchdog: ..."}
    ) == []


@pytest.mark.parametrize("mutate, fragment", [
    (lambda d: d.update(schema="bogus/v9"), "schema"),
    (lambda d: d["config"].update(streams=0), "streams"),
    (lambda d: d["config"].pop("delta"), "delta"),
    (lambda d: d["throughput"].pop("speedup"), "speedup"),
    (lambda d: d["backbone"].update(executions=-1), "executions"),
    (lambda d: d["backbone"].pop("by_program"), "by_program"),
    (lambda d: d.pop("reuse"), "reuse"),
    (lambda d: d["reuse"].update(reused_frames=True), "reused_frames"),
    (lambda d: d["exactness"].pop("mismatches"), "mismatches"),
    (lambda d: d.pop("isolation"), "isolation"),
    (lambda d: d["checks"].pop("reuse_labeled"), "reuse_labeled"),
    (lambda d: d.update(error=""), "error"),
])
def test_validate_stream_report_rejects_broken_docs(mutate, fragment):
    from tmr_tpu.diagnostics import validate_stream_report

    doc = _valid_doc()
    mutate(doc)
    problems = validate_stream_report(doc)
    assert problems, f"expected a problem for {fragment}"
    assert any(fragment in p for p in problems), problems


def test_read_stream_report_reduces_and_fails_closed(tmp_path):
    from tmr_tpu.utils.bench_trend import read_stream_report

    path = tmp_path / "stream.json"
    path.write_text(json.dumps(_valid_doc()) + "\n")
    out = read_stream_report(str(path))
    assert out["checks"] == {
        "backbone_amortized": True, "speedup_ok": True,
        "changed_frames_exact": True, "cross_stream_isolated": True,
        "reuse_labeled": True,
    }
    assert out["summary"]["backbone_executions"] == 8
    assert out["summary"]["frames"] == 16
    assert out["summary"]["speedup"] == 2.5
    # fail CLOSED: a missing check is not a pass
    doc = _valid_doc()
    del doc["checks"]["speedup_ok"]
    path.write_text(json.dumps(doc) + "\n")
    assert read_stream_report(str(path))["checks"]["speedup_ok"] is False
    # error record and unreadable file reduce to error records
    path.write_text(json.dumps({"schema": "stream_report/v1",
                                "error": "boom"}))
    assert "error" in read_stream_report(str(path))
    assert "error" in read_stream_report(str(tmp_path / "absent.json"))


def test_bench_trend_stream_rc_gates(tmp_path):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_valid_doc()) + "\n")
    bad_doc = _valid_doc()
    bad_doc["checks"]["changed_frames_exact"] = False
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(bad_doc) + "\n")
    script = os.path.join(REPO, "scripts", "bench_trend.py")
    ok = subprocess.run(
        [sys.executable, script, "--stream", str(good)],
        capture_output=True, text=True, timeout=120,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert json.loads(ok.stdout)["checks"]["changed_frames_exact"] is True
    fail = subprocess.run(
        [sys.executable, script, "--stream", str(bad)],
        capture_output=True, text=True, timeout=120,
    )
    assert fail.returncode == 1


def test_stream_bench_tiny_smoke_meets_acceptance_checks(tmp_path):
    """The acceptance proof, end to end on CPU: one JSON line, valid
    stream_report/v1, backbone executions strictly below frames on the
    bursty workload, >= 1.5x frames/s over the frame-independent
    baseline, changed frames bitwise-exact, reuse labeled and never
    crossing stream ids."""
    out_file = tmp_path / "stream_report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "stream_bench.py"),
         "--tiny", "--streams", "2", "--frames", "8",
         "--out", str(out_file)],
        env=_bench_env(tmp_path), capture_output=True, text=True,
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import validate_stream_report

    assert validate_stream_report(doc) == []
    assert "validator_problems" not in doc
    checks = doc["checks"]
    assert checks["backbone_amortized"] is True, doc["backbone"]
    assert checks["speedup_ok"] is True, doc["throughput"]
    assert checks["changed_frames_exact"] is True, doc["exactness"]
    assert checks["cross_stream_isolated"] is True, doc["isolation"]
    assert checks["reuse_labeled"] is True, doc
    assert checks["verdicts_as_expected"] is True, doc["reuse"]
    # the witness itself, not just its boolean: the bursty workload
    # (one content swap per stream) needs far fewer backbone runs than
    # frames, and every frame is accounted to a verdict
    bb = doc["backbone"]
    assert bb["executions"] < bb["frames"], bb
    r = doc["reuse"]
    assert r["reused_frames"] + r["changed_frames"] + r["first_frames"] \
        == doc["config"]["frames"]
    assert r["reused_frames"] > 0
    assert doc["exactness"]["mismatches"] == 0
    assert doc["throughput"]["speedup"] >= 1.5
    # --out wrote the same document; progress went to stderr only
    assert json.loads(out_file.read_text())["checks"] == checks
    assert "[stream_bench]" in out.stderr

"""scripts/gallery_bench.py: the gallery_report/v1 contract.

The smoke test runs the real script in a subprocess at tiny CPU shapes
in a CLEAN env (no forced host-device count — see test_serve.py's
caveat; the bench's bitwise pin compares across programs) with an
ISOLATED autotune cache (the bench persists its elected winners) and
asserts the acceptance checks: fused gallery arm bitwise-identical to
the N-loop of predict_multi_exemplar, backbone executions == frames
(not frames×N) via the flight recorder's program table, and the
prefilter's elected top-k at recall >= 0.99 with a >= 2x full-match
invocation cut. The validator tests pin the schema both ways."""

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
        # the bench records elected winners; tests must not write the
        # user's real cache (nor inherit its prior state)
        TMR_AUTOTUNE_CACHE=str(tmp_path / "autotune.json"),
        TMR_AUTOTUNE_SEED=str(tmp_path / "absent_seed.json"),
        **extra,
    )
    return env


def _valid_doc():
    from tmr_tpu.diagnostics import GALLERY_REPORT_SCHEMA

    return {
        "schema": GALLERY_REPORT_SCHEMA,
        "device": "cpu",
        "config": {"image_size": 128, "patterns": 8, "frames": 4},
        "bank": {"entries": 8, "groups": [
            {"capacity": 9, "k_bucket": 1, "n_real": 8, "n_bucket": 8}
        ]},
        "throughput": {"gallery_pattern_frames_per_sec": 5.8,
                       "n_loop_pattern_frames_per_sec": 2.9,
                       "speedup": 2.0},
        "backbone": {"frames": 4, "executions": 4,
                     "pattern_frame_pairs": 32,
                     "by_program": {"gallery": 4}},
        "prefilter": {
            "rungs": [{"topk": 2, "recall": 1.0, "invocation_cut": 4.0,
                       "full_matches": 8}],
            "elected_topk": 2,
        },
        "checks": {"bitwise_exact": True, "backbone_amortized": True,
                   "prefilter_recall_ok": True, "prefilter_cut_ok": True,
                   "speedup_vs_n_loop": 2.0},
    }


def _sweep_section():
    return {
        "points": [
            {"n": 1000, "topk": 32, "linear_ms": 12.0, "index_ms": 9.0,
             "recall": 1.0, "off_exact": True, "indexed": True,
             "centroids": 32, "probes": 32, "candidates": 1000},
            {"n": 10000, "topk": 32, "linear_ms": 110.0,
             "index_ms": 31.0, "recall": 0.97, "off_exact": True,
             "indexed": True, "centroids": 100, "probes": 32,
             "candidates": 3300},
        ],
        "fit": {"linear_exponent": 0.96, "index_exponent": 0.54},
        "checks": {"index_sublinear": True, "index_recall_ok": True,
                   "index_off_exact": True},
    }


def test_validate_gallery_report_accepts_valid_and_error_docs():
    from tmr_tpu.diagnostics import (
        GALLERY_REPORT_SCHEMA,
        validate_gallery_report,
    )

    assert validate_gallery_report(_valid_doc()) == []
    assert validate_gallery_report(
        {"schema": GALLERY_REPORT_SCHEMA, "error": "watchdog: ..."}
    ) == []
    # the n_sweep section is OPTIONAL (legacy docs above stay valid)
    # but validated when present
    with_sweep = _valid_doc()
    with_sweep["n_sweep"] = _sweep_section()
    assert validate_gallery_report(with_sweep) == []


@pytest.mark.parametrize("mutate, fragment", [
    (lambda d: d.update(schema="bogus/v9"), "schema"),
    (lambda d: d["config"].update(patterns=0), "patterns"),
    (lambda d: d.pop("bank"), "bank"),
    (lambda d: d["throughput"].pop("speedup"), "speedup"),
    (lambda d: d["backbone"].update(executions=-1), "executions"),
    (lambda d: d["backbone"].pop("by_program"), "by_program"),
    (lambda d: d["prefilter"].update(rungs="nope"), "rungs"),
    (lambda d: d["prefilter"]["rungs"][0].pop("recall"), "recall"),
    (lambda d: d["prefilter"].update(elected_topk=0), "elected_topk"),
    (lambda d: d["checks"].pop("bitwise_exact"), "bitwise_exact"),
    (lambda d: d.update(error=""), "error"),
    (lambda d: d.update(n_sweep="nope"), "n_sweep"),
    (lambda d: d.update(n_sweep=dict(_sweep_section(), points=[])),
     "points"),
    (lambda d: d.update(n_sweep=_sweep_section())
     or d["n_sweep"]["points"][0].update(n=0), "n"),
    (lambda d: d.update(n_sweep=_sweep_section())
     or d["n_sweep"]["points"][1].update(recall=1.5), "recall"),
    (lambda d: d.update(n_sweep=_sweep_section())
     or d["n_sweep"]["points"][0].update(index_ms=-1), "index_ms"),
    (lambda d: d.update(n_sweep=dict(_sweep_section(), fit=None)),
     "fit"),
    (lambda d: d.update(n_sweep=_sweep_section())
     or d["n_sweep"]["checks"].pop("index_sublinear"),
     "index_sublinear"),
])
def test_validate_gallery_report_rejects_broken_docs(mutate, fragment):
    from tmr_tpu.diagnostics import validate_gallery_report

    doc = _valid_doc()
    mutate(doc)
    problems = validate_gallery_report(doc)
    assert problems, f"expected a problem for {fragment}"
    assert any(fragment in p for p in problems), problems


def test_read_gallery_report_reduces_and_fails_closed(tmp_path):
    from tmr_tpu.utils.bench_trend import read_gallery_report

    path = tmp_path / "gal.json"
    path.write_text(json.dumps(_valid_doc()) + "\n")
    out = read_gallery_report(str(path))
    assert out["checks"] == {
        "bitwise_exact": True, "backbone_amortized": True,
        "prefilter_recall_ok": True, "prefilter_cut_ok": True,
    }
    assert out["summary"]["backbone_executions"] == 4
    assert out["rungs"][0]["topk"] == 2
    # fail CLOSED: a missing check is not a pass
    doc = _valid_doc()
    del doc["checks"]["backbone_amortized"]
    path.write_text(json.dumps(doc) + "\n")
    assert read_gallery_report(str(path))["checks"][
        "backbone_amortized"
    ] is False
    # error record and unreadable file reduce to error records
    path.write_text(json.dumps({"schema": "gallery_report/v1",
                                "error": "boom"}))
    assert "error" in read_gallery_report(str(path))
    assert "error" in read_gallery_report(str(tmp_path / "absent.json"))
    # the optional n_sweep section reduces to sweep_points + the three
    # sweep checks (fail closed: a missing check is not a pass)
    doc = _valid_doc()
    doc["n_sweep"] = _sweep_section()
    del doc["n_sweep"]["checks"]["index_recall_ok"]
    path.write_text(json.dumps(doc) + "\n")
    out = read_gallery_report(str(path))
    assert out["checks"]["index_sublinear"] is True
    assert out["checks"]["index_recall_ok"] is False
    assert "fleet_probe_ok" not in out["checks"]  # only when recorded
    assert out["summary"]["index_exponent"] == 0.54
    assert [p["n"] for p in out["sweep_points"]] == [1000, 10000]


def test_bench_trend_gallery_rc_gates(tmp_path):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_valid_doc()) + "\n")
    bad_doc = _valid_doc()
    bad_doc["checks"]["bitwise_exact"] = False
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(bad_doc) + "\n")
    script = os.path.join(REPO, "scripts", "bench_trend.py")
    ok = subprocess.run(
        [sys.executable, script, "--gallery", str(good)],
        capture_output=True, text=True, timeout=120,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert json.loads(ok.stdout)["checks"]["bitwise_exact"] is True
    fail = subprocess.run(
        [sys.executable, script, "--gallery", str(bad)],
        capture_output=True, text=True, timeout=120,
    )
    assert fail.returncode == 1
    # a failing n_sweep check gates rc even with the four legacy
    # checks green — and a passing sweep keeps rc 0
    sweep_doc = _valid_doc()
    sweep_doc["n_sweep"] = _sweep_section()
    swept = tmp_path / "swept.json"
    swept.write_text(json.dumps(sweep_doc) + "\n")
    ok2 = subprocess.run(
        [sys.executable, script, "--gallery", str(swept)],
        capture_output=True, text=True, timeout=120,
    )
    assert ok2.returncode == 0, ok2.stdout + ok2.stderr
    sweep_doc["n_sweep"]["checks"]["index_sublinear"] = False
    swept.write_text(json.dumps(sweep_doc) + "\n")
    fail2 = subprocess.run(
        [sys.executable, script, "--gallery", str(swept)],
        capture_output=True, text=True, timeout=120,
    )
    assert fail2.returncode == 1


def test_measured_gallery_winners_round_trip(tmp_path, monkeypatch):
    from tmr_tpu.utils.autotune import (
        gallery_cache_key,
        measured_gallery_nmax,
        measured_gallery_topk,
        record_gallery_winners,
    )

    monkeypatch.setenv("TMR_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(tmp_path / "absent.json"))
    kind = "TFRT_CPU_0"
    assert measured_gallery_nmax(128, device_kind=kind) is None
    assert measured_gallery_topk(128, device_kind=kind) is None
    record_gallery_winners(128, nmax=8, topk=2, device_kind=kind)
    assert measured_gallery_nmax(128, device_kind=kind) == 8
    assert measured_gallery_topk(128, device_kind=kind) == 2
    assert measured_gallery_nmax(999, device_kind=kind) is None
    # the key format is the writer/reader contract
    obj = json.loads((tmp_path / "autotune.json").read_text())
    assert gallery_cache_key(kind, 128) in obj


def test_gallery_bench_tiny_smoke_meets_acceptance_checks(tmp_path):
    """The acceptance proof, end to end on CPU: one JSON line, valid
    gallery_report/v1, fused arm bitwise vs the N-loop, backbone
    executions == frames for an N=8 bank, prefilter elected top-k at
    recall >= 0.99 with >= 2x invocation cut — non-hollow (detections
    exist and do not saturate)."""
    out_file = tmp_path / "gallery_report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "gallery_bench.py"),
         "--tiny", "--out", str(out_file)],
        env=_bench_env(tmp_path), capture_output=True, text=True,
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import validate_gallery_report

    assert validate_gallery_report(doc) == []
    assert "validator_problems" not in doc
    checks = doc["checks"]
    assert checks["bitwise_exact"] is True
    assert checks["backbone_amortized"] is True, doc["backbone"]
    assert checks["prefilter_recall_ok"] is True, doc["prefilter"]
    assert checks["prefilter_cut_ok"] is True, doc["prefilter"]
    assert checks["detections_nonzero"] and checks[
        "detections_nontrivial"
    ]
    assert doc["config"]["patterns"] >= 8  # the acceptance floor
    assert doc["backbone"]["executions"] == doc["backbone"]["frames"]
    assert doc["backbone"]["pattern_frame_pairs"] \
        == doc["config"]["patterns"] * doc["config"]["frames"]
    elected = doc["prefilter"]["elected_topk"]
    rung = next(r for r in doc["prefilter"]["rungs"]
                if r["topk"] == elected)
    assert rung["recall"] >= 0.99 and rung["invocation_cut"] >= 2.0
    # the elected winners persisted to the (isolated) autotune cache
    cache = json.loads((tmp_path / "autotune.json").read_text())
    (key,) = [k for k in cache if "|gallery|" in k]
    assert cache[key]["TMR_GALLERY_PREFILTER_TOPK"] == str(elected)
    # --out wrote the same document; progress went to stderr only
    assert json.loads(out_file.read_text())["checks"] == checks
    assert "[gallery_bench]" in out.stderr

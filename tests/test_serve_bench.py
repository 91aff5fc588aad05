"""scripts/serve_bench.py: the serve_report/v1 contract.

The smoke test runs the real script in a subprocess at tiny CPU shapes in
a CLEAN env (no forced host-device count — conftest's 8 virtual devices
change XLA:CPU's thread partitioning per batch shape, see test_serve.py)
and asserts the acceptance checks: batched+cached speedup >= 1.5x over the
sequential Predictor loop, results bitwise-identical to sequential, p99
bounded, cache hits observed. The validator tests pin the schema both ways.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve_env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    env.update(
        JAX_PLATFORMS="cpu",
        TMR_BENCH_TINY="1",
        TMR_BENCH_SIZE="128",
        **extra,
    )
    return env


def _valid_doc():
    from tmr_tpu.diagnostics import SERVE_REPORT_SCHEMA

    cache = {"result_cache": {"hits": 1, "misses": 2, "evictions": 0,
                              "inserts": 2},
             "feature_cache": {"hits": 0, "misses": 3, "evictions": 1,
                               "inserts": 1}}
    return {
        "schema": SERVE_REPORT_SCHEMA,
        "device": "cpu",
        "config": {"image_size": 128, "batch": 4, "max_wait_ms": 10.0},
        "workloads": [{
            "name": "exact_closed", "mode": "closed", "requests": 11,
            "throughput_img_per_sec": 1.2,
            "latency_ms": {"p50": 10.0, "p95": 20.0, "p99": 30.0},
            "batch_occupancy": {"4": 2, "3": 1},
            "cache": cache,
        }],
        "checks": {"speedup_vs_sequential": 1.9, "speedup_ok": True,
                   "exact_match": True, "p99_bounded": True,
                   "cache_hit": True},
    }


def test_validate_serve_report_accepts_valid_and_error_docs():
    from tmr_tpu.diagnostics import SERVE_REPORT_SCHEMA, validate_serve_report

    assert validate_serve_report(_valid_doc()) == []
    # bench_guard's wedge record is contractually valid
    assert validate_serve_report(
        {"schema": SERVE_REPORT_SCHEMA, "error": "watchdog: ..."}
    ) == []


@pytest.mark.parametrize("mutate, fragment", [
    (lambda d: d.update(schema="bogus/v9"), "schema"),
    (lambda d: d.pop("workloads"), "workloads"),
    (lambda d: d["workloads"][0].update(mode="sideways"), "mode"),
    (lambda d: d["workloads"][0]["latency_ms"].pop("p99"), "p99"),
    (lambda d: d["workloads"][0].update(batch_occupancy={"4": "two"}),
     "batch_occupancy"),
    (lambda d: d["workloads"][0]["cache"].pop("feature_cache"),
     "feature_cache"),
    (lambda d: d.pop("checks"), "checks"),
    (lambda d: d["checks"].pop("exact_match"), "exact_match"),
    (lambda d: d.update(error=""), "error"),
])
def test_validate_serve_report_rejects_broken_docs(mutate, fragment):
    from tmr_tpu.diagnostics import validate_serve_report

    doc = _valid_doc()
    mutate(doc)
    problems = validate_serve_report(doc)
    assert problems, f"expected a problem for {fragment}"
    assert any(fragment in p for p in problems), problems


def test_serve_bench_tiny_smoke_meets_acceptance_checks(tmp_path):
    """The acceptance proof, end to end on CPU: one JSON line, valid
    serve_report/v1, speedup >= 1.5x, bitwise exactness, bounded p99,
    cache hits > 0."""
    out_file = tmp_path / "serve_report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--tiny", "--batch", "4", "--out", str(out_file)],
        env=_serve_env(), capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import validate_serve_report

    assert validate_serve_report(doc) == []
    assert "validator_problems" not in doc
    checks = doc["checks"]
    assert checks["exact_match"] is True
    assert checks["speedup_ok"] is True, checks
    assert checks["speedup_vs_sequential"] >= 1.5
    assert checks["p99_bounded"] is True, checks
    assert checks["cache_hit"] is True and checks["cache_hits"] > 0
    names = [w["name"] for w in doc["workloads"]]
    assert "exact_closed" in names and "mixed_closed" in names
    assert any(n.startswith("open_rate_") for n in names)
    open_w = next(w for w in doc["workloads"]
                  if w["name"].startswith("open_rate_"))
    assert open_w["mode"] == "open" and "offered_img_per_sec" in open_w
    # --out wrote the same document
    assert json.loads(out_file.read_text())["checks"] == checks
    # progress goes to stderr, never stdout
    assert "[serve_bench]" in out.stderr


def _mesh_attachment():
    return {
        "spec": "dp2tp2",
        "shape": {"dp": 2, "tp": 2},
        "axis_names": ["dp", "tp"],
        "replica_groups": [["TFRT_CPU_0", "TFRT_CPU_1"],
                           ["TFRT_CPU_2", "TFRT_CPU_3"]],
        "tp_size_threshold": 512,
    }


def test_validate_serve_report_mesh_attachment():
    from tmr_tpu.diagnostics import validate_serve_report

    doc = _valid_doc()
    doc["mesh"] = _mesh_attachment()
    assert validate_serve_report(doc) == []
    # absent mesh = the unsharded engine, still valid (pre-mesh docs)
    assert validate_serve_report(_valid_doc()) == []
    for mutate, fragment in [
        (lambda m: m.update(spec=""), "spec"),
        (lambda m: m.update(shape={"dp": "two"}), "shape"),
        (lambda m: m.update(shape={"dp": 0}), "shape"),
        (lambda m: m.update(axis_names="dp,tp"), "axis_names"),
        (lambda m: m.update(replica_groups=[]), "replica_groups"),
        (lambda m: m.update(replica_groups=[[1, 2]]), "replica_groups"),
    ]:
        doc = _valid_doc()
        doc["mesh"] = _mesh_attachment()
        mutate(doc["mesh"])
        problems = validate_serve_report(doc)
        assert any(fragment in p for p in problems), (fragment, problems)


def test_read_serve_sweep_reduces_mesh_rounds(tmp_path):
    from tmr_tpu.utils.bench_trend import read_serve_sweep

    doc = _valid_doc()
    doc["mesh"] = _mesh_attachment()
    doc["config"]["devices"] = 4
    doc["workloads"][0]["single_device_img_per_sec"] = 0.6
    doc["checks"].update(scaling_vs_single_device=2.0, scaling_ok=True,
                         parity="bitwise", p99_ms=30.0)
    doc["aot"] = {"compile_events_after_warmup": 0}
    sweep = tmp_path / "sweep.jsonl"
    sweep.write_text(json.dumps(doc) + "\n" + json.dumps(doc) + "\n"
                     + "not json\n")
    out = read_serve_sweep(str(sweep))
    assert out["checks"]["shapes_read"] == 2
    assert out["checks"]["all_exact"] is True
    assert out["checks"]["all_scaling_ok"] is True
    assert out["checks"]["all_warm"] is True
    row = out["rows"][0]
    assert row["spec"] == "dp2tp2" and row["scaling"] == 2.0
    assert row["cold_compiles_after_warmup"] == 0
    # an empty / mesh-less file is an error record, not a crash
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps(_valid_doc()) + "\n")
    assert "error" in read_serve_sweep(str(empty))
    assert "error" in read_serve_sweep(str(tmp_path / "absent.jsonl"))


def test_serve_bench_mesh_sweep_smoke(tmp_path):
    """``--mesh dp2`` on a forced-8-device CPU subprocess: one
    serve_report/v1 line with a validated mesh attachment, bitwise
    parity vs the single-device engine, and the AOT zero-cold-compile
    pin — the tentpole's sweep contract end to end."""
    out_file = tmp_path / "mesh_sweep.jsonl"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--tiny", "--batch", "1", "--mesh", "dp2",
         "--out", str(out_file)],
        env=_serve_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
        ),
        capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected one line per mesh shape: {lines}"
    doc = json.loads(lines[0])

    from tmr_tpu.diagnostics import validate_serve_report

    assert validate_serve_report(doc) == []
    assert "validator_problems" not in doc
    assert doc["mesh"]["spec"] == "dp2"
    assert doc["mesh"]["shape"] == {"dp": 2, "tp": 1}
    assert len(doc["mesh"]["replica_groups"]) == 2
    checks = doc["checks"]
    assert checks["parity"] == "bitwise"
    assert checks["exact_match"] is True
    assert checks["no_cold_compiles_after_warmup"] is True
    assert checks["p99_bounded"] is True, checks
    assert checks["scaling_ok"] is True, checks
    assert doc["aot"]["warmup"]["programs"] >= 1
    assert doc["stats"]["per_group_queues"].keys() >= {"group0",
                                                       "group1", "dp"}
    # the sweep reader consumes the --out file
    from tmr_tpu.utils.bench_trend import read_serve_sweep

    reduced = read_serve_sweep(str(out_file))
    assert reduced["checks"]["shapes_read"] == 1
    assert reduced["checks"]["all_warm"] is True


@pytest.mark.slow
def test_serve_bench_watchdog_emits_error_record(tmp_path):
    """A wedge yields the contractual one-line error record — still a
    valid serve_report/v1 document (the bench_guard pattern)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
         "--tiny"],
        env=_serve_env(
            TMR_BENCH_ALARM="1",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"),
        ),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "watchdog" in rec["error"]

    from tmr_tpu.diagnostics import validate_serve_report

    assert validate_serve_report(rec) == []

"""The driver's benchmark entry (bench.py) — one JSON line, correct keys.

Runs the real script in a subprocess at tiny CPU shapes.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



import pytest

pytestmark = pytest.mark.slow  # multi-minute module: CI-only, excluded from the `-m fast` dev loop (VERDICT r4 #8)

def _bench_env(**extra):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        TMR_BENCH_SIZE="256",
        TMR_BENCH_BATCH="1",
        TMR_BENCH_CHAIN="2",
        **extra,
    )
    # per-stage tail timings and the program-tier audit are exercised by
    # their dedicated tests below; the other subprocess runs skip them
    # to stay in budget
    env.setdefault("TMR_BENCH_STAGES", "0")
    env.setdefault("TMR_BENCH_AUDIT", "0")
    return env


def test_bench_prints_one_json_line_with_required_keys():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(), capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "mfu",
                "ms_per_batch", "autotuned"):
        assert key in rec, key
    assert rec["unit"] == "img/s"
    assert rec["value"] > 0
    # stage progress goes to stderr, never stdout
    assert "[bench +" in out.stderr


def _committed_live():
    """The repo's committed BENCH_LIVE.json value (None when absent or an
    outage record) — the number a failed probe must carry, not erase."""
    live_path = os.path.join(REPO, "BENCH_LIVE.json")
    if not os.path.exists(live_path):
        return None
    with open(live_path) as f:
        live = json.load(f)
    if not isinstance(live, dict) or "error" in live or not live.get("value"):
        return None
    return live


def _assert_outage_record(rec):
    """Shared contract for watchdog/fast-failure records: when the repo
    holds a live measurement the record carries it as the HEADLINE value
    (carried: true + stale_hours — a driver keying on `value` must never
    read 0.0 while a committed number exists); with no live file the
    value is an honest 0.0."""
    live = _committed_live()
    if live is not None:
        assert rec["value"] == live["value"]
        assert rec["carried"] is True
        assert rec["stale_hours"] >= 0
        assert rec["vs_baseline"] > 0
    else:
        assert rec["value"] == 0.0


def test_bench_records_validated_stage_breakdown():
    """With TMR_BENCH_STAGES on (the default), the bench record embeds a
    ``stage_breakdown`` that passes diagnostics.validate_stage_breakdown:
    seconds (or a recorded error) for the decoder_heads and decode_tail
    stages plus the formulation stamps saying what actually traced — the
    per-stage visibility the MFU push needs across rounds."""
    from tmr_tpu.diagnostics import validate_stage_breakdown

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(TMR_BENCH_STAGES="1", TMR_BENCH_AUDIT="1"),
        capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    sb = rec["stage_breakdown"]
    assert validate_stage_breakdown(sb) == [], sb
    # off-TPU the knobs sit at their defaults; both stages must have
    # actually measured (an error string here means the harness broke)
    assert sb["decoder_impl"] == "xla"
    assert sb["quant"] == "off"
    assert sb["decode_tail"] == "host"
    assert sb["decoder_heads_s"] > 0
    assert sb["decode_tail_s"] > 0
    # the program-tier audit verdict rides the same record: the elected
    # configuration's traced programs pass the jaxpr invariants, and a
    # failure would carry structured program_audit refusal causes
    # (diagnostics.gate_refused — the kernel-gate contract)
    audit = rec["program_audit"]
    assert audit["ok"] is True, audit
    assert audit["refusals"] == []
    assert audit["programs"]["match_heads"] is True


def test_bench_watchdog_emits_error_line(tmp_path):
    # a 1s alarm beats even a fully cache-warm run (interpreter + jax init
    # alone exceed it); a cold per-test compilation cache double-insures
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(
            TMR_BENCH_ALARM="1",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla-cache"),
        ),
        capture_output=True, text=True, timeout=300,
    )
    # non-zero exit so a driver keying on status sees the wedge as a failure
    assert out.returncode == 2
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "watchdog" in rec["error"]
    _assert_outage_record(rec)


def test_bench_fast_failure_emits_error_line():
    # round 3's actual failure mode: a fast exception (jax.devices()
    # RuntimeError) long before the watchdog — must still yield the one
    # contractual JSON line, not a raw traceback (BENCH_r03.json regression)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(TMR_BENCH_SELFTEST_FAIL="1"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    rec = json.loads(lines[0])
    assert "selftest" in rec["error"]
    for key in ("metric", "value", "unit", "vs_baseline", "error"):
        assert key in rec, key
    # an outage record carries the last committed live measurement (with
    # provenance) AND promotes it to the headline value — a round-end
    # wedge must never erase the round's number (three consecutive rounds
    # of rc!=0/0.0 records while 21 img/s sat committed)
    _assert_outage_record(rec)
    live = _committed_live()
    if live is not None:
        # a clean checkout carries provenance; a working tree where the
        # watcher just dropped a fresh (uncommitted) measurement gets
        # the clearly-labeled uncommitted key instead
        if "last_committed_live" in rec:
            assert rec["last_committed_live"]["value"] == live["value"]
            assert rec["last_committed_live"]["committed_at"]
            # the driver must be able to see exactly how old the
            # carried number is (VERDICT r4 #6)
            assert rec["last_committed_live"]["stale_hours"] >= 0
        else:
            assert rec["last_live_uncommitted"]["value"] == live["value"]
            assert rec["last_live_uncommitted"]["stale_hours"] >= 0


def test_bench_preliminary_survives_post_measure_failure():
    """A failure AFTER the pre-sweep preliminary measurement banked must
    print the real measurement (annotated, rc 0), not a zero-value outage
    record — a wedge during the sweeps can no longer erase a completed
    headline (VERDICT r4 #6 follow-through)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(TMR_BENCH_SELFTEST_PRELIM="1"),
        capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines}"
    rec = json.loads(lines[0])
    assert rec["value"] > 0
    assert rec["preliminary"] is True
    assert "selftest" in rec["sweep_aborted"]


def test_bench_restores_checkpoint(tmp_path):
    # plumbing mode: --epochs 0 saves init params in the exact bench model
    # layout; bench must restore them and say so in the metric line
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "make_bench_ckpt.py"),
         "--epochs", "0", "--image_size", "64", "--compute_dtype", "float32",
         "--out", str(tmp_path / "bench_ckpt")],
        env=_bench_env(), capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    ckpt = str(tmp_path / "bench_ckpt" / "params")
    assert os.path.isdir(ckpt)

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=_bench_env(TMR_BENCH_CKPT=ckpt),
        capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "restored ckpt" in rec["metric"]
    assert rec["value"] > 0
    assert "params restored" in out.stderr


def test_gate_probe_json_contract(tmp_path):
    """scripts/gate_probe.py --json must emit ONE gate_probe/v1 document
    whose probes carry structured refusal causes (exception class/message,
    tile config, device kind) — exercised end-to-end with a FORCED refusal
    (TMR_NO_FLASH_ATTN kill-switch) so at least one cause is guaranteed
    regardless of backend, alongside the organic off-TPU backend
    refusals. --out writes the same document (the committed artifact)."""
    out_path = str(tmp_path / "gate_probe.json")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "gate_probe.py"),
         "--json", "--out", out_path],
        env=_bench_env(TMR_NO_FLASH_ATTN="1"),
        capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["schema"] == "gate_probe/v1"
    assert doc["backend"]["default_backend"]
    by_name = {p["probe"]: p for p in doc["probes"]}
    # the forced kill-switch refusal must surface with its structured cause
    flash = by_name["flash_global_64x64_d64"]
    assert flash["ok"] is False
    causes = flash["refusals"]
    assert causes and causes[0]["gate"] == "flash_attention_ok"
    assert causes[0]["cause"] == "kill-switch"
    assert causes[0]["device_kind"]
    assert causes[0]["config"]["gh"] == 64
    # the program-tier audit rides the probe document: the production
    # programs traced under the ambient env pass the jaxpr invariants
    # (reduced geometry off-TPU; the per-platform transfer pins make
    # this hold under the CPU backend too)
    audit = by_name["program_audit"]
    assert audit["ok"] is True, audit
    assert audit["problems"] == []
    assert "gate_state" in audit
    # every refused gate row carries at least one cause record, and the
    # flat aggregate collects them all
    refused = [p for p in doc["probes"]
               if p.get("ok") is False and "refusals" in p]
    assert refused
    for p in refused:
        assert p["refusals"], p["probe"]
        for c in p["refusals"]:
            assert c["schema"] == "gate_probe/v1"
            assert c["cause"]
    assert len(doc["refusals"]) >= len(refused)
    # the --out artifact is the same document
    with open(out_path) as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == "gate_probe/v1"
    assert len(on_disk["probes"]) == len(doc["probes"])


def test_bench_extra_emits_json_on_failure_and_success(tmp_path):
    """bench_extra.py shares bench.py's contract: ONE JSON line no matter
    what (round 3 died at unguarded backend init; per-config errors were
    already inline but everything outside them wasn't)."""
    script = os.path.join(REPO, "scripts", "bench_extra.py")
    # success path at tiny shapes, single cheapest config
    out = subprocess.run(
        [sys.executable, script, "--only", "demo"],
        env=_bench_env(TMR_BENCH_TINY="1"),
        capture_output=True, text=True, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "demo" in rec and "device" in rec

    # an unknown --only name is caught by the per-config guard: still one
    # JSON line, error recorded inline, rc 0
    out = subprocess.run(
        [sys.executable, script, "--only", "nonsense"],
        env=_bench_env(), capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "error" in rec["nonsense"]

    # fast-fail OUTSIDE the per-config guards (round 3's bench.py death
    # mode): backend init fails -> one error-JSON line, rc 1
    out = subprocess.run(
        [sys.executable, script, "--only", "demo"],
        env={**_bench_env(), "JAX_PLATFORMS": "bogus"},
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 1
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "error" in rec

    # watchdog path
    out = subprocess.run(
        [sys.executable, script, "--only", "demo"],
        env=_bench_env(TMR_BENCH_TINY="1", TMR_BENCH_ALARM="1"),
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 2
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "watchdog" in rec["error"]

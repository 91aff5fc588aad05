"""Flight recorder layer (tmr_tpu/obs/devtime.py + flight.py): device-
time attribution, MFU/roofline accounting, anomaly detection, health
heartbeat, and the bench-history trend reader.

The load-bearing contract mirrors PR 4's span pin: with TMR_FLIGHT=0
(the default) an instrumented program call costs one module-global bool
check. The detector tests drive every anomaly kind deterministically
with synthetic snapshots — no engine, no compiles — so the whole file
stays lean under the tier-1 time budget.
"""

import json
import time

import numpy as np
import pytest

from tmr_tpu.diagnostics import (
    ANOMALY_KINDS,
    validate_bench_trend,
    validate_flight_report,
    validate_health_report,
    validate_mfu_report,
)
from tmr_tpu.obs import devtime, flight


@pytest.fixture(scope="module")
def pred64():
    """One tiny Predictor (64² keeps the jitted init to seconds on CPU;
    the health-window test never runs an inference program)."""
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=64,
                 compute_dtype="float32", batch_size=1)
    pred = Predictor(cfg)
    pred.init_params(seed=0, image_size=64)
    return pred


@pytest.fixture(autouse=True)
def _flight_off_after():
    """Every test leaves the flight recorder disabled and its tables
    drained — the obs-suite hygiene protocol."""
    yield
    flight.configure(enabled=False)
    devtime.reset()
    flight.get_recorder().clear()


# ------------------------------------------------------------ validators


def _valid_mfu():
    return {
        "schema": "mfu_report/v1",
        "platform": {"backend": "cpu", "device_kind": "cpu",
                     "peak_tflops": 0.5, "peak_gbps": 50.0,
                     "peak_source": "nominal"},
        "programs": [{
            "kind": "single", "key": "(9,)", "bucket": {"capacity": 9},
            "calls": 2, "warmup_calls": 1, "dispatch_s": 0.01,
            "device_s": 1.0, "wall_s": 1.01, "cost_source": "xla",
            "mfu": 0.1, "bound": "compute",
        }],
        "totals": {"device_s": 1.0, "flops": 1e10,
                   "achieved_tflops": 0.01, "mfu": 0.02},
    }


def _valid_health():
    return {
        "schema": "health_report/v1",
        "ts": time.time(), "uptime_s": 1.0, "closed": False,
        "inflight": 0,
        "queues": {"pending": 0, "per_bucket": {}},
        "devices": ["cpu:0"], "per_device_batches": {},
        "caches": {
            "result": {"hits": 0, "misses": 0, "evictions": 0},
            "feature": {"hits": 0, "misses": 0, "evictions": 0},
        },
        "counters": {"submitted": 1},
        "compile": {"total": 0, "cold": 0, "key_change": 0},
        "anomalies": [],
    }


def test_validate_mfu_report_accepts_valid_and_rejects_broken():
    assert validate_mfu_report(_valid_mfu()) == []
    bad = _valid_mfu()
    bad["programs"][0]["bound"] = "sideways"
    assert any("bound" in p for p in validate_mfu_report(bad))
    bad = _valid_mfu()
    bad["platform"]["peak_tflops"] = 0
    assert any("peak_tflops" in p for p in validate_mfu_report(bad))
    bad = _valid_mfu()
    del bad["totals"]
    assert any("totals" in p for p in validate_mfu_report(bad))


def test_validate_health_report_accepts_valid_and_rejects_broken():
    doc = _valid_health()
    assert validate_health_report(doc) == []
    doc["anomalies"] = [{"anomaly": "recompile_storm",
                         "message": "m", "evidence": {}}]
    assert validate_health_report(doc) == []
    doc["anomalies"] = [{"anomaly": "weird", "message": "m",
                         "evidence": {}}]
    assert any("anomal" in p for p in validate_health_report(doc))
    doc = _valid_health()
    del doc["queues"]
    assert any("queues" in p for p in validate_health_report(doc))


def test_validate_flight_report_error_record_is_valid():
    assert validate_flight_report(
        {"schema": "flight_report/v1", "error": "watchdog: ..."}
    ) == []
    assert validate_flight_report({"schema": "bogus"}) != []


def test_serve_and_map_reports_validate_mfu_attachment():
    from tmr_tpu.diagnostics import validate_map_report

    doc = {
        "schema": "map_report/v1", "shards": [], "quarantined": [],
        "resumed": [],
        "totals": {k: 0 for k in (
            "shards", "ok", "quarantined", "resumed", "images",
            "skipped_images", "nonfinite_images", "retries")},
        "mfu": {"schema": "wrong"},
    }
    assert any(p.startswith("mfu:") for p in validate_map_report(doc))
    doc["mfu"] = _valid_mfu()
    assert not any(p.startswith("mfu:") for p in validate_map_report(doc))


# -------------------------------------------------------------- recorder


def test_flight_recorder_ring_bounds_and_counts_drops():
    rec = flight.FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("probe", i=i)
    snap = rec.snapshot()
    assert len(snap) == 16
    assert snap[-1]["i"] == 39 and snap[0]["i"] == 24  # oldest rolled off
    assert rec.dropped() == 24
    rec.clear()
    assert rec.snapshot() == [] and rec.dropped() == 0


def test_flight_record_is_noop_when_disabled():
    flight.configure(enabled=False)
    flight.get_recorder().clear()
    assert flight.record("probe") is None
    assert flight.get_recorder().snapshot() == []
    flight.configure(enabled=True)
    assert flight.record("probe", x=1)["x"] == 1
    assert len(flight.get_recorder().snapshot()) == 1


# -------------------------------------------------------------- detector


def test_health_watch_recompile_storm_fires_exactly_at_threshold():
    watch = flight.HealthWatch(recompile_storm_threshold=3)
    below = [{"cause": "key-change", "kind": "single", "wall_s": 1.0}] * 2
    assert watch.observe({}, compile_events=below) == []
    at = [{"cause": "key-change", "kind": "single", "wall_s": 1.0}] * 3
    fired = watch.observe({}, compile_events=at)
    assert [a["anomaly"] for a in fired] == ["recompile_storm"]
    assert fired[0]["evidence"]["key_change_events"] == 3
    # cold events are warmup, never a storm
    cold = [{"cause": "cold", "kind": "single", "wall_s": 1.0}] * 10
    assert watch.observe({}, compile_events=cold) == []


def test_health_watch_queue_saturation():
    watch = flight.HealthWatch(queue_depth_threshold=8)
    assert watch.observe({}, pending=7) == []
    fired = watch.observe({}, pending=8)
    assert [a["anomaly"] for a in fired] == ["queue_saturation"]
    assert fired[0]["evidence"]["pending"] == 8


def test_health_watch_latency_regression_vs_rolling_baseline():
    from tmr_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    hist = reg.histogram("serve.request_latency_s")
    watch = flight.HealthWatch(p99_factor=3.0, min_window_requests=20)
    for _ in range(30):
        hist.observe(0.010)
    assert watch.observe(reg.snapshot()) == []  # first window: baseline
    for _ in range(30):
        hist.observe(0.010)
    assert watch.observe(reg.snapshot()) == []  # steady: no fire
    for _ in range(30):
        hist.observe(0.500)  # 50x the baseline window
    fired = watch.observe(reg.snapshot())
    assert [a["anomaly"] for a in fired] == ["latency_regression"]
    ev = fired[0]["evidence"]
    assert ev["p99_s"] > 3.0 * ev["baseline_s"]
    # a SUSTAINED regression keeps firing: the regressed window must
    # not poison its own rolling baseline and silence the detector
    for _ in range(30):
        hist.observe(0.500)
    still = watch.observe(reg.snapshot())
    assert [a["anomaly"] for a in still] == ["latency_regression"]


def test_health_watch_cache_hit_collapse():
    watch = flight.HealthWatch(hit_rate_drop=0.5, min_window_lookups=20)
    c1 = {"counters": {"serve.cache.result.hits": 90,
                       "serve.cache.result.misses": 10}}
    assert watch.observe(c1) == []  # baseline window (rate 0.9)
    c2 = {"counters": {"serve.cache.result.hits": 95,
                       "serve.cache.result.misses": 105}}
    fired = watch.observe(c2)  # window rate 5/100 = 0.05
    assert [a["anomaly"] for a in fired] == ["cache_hit_collapse"]
    assert fired[0]["evidence"]["hit_rate"] < 0.1


def test_health_watch_mfu_drop():
    watch = flight.HealthWatch(mfu_drop=0.5)
    watch.observe({}, mfu_totals={"flops": 0.0, "device_s": 0.0})
    assert watch.observe(
        {}, mfu_totals={"flops": 1e12, "device_s": 1.0}
    ) == []  # baseline window: 1 TFLOP/s
    fired = watch.observe(
        {}, mfu_totals={"flops": 1.1e12, "device_s": 2.0}
    )  # window: 0.1 TFLOP/s
    assert [a["anomaly"] for a in fired] == ["mfu_drop"]
    assert watch.recent()[-1]["anomaly"] == "mfu_drop"
    # sustained drop keeps firing (no baseline self-poisoning)
    still = watch.observe(
        {}, mfu_totals={"flops": 1.2e12, "device_s": 3.0}
    )
    assert [a["anomaly"] for a in still] == ["mfu_drop"]


def test_anomaly_records_are_gate_refused_style():
    watch = flight.HealthWatch(queue_depth_threshold=1)
    rec = watch.observe({}, pending=5)[0]
    assert rec["anomaly"] in ANOMALY_KINDS
    assert rec["message"] and isinstance(rec["evidence"], dict)
    from tmr_tpu.diagnostics import validate_anomaly

    assert validate_anomaly(rec) == []


# ------------------------------------------------------------- heartbeat


def test_heartbeat_writes_jsonl_and_final_beat(tmp_path):
    path = tmp_path / "health.jsonl"
    beats = {"n": 0}

    def emit():
        beats["n"] += 1
        return {"beat": beats["n"]}

    hb = flight.Heartbeat(emit, str(path), interval_s=30.0)
    assert hb.beats == 1  # first beat lands synchronously
    hb.stop()
    docs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [d["beat"] for d in docs] == [1, 2]  # start + final beat
    assert hb.errors == 0
    hb.stop()  # idempotent


def test_heartbeat_write_failure_counts_never_raises(tmp_path):
    hb = flight.Heartbeat(lambda: {}, str(tmp_path / "no" / "dir.jsonl"),
                          interval_s=30.0)
    hb.stop()
    assert hb.errors >= 1 and hb.beats == 0


# ----------------------------------------------------- devtime wrapper


def test_track_devtime_disabled_is_passthrough_and_cheap():
    flight.configure(enabled=False)
    calls = []
    wrapped = devtime.track_devtime(lambda x: calls.append(x) or x,
                                    "probe", ("k",))
    assert wrapped(3) == 3 and calls == [3]
    assert devtime.mfu_report()["programs"] == []  # nothing recorded
    n = 20000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            wrapped(0)
        best = min(best, (time.perf_counter() - t0) / n)
    # the whole-layer disabled cost contract (the PR 4 span pin shape)
    assert best * 1e9 < 2500, f"disabled flight cost {best * 1e9:.0f} ns"


def test_track_devtime_attributes_and_reports_mfu():
    import jax
    import jax.numpy as jnp

    flight.configure(enabled=True)
    devtime.reset()
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    wrapped = devtime.track_devtime(fn, "probe_unit", ("k", 1),
                                    bucket={"capacity": 9})
    x = jnp.ones((64, 64), jnp.float32)
    for _ in range(3):
        np.asarray(wrapped(x))
    doc = devtime.mfu_report()
    assert validate_mfu_report(doc) == []
    (prog,) = doc["programs"]
    assert prog["kind"] == "probe_unit"
    assert prog["warmup_calls"] == 1 and prog["calls"] == 2
    assert prog["cost_source"] == "xla"
    assert prog["flops_per_call"] > 0
    assert prog["mfu"] is not None and np.isfinite(prog["mfu"])
    assert prog["bound"] in ("compute", "memory", "unknown")
    assert doc["totals"]["device_s"] > 0
    devtime.reset()
    assert devtime.mfu_report()["programs"] == []


def test_devtime_totals_resolves_costs_without_mfu_report():
    """The heartbeat path calls totals() (via health()) and never
    mfu_report() — pending cost records must resolve there too, or the
    mfu_drop detector is permanently blind in production wiring."""
    import jax
    import jax.numpy as jnp

    flight.configure(enabled=True)
    devtime.reset()
    wrapped = devtime.track_devtime(jax.jit(lambda x: x + 1.0),
                                    "probe_totals", ("k",))
    x = jnp.ones((32, 32), jnp.float32)
    for _ in range(2):
        np.asarray(wrapped(x))
    totals = devtime.totals()  # no mfu_report() call before this
    assert totals["flops"] > 0 and totals["device_s"] > 0


def test_compile_events_since_cursor_survives_drain_and_trim():
    """ServeEngine.health() windows compile events by monotonic seq —
    the cursor must keep working across a drain (and by the same
    mechanism, the bounded log's head trim)."""
    from tmr_tpu import obs

    seq0 = obs.compile_event_seq()
    obs.record_compile_event("cursor_probe", ("a",), 0.0, 0.1)
    evs, seq1 = obs.compile_events_since(seq0)
    assert seq1 == seq0 + 1
    assert [e["kind"] for e in evs] == ["cursor_probe"]
    assert all(e["seq"] > seq0 for e in evs)
    obs.drain_compile_events()  # another harness drains the log...
    evs2, seq2 = obs.compile_events_since(seq1)
    assert evs2 == [] and seq2 == seq1  # ...the cursor is unaffected
    obs.record_compile_event("cursor_probe", ("b",), 0.0, 0.1)
    evs3, seq3 = obs.compile_events_since(seq1)
    assert [e["key"] for e in evs3] == [repr(("b",))]
    assert seq3 == seq1 + 1


def test_engine_health_window_starts_at_construction(pred64):
    """Key-change compile events paid BEFORE an engine existed must not
    fire a spurious recompile_storm on its first health() pass."""
    from tmr_tpu import obs
    from tmr_tpu.serve import ServeEngine

    t0 = time.perf_counter()
    for i in range(5):  # a pre-engine storm (4 key-change events)
        obs.record_compile_event("pre_engine_probe", ("k", i), t0,
                                 t0 + 0.01)
    with ServeEngine(pred64, batch=2, max_wait_ms=5,
                     exemplar_cache=0, feature_cache=0) as engine:
        doc = engine.health()
        assert doc["anomalies"] == []
        assert validate_health_report(doc) == []


def test_forward_tflops_parts_sum_and_padding_correction():
    full = devtime.forward_tflops_per_image(1024)
    bb = devtime.forward_tflops_per_image(1024, part="backbone")
    hd = devtime.forward_tflops_per_image(1024, part="heads")
    assert full == pytest.approx(bb + hd)
    # the windowed-qkv padding correction: the model must sit ABOVE the
    # old unpadded-token count (1.57 TF at 1024) and close to the
    # cost_analysis()-checked 1.60 TF (PERF.md envelope note)
    assert 1.58 < full < 1.62
    with pytest.raises(ValueError):
        devtime.forward_tflops_per_image(1024, part="sideways")


def test_map_report_attaches_mfu_only_when_flight_enabled():
    from tmr_tpu.diagnostics import validate_map_report
    from tmr_tpu.parallel.mapreduce import MapReport

    flight.configure(enabled=False)
    assert "mfu" not in MapReport().document()
    flight.configure(enabled=True)
    doc = MapReport().document()
    assert "mfu" in doc
    assert validate_map_report(doc) == []


# ----------------------------------------------------------- bench trend


def _write(path, doc):
    path.write_text(json.dumps(doc))


def test_bench_trend_reads_history_and_flags_regressions(tmp_path):
    from tmr_tpu.utils.bench_trend import collect_bench_trend

    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "rc": 0, "parsed": {"value": 10.0, "mfu": 0.08}})
    # outage round carrying the committed measurement (bench.py's
    # promoted shape: value + carried: true + error)
    _write(tmp_path / "BENCH_r02.json",
           {"n": 2, "rc": 1, "parsed": {
               "value": 10.0, "mfu": 0.08, "carried": True,
               "error": "watchdog", "stale_hours": 5.0}})
    _write(tmp_path / "BENCH_r03.json",
           {"n": 3, "rc": 0, "parsed": {"value": 8.0, "mfu": 0.05}})
    _write(tmp_path / "BENCH_r04.json", {"n": 4, "rc": 1, "parsed": None})
    _write(tmp_path / "BENCH_LIVE.json", {"value": 12.0, "mfu": 0.09})

    doc = collect_bench_trend(str(tmp_path))
    assert validate_bench_trend(doc) == []
    by_label = {r["label"]: r for r in doc["rounds"]}
    assert by_label["r01"]["source"] == "measured"
    assert by_label["r02"]["source"] == "carried"
    assert by_label["r02"]["value"] == 10.0
    assert by_label["r04"]["source"] == "error"
    assert by_label["BENCH_LIVE.json"]["source"] == "measured"
    # the r02 (carried 10.0) -> r03 (8.0) drop is 20% on value and
    # 37.5% on mfu; live recovers, so exactly one flag per field
    fields = {(r["field"], r["from_label"], r["to_label"])
              for r in doc["regressions"]}
    assert ("value", "r02", "r03") in fields
    assert ("mfu", "r02", "r03") in fields
    assert doc["checks"]["regressed"] is True
    assert doc["checks"]["measured_rounds"] == 3


def test_bench_trend_pre_promotion_outage_shape_and_empty_dir(tmp_path):
    from tmr_tpu.utils.bench_trend import collect_bench_trend

    # the r04/r05 on-disk shape: value 0.0 + last_committed_live, no
    # top-level promotion
    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "rc": 1, "parsed": {
               "value": 0.0, "error": "wedge",
               "last_committed_live": {"value": 21.065, "mfu": 0.1678}}})
    doc = collect_bench_trend(str(tmp_path))
    assert validate_bench_trend(doc) == []
    (r,) = doc["rounds"]
    assert r["source"] == "carried" and r["value"] == 21.065
    assert r["mfu"] == 0.1678

    empty = tmp_path / "empty"
    empty.mkdir()
    err = collect_bench_trend(str(empty))
    assert "error" in err
    assert validate_bench_trend(err) == []

    # a stray non-numbered BENCH_r*.json must be skipped, not crash
    _write(tmp_path / "BENCH_rerun.json", {"anything": 1})
    doc2 = collect_bench_trend(str(tmp_path))
    assert validate_bench_trend(doc2) == []
    assert all(r["label"] != "rerun" for r in doc2["rounds"])


def test_bench_trend_cli_one_line_and_rc(tmp_path):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "rc": 0, "parsed": {"value": 10.0, "mfu": 0.08}})
    _write(tmp_path / "BENCH_r02.json",
           {"n": 2, "rc": 0, "parsed": {"value": 5.0, "mfu": 0.04}})
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "bench_trend.py"),
         "--repo", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1  # regression flagged
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert validate_bench_trend(doc) == []
    assert doc["checks"]["regressed"] is True
    # a longer written history with an errored round in it: must read
    # without error and emit one valid line (the driver's own records left
    # the tree with the transport they were taken over)
    hist = tmp_path / "history"
    hist.mkdir()
    for n, value in enumerate((1.2, 0.0, 10.1, 21.0, 21.1), start=1):
        rec = {"n": n, "rc": 0, "parsed": {"value": value, "mfu": 0.1}}
        if not value:
            rec = {"n": n, "rc": 1, "parsed": None}
        _write(hist / f"BENCH_r0{n}.json", rec)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "bench_trend.py"),
         "--repo", str(hist)],
        capture_output=True, text=True, timeout=120,
    )
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert validate_bench_trend(doc) == []
    assert doc["checks"]["rounds_read"] >= 5

"""Continuous in-production autotune (tmr_tpu/autotune_live.py): the
per-device-generation winner bank (isolation across cpu/v5e/v6e, stale
``_SWEEP_REV`` entries falling back to the offline cache, offline-cache
seeding), the LiveTuner election policy (consecutive decisive wins,
streak reset, oracle refusal, anomaly demotion with cause, decision-log
replay), the hot-swap hook (``Predictor.invalidate_compiled`` kind
scoping + ``apply_winner``), the engine/fleet wiring (attach refused
when disabled, offers from the serve pipeline, ``live_tune_pass``
counter aggregation + beat-reply election push with the worker's epoch
guard), the HealthWatch/FleetHealthWatch listener hooks, the
bench_trend carried-age audit, both new validators, and the full
scripts/live_tune_probe.py proof behind ``bench_trend --live-tune``."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from tmr_tpu import autotune_live
from tmr_tpu.autotune_live import (
    DEMOTE_ANOMALIES,
    LiveTuner,
    apply_winner,
    bank_key,
    load_bank,
    make_entry,
    recorded_elections,
    replay_decisions,
    seed_bank_from_cache,
    store_bank,
)
from tmr_tpu.diagnostics import (
    LIVE_TUNE_REPORT_SCHEMA,
    WINNER_BANK_SCHEMA,
    validate_bench_trend,
    validate_live_tune_report,
    validate_winner_bank,
)

SIZE = 32
EX = np.asarray([[0.4, 0.4, 0.6, 0.6]], np.float32)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DETS = {"scores": np.ones((1, 4), np.float32)}
GENS = ("cpu", "TPU v5e", "TPU v6e")


@pytest.fixture(autouse=True)
def _live_tune_off(monkeypatch):
    """Every test opts in explicitly — the disabled byte-identity
    contract of every OTHER test file depends on the default."""
    for name in ("TMR_LIVE_TUNE", "TMR_LIVE_TUNE_SAMPLE",
                 "TMR_LIVE_TUNE_BUDGET", "TMR_LIVE_TUNE_WINS",
                 "TMR_LIVE_TUNE_BANK"):
        monkeypatch.delenv(name, raising=False)
    yield


def _tuner(tmp_path, *, arms=("fused",), incumbent="xla",
           runner=None, **kw):
    kw.setdefault("knob", "TMR_DECODER_IMPL")
    kw.setdefault("device_kind", "cpu")
    kw.setdefault("geometry", "g1")
    kw.setdefault("sample", 1.0)
    kw.setdefault("budget_s", 100.0)
    kw.setdefault("wins_needed", 3)
    knob = kw.pop("knob")
    return LiveTuner(
        knob, list(arms), incumbent,
        runner=runner or (lambda arm, payload:
                          (DETS, 0.004 if arm != incumbent else 0.010)),
        bank_file=str(tmp_path / "bank.json"), **kw,
    )


# ------------------------------------------------------------ winner bank
def test_winner_bank_device_generation_isolation(tmp_path):
    """The REQUIRED isolation pin: one bank file holding cpu/v5e/v6e
    elections never lets one generation's winner load into another."""
    path = str(tmp_path / "bank.json")
    entries = {}
    for kind in GENS:
        key = bank_key(kind, "TMR_XCORR_IMPL_SMALL", "g1")
        entries[key] = make_entry(kind, "TMR_XCORR_IMPL_SMALL", "g1", "vmap",
                                  source="offline")
    assert store_bank(entries, path)
    with open(path) as f:
        raw = json.load(f)
    assert validate_winner_bank(raw) == []
    assert raw["schema"] == WINNER_BANK_SCHEMA
    # unfiltered: all three; filtered: EXACTLY the asked generation
    assert len(load_bank(path)) == 3
    for kind in GENS:
        got = load_bank(path, device_kind=kind)
        assert len(got) == 1
        (entry,) = got.values()
        assert entry["device_kind"] == kind


def test_winner_bank_stale_rev_falls_back(tmp_path, monkeypatch):
    """An entry stamped by an older harness revision is NEVER electable
    (load drops it) — the consumer falls back to the offline cache,
    whose seeding applies the same per-knob variants-stamp staleness."""
    from tmr_tpu.utils.autotune import _variants_sig

    path = str(tmp_path / "bank.json")
    fresh = make_entry("cpu", "TMR_XCORR_IMPL_SMALL", "g1", "vmap",
                       source="live")
    stale = make_entry("cpu", "TMR_QUANT", "g1", "int8",
                       source="offline")
    stale["sweep_rev"] = "pre-history"
    store_bank({bank_key("cpu", "TMR_XCORR_IMPL_SMALL", "g1"): fresh,
                bank_key("cpu", "TMR_QUANT", "g1"): stale}, path)
    got = load_bank(path, device_kind="cpu")
    assert set(got) == {bank_key("cpu", "TMR_XCORR_IMPL_SMALL", "g1")}

    # offline-cache seeding: fresh variants stamp seeds, stale stamp and
    # fallback-annotated winners do not, other generations do not, and
    # an existing bank entry is never overwritten by its own seed
    monkeypatch.setattr(
        "tmr_tpu.utils.autotune._cache_load", lambda: {
            "cpu|96x96": {
                "TMR_DECODER_IMPL": "fused",
                "_variants_TMR_DECODER_IMPL":
                    _variants_sig("TMR_DECODER_IMPL"),
                "TMR_QUANT": "int8",
                "_variants_TMR_QUANT": "stale-stamp",
                "TMR_XCORR_IMPL_SMALL": "conv (fallback)",
                "_variants_TMR_XCORR_IMPL_SMALL":
                    _variants_sig("TMR_XCORR_IMPL_SMALL"),
            },
            "TPU v5e|96x96": {
                "TMR_DECODER_IMPL": "xla",
                "_variants_TMR_DECODER_IMPL":
                    _variants_sig("TMR_DECODER_IMPL"),
            },
        })
    bank = seed_bank_from_cache("cpu", path)
    key = bank_key("cpu", "TMR_DECODER_IMPL", "96x96")
    assert bank[key]["winner"] == "fused"
    assert bank[key]["source"] == "offline"
    assert bank_key("cpu", "TMR_QUANT", "96x96") not in bank
    assert bank_key("cpu", "TMR_XCORR_IMPL_SMALL", "96x96") not in bank
    assert not any(k.startswith("TPU v5e|") for k in bank)
    # a live election for the same key outranks a later re-seed
    bank[key] = make_entry("cpu", "TMR_DECODER_IMPL", "96x96", "xla",
                           source="live", wins=3)
    store_bank(bank, path)
    reseeded = seed_bank_from_cache("cpu", path)
    assert reseeded[key]["winner"] == "xla"


def test_winner_bank_rejects_invalid(tmp_path):
    path = str(tmp_path / "bank.json")
    # foreign file: degrade to no bank, never a crash
    (tmp_path / "bank.json").write_text("not json")
    assert load_bank(path) == {}
    # fallback-annotated winner: never electable
    bad = make_entry("cpu", "TMR_XCORR_IMPL_SMALL", "g1", "conv (fallback)",
                     source="live")
    # key/entry mismatch: a hand-edit, dropped
    moved = make_entry("cpu", "TMR_XCORR_IMPL_SMALL", "g2", "vmap",
                       source="live")
    store_bank({bank_key("cpu", "TMR_XCORR_IMPL_SMALL", "g1"): bad,
                bank_key("cpu", "TMR_XCORR_IMPL_SMALL", "g3"): moved}, path)
    assert load_bank(path) == {}
    # validator-level: source outside the vocabulary / boolean wins
    doc = {"schema": WINNER_BANK_SCHEMA, "sweep_rev": "r", "ts": 1.0,
           "entries": {"k": {"device_kind": "cpu", "knob": "K",
                             "geometry": "g", "winner": "w",
                             "sweep_rev": "r", "source": "guessed",
                             "wins": True, "ts": 1.0}}}
    problems = validate_winner_bank(doc)
    assert any("source" in p for p in problems)
    assert any("wins" in p for p in problems)


# ------------------------------------------------------- election policy
def test_tuner_promotes_after_consecutive_decisive_wins(tmp_path):
    applied = []
    t = _tuner(tmp_path, apply_fn=lambda k, v: applied.append((k, v)))
    for _ in range(2):
        t._shadow_one(None, None, 1)
    assert t.incumbent == "xla"  # two wins: not yet decisive
    t._shadow_one(None, None, 1)
    assert t.incumbent == "fused"
    assert applied == [("TMR_DECODER_IMPL", "fused")]
    c = t.counters()
    assert c["promotions"] == 1 and c["shadow_runs"] == 3
    events = [d["event"] for d in t.decisions]
    assert events == ["shadow", "shadow", "shadow", "promote"]
    assert t.decisions[-1]["wins"] == 3
    # the election landed in the bank as a live-source entry
    entry = load_bank(t.bank_file, device_kind="cpu")[
        bank_key("cpu", "TMR_DECODER_IMPL", "g1")]
    assert entry["winner"] == "fused" and entry["source"] == "live"
    assert entry["device_s_per_item"]["incumbent"] > 0


def test_tuner_streak_resets_on_non_win(tmp_path):
    """Decisive wins are CONSECUTIVE — a non-win resets the arm, so an
    intermittently-fast candidate never promotes."""
    seq = iter([0.004, 0.004, 0.010,   # two wins, then a tie: reset
                0.004, 0.004, 0.010])  # never three in a row

    def runner(arm, payload):
        return (DETS, 0.010) if arm == "xla" else (DETS, next(seq))

    t = _tuner(tmp_path, runner=runner)
    for _ in range(6):
        t._shadow_one(None, None, 1)
    assert t.incumbent == "xla"
    assert t.counters()["promotions"] == 0
    wins = [d["wins"] for d in t.decisions if d["event"] == "shadow"]
    assert wins == [1, 2, 0, 1, 2, 0]


def test_tuner_oracle_refusal_disqualifies(tmp_path):
    """A candidate whose RESULT disagrees with the incumbent is refused
    regardless of timing: recorded, disqualified, never promoted."""
    wrong = {"scores": np.zeros((1, 4), np.float32)}

    def runner(arm, payload):
        return (DETS, 0.010) if arm == "xla" else (wrong, 0.001)

    t = _tuner(tmp_path)
    t._runner = runner
    for _ in range(4):
        t._shadow_one(None, None, 1)
    assert t.incumbent == "xla"
    c = t.counters()
    assert c["refusals"] == 1 and c["promotions"] == 0
    assert t.report()["disqualified"] == ["fused"]
    # only ONE refusal decision: a disqualified arm leaves the pool
    assert [d["event"] for d in t.decisions] == ["refusal"]
    # a refusal of the PROMOTED arm demotes with oracle_refusal cause
    # (two arms round-robin, so "fused" shadows on runs 1/3/5)
    t2 = _tuner(tmp_path, arms=("fused", "flash"))
    for _ in range(5):
        t2._shadow_one(None, None, 1)
    assert t2.incumbent == "fused"
    t2._refuse("fused", 0.010, 0.001, 1)
    assert t2.incumbent == "xla"
    demotes = [d for d in t2.decisions if d["event"] == "demote"]
    assert demotes and demotes[-1]["cause"] == "oracle_refusal"


def test_tuner_anomaly_demotes_with_cause(tmp_path):
    applied = []
    t = _tuner(tmp_path, apply_fn=lambda k, v: applied.append(v))
    # an anomaly with NOTHING promoted must not thrash anything
    t.observe_anomalies([{"anomaly": "mfu_drop"}])
    assert t.counters()["demotions"] == 0
    for _ in range(3):
        t._shadow_one(None, None, 1)
    assert t.incumbent == "fused"
    # a non-demote anomaly kind is ignored
    t.observe_anomalies([{"anomaly": "queue_saturation"}])
    assert t.incumbent == "fused"
    assert "queue_saturation" not in DEMOTE_ANOMALIES
    t.observe_anomalies([
        {"anomaly": "fleet_mfu_drop", "evidence": {"injected": True}},
    ])
    assert t.incumbent == "xla"
    assert applied == ["fused", "xla"]  # promote swap, demote rollback
    d = t.decisions[-1]
    assert d["event"] == "demote" and d["cause"] == "fleet_mfu_drop"
    assert d["evidence"] == {"injected": True}
    # the demoted arm is disqualified: further wins cannot re-promote
    t._shadow_one(None, None, 1)
    assert t.incumbent == "xla"
    # the bank rolled back with the election
    entry = load_bank(t.bank_file, device_kind="cpu")[
        bank_key("cpu", "TMR_DECODER_IMPL", "g1")]
    assert entry["winner"] == "xla"


def test_replay_decisions_matches_recorded(tmp_path):
    t = _tuner(tmp_path)
    for _ in range(3):
        t._shadow_one(None, None, 1)
    t.observe_anomalies([{"anomaly": "latency_regression"}])
    log = t.report()["decisions"]
    recorded = recorded_elections(log)
    assert recorded == [("promote", "fused"), ("demote", "fused")]
    assert replay_decisions(log, wins_needed=3) == recorded
    # the replay is a FUNCTION of the measurements: a stricter policy
    # reaches a different election than the recorded one
    assert replay_decisions(log, wins_needed=4) == []
    # hand-written log: a refusal of the promoted arm replays as demote
    synth = [
        {"event": "shadow", "arm": "a", "base_s_per_item": 1.0,
         "cand_s_per_item": 0.5},
        {"event": "promote", "arm": "a"},
        {"event": "refusal", "arm": "a"},
    ]
    assert replay_decisions(synth, wins_needed=1) == \
        [("promote", "a"), ("demote", "a")]


# ----------------------------------------------------------- hot-swap hook
def test_invalidate_compiled_kind_scoping():
    from tmr_tpu.inference import Predictor

    p = Predictor.__new__(Predictor)
    p._compiled = {
        (64, "k1"): "single-prog", (128, "k2"): "single-prog-2",
        ("multi", 64): "m", ("multi_batched", 64): "mb",
        ("backbone", 96): "bb", ("heads", 96): "h",
        ("gallery", 1): "g", ("gallery_heads", 1): "gh",
    }
    p._storage_cache = object()
    # int-led keys ARE the single-image programs
    assert p.invalidate_compiled(("single",)) == 2
    assert not any(isinstance(k[0], int) for k in p._compiled)
    # the TMR_DECODER_IMPL scope: decode-tail programs, NOT backbone
    dropped = p.invalidate_compiled(
        autotune_live.KNOB_PROGRAM_KINDS["TMR_DECODER_IMPL"])
    assert dropped == 5
    assert set(p._compiled) == {("backbone", 96)}
    assert p._storage_cache is not None  # scoped drop keeps storage
    assert p.invalidate_compiled(None) == 1
    assert p._compiled == {} and p._storage_cache is None


def test_apply_winner_env_and_kinds(monkeypatch):
    monkeypatch.setenv("TMR_DECODER_IMPL", "auto")
    calls = []

    class _Pred:
        def invalidate_compiled(self, kinds):
            calls.append(kinds)
            return 7

    assert apply_winner(_Pred(), "TMR_DECODER_IMPL", "fused") == 7
    assert os.environ["TMR_DECODER_IMPL"] == "fused"
    assert calls == [autotune_live.KNOB_PROGRAM_KINDS["TMR_DECODER_IMPL"]]
    monkeypatch.setenv("TMR_XCORR_IMPL_SMALL", "conv")
    assert apply_winner(_Pred(), "TMR_XCORR_IMPL_SMALL", "vmap") == 7
    assert calls[-1] is None  # matcher knobs invalidate EVERYTHING
    # a predictor without the hook (the fleet stub): env-only, 0 drops
    assert apply_winner(object(), "TMR_XCORR_IMPL_SMALL", "conv") == 0


# ----------------------------------------------------------- engine wiring
def test_engine_attach_refused_when_disabled(tmp_path):
    from tmr_tpu.serve.fleet import stub_engine

    t = _tuner(tmp_path)
    with stub_engine(0.0) as eng:
        assert eng.attach_live_tuner(t) is False
        assert eng._tuner is None
        eng.submit(np.zeros((SIZE, SIZE, 3), np.float32),
                   EX).result(timeout=30)
        counters = eng.metrics_snapshot().get("counters") or {}
        assert not any(k.startswith("live_tune.") for k in counters)
    assert t.counters()["offers"] == 0


def test_engine_offers_batches_when_enabled(tmp_path, monkeypatch):
    from tmr_tpu.serve.fleet import stub_engine

    monkeypatch.setenv("TMR_LIVE_TUNE", "1")
    monkeypatch.setenv("TMR_LIVE_TUNE_BANK", str(tmp_path / "bank.json"))
    seen = []

    def runner(arm, payload):
        bucket, reqs = payload
        seen.append((arm, len(reqs)))
        assert all(r[0].shape == (SIZE, SIZE, 3) for r in reqs)
        return (DETS, 0.010 if arm == "xla" else 0.004)

    t = _tuner(tmp_path, runner=runner, metrics=None)
    eng = stub_engine(0.0)
    try:
        assert eng.attach_live_tuner(t) is True
        for i in range(4):
            eng.submit(np.full((SIZE, SIZE, 3), i, np.float32),
                       EX).result(timeout=30)
        t.drain(timeout=20.0)
        c = t.counters()
        assert c["offers"] >= 4 and c["sampled"] >= 1
        # 3 shadows promoted "fused"; later samples have no arm left
        assert c["shadow_runs"] == 3 and c["promotions"] == 1
        assert t.incumbent == "fused"
        assert seen  # the runner saw real (image, exemplars, k) tuples
    finally:
        eng.close()
    assert t._thread is None  # close() stopped the shadow thread


def test_healthwatch_listener_demotes_live_promotion(tmp_path):
    """The engine-side demotion trigger end to end: a real HealthWatch
    mfu_drop pass (not an injected record) reaches the tuner through
    add_listener and rolls the promotion back."""
    from tmr_tpu.obs.flight import HealthWatch

    t = _tuner(tmp_path)
    for _ in range(3):
        t._shadow_one(None, None, 1)
    assert t.incumbent == "fused"
    watch = HealthWatch()
    watch.add_listener(t.observe_anomalies)
    snap = {"counters": {}, "histograms": {}}
    watch.observe(snap, mfu_totals={"flops": 0.0, "device_s": 0.0})
    watch.observe(snap, mfu_totals={"flops": 1e12, "device_s": 1.0})
    fired = watch.observe(snap, mfu_totals={"flops": 1.1e12,
                                            "device_s": 2.0})
    assert [r["anomaly"] for r in fired] == ["mfu_drop"]
    assert t.incumbent == "xla"
    assert t.decisions[-1]["cause"] == "mfu_drop"


# ------------------------------------------------------------ fleet wiring
def test_fleet_live_tune_pass_and_beat_push(tmp_path, monkeypatch):
    from tmr_tpu.obs import fleetobs
    from tmr_tpu.parallel.leases import LeasePolicy
    from tmr_tpu.serve.fleet import FleetWorker, ServeFleet, stub_engine

    monkeypatch.setenv("TMR_LIVE_TUNE", "1")
    fleetobs.configure(enabled=True)
    fleet = ServeFleet([SIZE], classes=1, policy=LeasePolicy(
        lease_ttl_s=2.0, hb_interval_s=0.1, check_interval_s=0.05,
        straggler_factor=0.0, max_reassigns=1_000_000_000,
        resource_fail_workers=1_000_000_000,
    ), check_interval_s=0.05)
    fleet.start()
    try:
        knob = "TMR_DECODER_IMPL"
        # nothing elected yet: the beat reply carries no election key
        reply = fleet._op_beat({"op": "beat", "worker": "w0",
                                "held": []})
        assert "live_tune" not in reply
        assert fleet.live_tune_pass(knob) is None
        # two workers' decisive-win counters fold in over beats; their
        # SUM reaches the threshold no single worker reached
        fo = fleet.fleet_obs
        fo.metrics.fold("w1", {
            "counters": {f"live_tune.win.{knob}=fused": 2},
            "gauges": {}, "histograms": {}})
        fo.metrics.fold("w2", {
            "counters": {f"live_tune.win.{knob}=fused": 1,
                         f"live_tune.win.{knob}=other": 9,
                         f"live_tune.refusal.{knob}=other": 1},
            "gauges": {}, "histograms": {}})
        doc = fleet.live_tune_pass(knob, wins_needed=3, geometry="g1")
        # the refused arm lost despite more wins — refusals outrank
        assert doc["winner"] == "fused" and doc["wins"] == 3
        assert doc["demoted"] is False and doc["epoch"] == 1
        reply = fleet._op_beat({"op": "beat", "worker": "w0",
                                "held": []})
        assert reply["live_tune"]["winner"] == "fused"
        # a live worker applies the election ONCE (epoch guard)
        got = []
        worker = FleetWorker(fleet.address, "w1", stub_engine())
        worker.on_live_tune(got.append)
        worker.start()
        try:
            deadline = time.monotonic() + 15.0
            while not got and time.monotonic() < deadline:
                time.sleep(0.02)
            assert got and got[0]["winner"] == "fused"
            time.sleep(0.35)  # several more beats: same epoch, no re-apply
            assert len(got) == 1
            # a fleet-wide demote anomaly revokes the election and bumps
            # the epoch — the worker applies the rollback verdict
            fo.watch._recent.append({
                "schema": "anomaly/v1", "anomaly": "fleet_mfu_drop",
                "message": "injected", "evidence": {"worker": "w1"},
                "ts": time.time()})
            doc = fleet.live_tune_pass(knob, wins_needed=3)
            assert doc["demoted"] is True and doc["winner"] is None
            assert doc["cause"] == "fleet_mfu_drop"
            assert doc["demoted_arm"] == "fused" and doc["epoch"] == 2
            deadline = time.monotonic() + 15.0
            while len(got) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(got) == 2 and got[1]["demoted"] is True
            # the demoted arm can never win a later pass
            fo.watch._recent.clear()
            fo.metrics.fold("w1", {
                "counters": {f"live_tune.win.{knob}=fused": 50},
                "gauges": {}, "histograms": {}})
            doc = fleet.live_tune_pass(knob, wins_needed=3)
            assert doc["winner"] is None and "fused" in doc["demoted_arms"]
        finally:
            worker.stop()
    finally:
        fleet.close()
        fleetobs.configure(enabled=False)


def test_fleet_live_tune_pass_disabled_is_none(tmp_path):
    from tmr_tpu.parallel.leases import LeasePolicy
    from tmr_tpu.serve.fleet import ServeFleet

    fleet = ServeFleet([SIZE], classes=1, policy=LeasePolicy(
        lease_ttl_s=2.0, hb_interval_s=0.1, check_interval_s=0.05))
    fleet.start()
    try:
        # TMR_LIVE_TUNE unset AND no obs plane: the pass is inert
        assert fleet.live_tune_pass("TMR_DECODER_IMPL") is None
    finally:
        fleet.close()


# ------------------------------------------------- bench_trend age audit
def _write(path, doc):
    path.write_text(json.dumps(doc))


def test_bench_trend_carried_age_audit(tmp_path):
    from tmr_tpu.utils.bench_trend import collect_bench_trend

    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "rc": 0, "parsed": {"value": 10.0, "mfu": 0.08}})
    _write(tmp_path / "BENCH_r02.json",
           {"n": 2, "rc": 1, "parsed": {
               "value": 10.0, "mfu": 0.08, "carried": True,
               "error": "watchdog", "stale_hours": 30.0}})
    _write(tmp_path / "BENCH_r03.json",
           {"n": 3, "rc": 1, "parsed": {
               "value": 10.0, "mfu": 0.08, "carried": True,
               "error": "watchdog"}})  # no age stamp at all
    # default: the exact pre-audit shape (no new keys)
    doc = collect_bench_trend(str(tmp_path))
    assert validate_bench_trend(doc) == []
    assert "stale_carried" not in doc
    assert "carried_age_ok" not in doc["checks"]
    by_label = {r["label"]: r for r in doc["rounds"]}
    assert by_label["r02"]["stale_hours"] == 30.0
    assert by_label["r03"]["stale_hours"] is None
    # armed: the 30h round exceeds 24h, the unstamped one fails closed
    doc = collect_bench_trend(str(tmp_path), max_carried_age_h=24.0)
    assert validate_bench_trend(doc) == []
    assert doc["checks"]["carried_age_ok"] is False
    assert {r["label"] for r in doc["stale_carried"]} == {"r02", "r03"}
    # a generous bound passes the stamped round, still fails unstamped
    doc = collect_bench_trend(str(tmp_path), max_carried_age_h=48.0)
    assert {r["label"] for r in doc["stale_carried"]} == {"r03"}
    # all stamped within bound: the audit passes
    fresh = tmp_path / "fresh"
    fresh.mkdir()
    _write(fresh / "BENCH_r01.json",
           {"n": 1, "rc": 0, "parsed": {"value": 10.0, "mfu": 0.08}})
    _write(fresh / "BENCH_r02.json",
           {"n": 2, "rc": 1, "parsed": {
               "value": 10.0, "mfu": 0.08, "carried": True,
               "error": "watchdog", "stale_hours": 5.0}})
    doc = collect_bench_trend(str(fresh), max_carried_age_h=24.0)
    assert doc["checks"]["carried_age_ok"] is True
    assert doc["stale_carried"] == []


def test_bench_trend_cli_carried_age_gate(tmp_path):
    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "rc": 0, "parsed": {"value": 10.0, "mfu": 0.08}})
    _write(tmp_path / "BENCH_r02.json",
           {"n": 2, "rc": 1, "parsed": {
               "value": 10.0, "mfu": 0.08, "carried": True,
               "error": "watchdog", "stale_hours": 30.0}})
    cli = [sys.executable, os.path.join(REPO, "scripts",
                                        "bench_trend.py"),
           "--repo", str(tmp_path), "--max-carried-age-h", "24"]
    # default: a WARNING on stderr, stdout stays one JSON line, rc 0
    out = subprocess.run(cli, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0
    assert "stale" in out.stderr
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1 and json.loads(lines[0])
    # --strict-carried arms the gate: same document, rc 1
    out = subprocess.run(cli + ["--strict-carried"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert json.loads(out.stdout.strip().splitlines()[0])


# --------------------------------------------------------------- validators
def test_live_tune_report_validator():
    good = {
        "schema": LIVE_TUNE_REPORT_SCHEMA, "device_kind": "cpu",
        "tuner": {"knob": "K", "incumbent": "a",
                  "counters": {"offers": 1},
                  "decisions": [
                      {"event": "shadow", "knob": "K", "arm": "b",
                       "ts": 1.0},
                      {"event": "demote", "knob": "K", "arm": "b",
                       "ts": 2.0, "cause": "mfu_drop"},
                  ]},
        "summary": {}, "checks": {"ok": True},
    }
    assert validate_live_tune_report(good) == []
    assert validate_live_tune_report(
        {"schema": LIVE_TUNE_REPORT_SCHEMA, "error": "wedge"}) == []
    bad = json.loads(json.dumps(good))
    bad["tuner"]["decisions"][0]["event"] = "guessed"
    del bad["tuner"]["decisions"][1]["cause"]
    bad["checks"] = {}
    problems = validate_live_tune_report(bad)
    assert any("event" in p for p in problems)
    assert any("cause" in p for p in problems)
    assert any("checks" in p for p in problems)


# -------------------------------------------------------- the full probe
def test_live_tune_probe_and_gate(tmp_path):
    """The acceptance proof end to end: the probe emits ONE validated
    line with every check true, and ``bench_trend --live-tune``
    rc-gates it (fail-closed on a broken file)."""
    report = tmp_path / "live_tune.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TMR_LIVE_TUNE", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "live_tune_probe.py"),
         "--out", str(report)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1  # ONE JSON line on stdout, warnings on stderr
    doc = json.loads(lines[0])
    assert validate_live_tune_report(doc) == []
    assert all(v is True for v in doc["checks"].values())
    assert doc["summary"]["shadow_fraction"] < 0.01
    assert doc["summary"]["promotion_speedup"] > 2.0
    assert doc["summary"]["demote_cause"] == "mfu_drop"
    gate = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "bench_trend.py"),
         "--live-tune", str(report)],
        capture_output=True, text=True, timeout=120,
    )
    assert gate.returncode == 0
    reduced = json.loads(gate.stdout.strip().splitlines()[0])
    assert reduced["checks"]["promoted_decisively"] is True
    # fail-closed: a check forced false flips the gate
    doc["checks"]["replay_consistent"] = False
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps(doc))
    gate = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "bench_trend.py"),
         "--live-tune", str(broken)],
        capture_output=True, text=True, timeout=120,
    )
    assert gate.returncode == 1

"""Continuous-autotune probe: proves the live shadow-election loop
end to end on the numpy fleet stub (zero XLA, CPU-deterministic) and
emits ONE validated ``live_tune_report/v1`` JSON line.

Phases:

1. **Disabled pin** — with ``TMR_LIVE_TUNE`` off (the default), an
   engine that had ``attach_live_tuner`` called on it (refused) serves
   BITWISE-identical results to one that never heard of live tuning,
   and its metrics registry carries no ``live_tune.*`` keys.
2. **Promotion** — a slow incumbent formulation (stub program paced at
   ``SLOW_S``) vs a decisively faster candidate (``FAST_S``): sampled
   serve batches are shadow-measured off the critical path, the
   candidate passes the oracle and wins consecutively, promotion
   hot-swaps the serving predictor — the SAME engine then serves
   measurably faster with ZERO cold compiles on the hot path, the
   winner bank records the election, all under the device-seconds
   budget.
3. **Shadow-fraction pin** — at the DEFAULT sample rate the shadow
   work (incumbent + candidate per sample) stays under 1% of the
   steady-state serve device seconds.
4. **Demotion** — an injected ``mfu_drop`` anomaly (the HealthWatch
   record shape, delivered through the tuner's listener hook) rolls
   the promotion back to the incumbent with the cause recorded, and
   the bank follows.
5. **Replay + bank isolation** — the decision log replays to exactly
   the recorded elections, and winner banks never leak across device
   generations (cpu / TPU v5e / TPU v6e) or across sweep revisions.

``bench_trend.py --live-tune <file>`` rc-gates the emitted line
fail-closed. Usage: python scripts/live_tune_probe.py [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from tmr_tpu import autotune_live  # noqa: E402
from tmr_tpu.diagnostics import (  # noqa: E402
    LIVE_TUNE_REPORT_SCHEMA,
    validate_live_tune_report,
)

SLOW_S = 0.03   # incumbent stub program pacing (per call)
FAST_S = 0.003  # candidate pacing: a ~10x win, decisive by any margin
KNOB = "TMR_DECODER_IMPL"


def _warn(msg: str) -> None:
    print(f"[live_tune_probe] {msg}", file=sys.stderr)


def _images(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.random((16, 16, 3), np.float32).astype(np.float32)
            for _ in range(n)]


def _serve(engine, images) -> list:
    """Sequential submit+wait (one batch per request) returning the
    full result dicts — the bitwise-comparison payload."""
    ex = np.zeros((1, 4), np.float32)
    out = []
    for img in images:
        out.append(engine.submit(img, ex).result(timeout=60))
    return out


def _results_equal(a: list, b: list) -> bool:
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if sorted(ra) != sorted(rb):
            return False
        if not all(np.array_equal(np.asarray(ra[k]), np.asarray(rb[k]))
                   for k in ra):
            return False
    return True


def _phase_disabled() -> dict:
    """TMR_LIVE_TUNE off: attach refuses, serving is bitwise-identical,
    no live_tune metrics keys exist."""
    from tmr_tpu.serve.fleet import stub_engine

    os.environ.pop("TMR_LIVE_TUNE", None)
    images = _images(8, seed=7)
    with stub_engine(0.0) as plain:
        baseline = _serve(plain, images)
    with stub_engine(0.0) as eng:
        tuner = autotune_live.LiveTuner(
            KNOB, ["fused"], "xla",
            runner=lambda arm, payload: (None, 0.0),
        )
        attached = eng.attach_live_tuner(tuner)
        attempted = _serve(eng, images)
        counters = (eng.metrics_snapshot().get("counters") or {})
    live_keys = [k for k in counters if k.startswith("live_tune.")]
    return {
        "attach_refused": attached is False,
        "bitwise_identical": _results_equal(baseline, attempted),
        "live_tune_metrics_keys": live_keys,
    }


def _phase_election(bank_file: str) -> dict:
    """Promotion -> demotion on one live engine under TMR_LIVE_TUNE=1."""
    from tmr_tpu.obs import compile_event_seq, compile_events_since
    from tmr_tpu.serve.fleet import StubFleetPredictor, stub_engine

    os.environ["TMR_LIVE_TUNE"] = "1"
    engine = stub_engine(SLOW_S)
    serving_pred = engine._pred
    # per-arm shadow predictors: same numerics (the oracle must pass),
    # different pacing (the candidate's decisive win)
    shadow = {"xla": StubFleetPredictor(delay_s=SLOW_S),
              "fused": StubFleetPredictor(delay_s=FAST_S)}

    def runner(arm, payload):
        _bucket, reqs = payload
        images = np.stack([r[0] for r in reqs])
        t0 = time.perf_counter()
        out = shadow[arm]._run(images)
        return out, time.perf_counter() - t0

    applied = []

    def apply_fn(knob, value):
        # the production hot-swap (env export + compiled-program
        # invalidation; the stub has no _compiled, so 0 drops) plus the
        # stub's analogue of "the program got faster": pacing swap
        applied.append((knob, value,
                        autotune_live.apply_winner(serving_pred, knob,
                                                   value)))
        serving_pred.delay_s = FAST_S if value == "fused" else SLOW_S

    tuner = autotune_live.LiveTuner(
        KNOB, ["fused"], "xla", runner=runner,
        device_kind="cpu", geometry="stub16",
        sample=0.5, budget_s=5.0, wins_needed=3,
        bank_file=bank_file, apply_fn=apply_fn, metrics=engine.metrics,
    )
    out: dict = {}
    try:
        if not engine.attach_live_tuner(tuner):
            out["error"] = "attach_live_tuner refused under " \
                           "TMR_LIVE_TUNE=1"
            return out
        # --- pre-promotion serving (shadow sampling live underneath)
        pre_images = _images(6, seed=11)
        t0 = time.perf_counter()
        _serve(engine, pre_images)
        pre_wall = time.perf_counter() - t0
        tuner.drain(timeout=30.0)
        rep = tuner.report()
        out["promoted_arm"] = rep["incumbent"]
        out["promotions"] = rep["counters"]["promotions"]
        out["pre_s_per_req"] = pre_wall / len(pre_images)
        # --- post-promotion serving: faster, zero hot-path compiles
        seq = compile_event_seq()
        post_images = _images(10, seed=13)
        t0 = time.perf_counter()
        _serve(engine, post_images)
        post_wall = time.perf_counter() - t0
        events, _ = compile_events_since(seq)
        out["post_s_per_req"] = post_wall / len(post_images)
        out["hot_path_compiles"] = len(events)
        out["speedup"] = (out["pre_s_per_req"] / out["post_s_per_req"]
                          if out["post_s_per_req"] > 0 else None)
        bank = autotune_live.load_bank(bank_file, device_kind="cpu")
        key = autotune_live.bank_key("cpu", KNOB, "stub16")
        out["bank_after_promote"] = (bank.get(key) or {}).get("winner")
        # --- injected anomaly -> demotion with recorded cause
        tuner.observe_anomalies([{
            "schema": "anomaly/v1", "anomaly": "mfu_drop",
            "message": "injected: post-promotion MFU collapse",
            "evidence": {"injected": True}, "ts": time.time(),
        }])
        rep = tuner.report()
        out["restored_arm"] = rep["incumbent"]
        out["demotions"] = rep["counters"]["demotions"]
        demotes = [d for d in rep["decisions"] if d["event"] == "demote"]
        out["demote_cause"] = demotes[-1]["cause"] if demotes else None
        out["serving_delay_s"] = serving_pred.delay_s
        bank = autotune_live.load_bank(bank_file, device_kind="cpu")
        out["bank_after_demote"] = (bank.get(key) or {}).get("winner")
        out["applied"] = applied
        out["tuner"] = tuner.report()
    finally:
        engine.close()
    return out


def _phase_fraction() -> dict:
    """Default-sample-rate shadow cost against simulated steady-state
    traffic: synthesized per-arm timings (no sleeping — the fraction is
    a structural property of sample rate x (1 + cand/base))."""
    dets = {"scores": np.zeros((1, 4), np.float32)}

    def runner(arm, payload):
        return dets, 0.010 if arm == "xla" else 0.004

    tuner = autotune_live.LiveTuner(
        "TMR_GLOBAL_ATTN", ["flash"], "blockwise", runner=runner,
        device_kind="cpu", geometry="frac",
        sample=None,            # the DEFAULT rate — the pin under test
        budget_s=5.0, wins_needed=10 ** 6,  # never promote here
    )
    # blockwise/flash arms reuse the runner's xla/other split
    tuner._runner = lambda arm, payload: runner(
        "xla" if arm == "blockwise" else "flash", payload
    )
    tuner.start()
    offers = 3000
    for _ in range(offers):
        tuner.offer(None, None, items=1)
        if not tuner._q.empty():
            tuner.drain(timeout=10.0)  # keep the bounded queue drained
    tuner.drain(timeout=30.0)
    tuner.stop()
    counters = tuner.counters()
    return {
        "offers": offers,
        "sample": tuner.sample,
        "shadow_runs": counters["shadow_runs"],
        "shadow_device_s": counters["shadow_device_s"],
        "budget_s": tuner.budget_s,
        "shadow_fraction": tuner.shadow_fraction(),
    }


def _phase_bank_isolation(path: str) -> dict:
    """Per-generation isolation + stale-revision fallback on one file."""
    entries = {}
    for kind in ("cpu", "TPU v5e", "TPU v6e"):
        key = autotune_live.bank_key(kind, "TMR_GLOBAL_ATTN", "g1")
        entries[key] = autotune_live.make_entry(
            kind, "TMR_GLOBAL_ATTN", "g1", "flash", source="offline")
    stale_key = autotune_live.bank_key("cpu", "TMR_QUANT", "g1")
    stale = autotune_live.make_entry("cpu", "TMR_QUANT", "g1", "int8",
                                     source="offline")
    stale["sweep_rev"] = "pre-history"  # a harness revision ago
    entries[stale_key] = stale
    autotune_live.store_bank(entries, path)
    loads = {
        kind: autotune_live.load_bank(path, device_kind=kind)
        for kind in ("cpu", "TPU v5e", "TPU v6e")
    }
    return {
        "per_kind_counts": {k: len(v) for k, v in loads.items()},
        "isolated": all(
            set(e["device_kind"] for e in loads[k].values()) <= {k}
            and len(loads[k]) == 1  # own entry only; stale one dropped
            for k in loads
        ),
        "stale_dropped": stale_key not in loads["cpu"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this path")
    args = ap.parse_args(argv)

    tmpdir = tempfile.mkdtemp(prefix="live_tune_probe_")
    bank_file = os.path.join(tmpdir, "winner_bank.json")
    iso_file = os.path.join(tmpdir, "winner_bank_iso.json")
    os.environ["TMR_LIVE_TUNE_BANK"] = bank_file
    prior_live = os.environ.get("TMR_LIVE_TUNE")
    try:
        disabled = _phase_disabled()
        election = _phase_election(bank_file)
        fraction = _phase_fraction()
        isolation = _phase_bank_isolation(iso_file)
    finally:
        if prior_live is None:
            os.environ.pop("TMR_LIVE_TUNE", None)
        else:
            os.environ["TMR_LIVE_TUNE"] = prior_live

    if "error" in election:
        doc = {"schema": LIVE_TUNE_REPORT_SCHEMA,
               "error": election["error"]}
        print(json.dumps(doc))
        return 1

    tuner_rep = election.pop("tuner")
    decisions = tuner_rep["decisions"]
    replay = autotune_live.replay_decisions(
        decisions, wins_needed=tuner_rep["wins_needed"],
        win_ratio=tuner_rep["win_ratio"],
    )
    recorded = autotune_live.recorded_elections(decisions)
    shadow_wins = [d for d in decisions
                   if d["event"] == "shadow" and d["win"]]
    counters = tuner_rep["counters"]

    checks = {
        "disabled_identical": bool(
            disabled["attach_refused"]
            and disabled["bitwise_identical"]
            and not disabled["live_tune_metrics_keys"]
        ),
        "shadow_fraction_ok": bool(
            isinstance(fraction["shadow_fraction"], float)
            and fraction["shadow_fraction"] < 0.01
        ),
        "budget_respected": bool(
            counters["shadow_device_s"] <= tuner_rep["budget_s"]
            and fraction["shadow_device_s"] <= fraction["budget_s"]
        ),
        "promoted_decisively": bool(
            election["promotions"] == 1
            and election["promoted_arm"] == "fused"
            and len(shadow_wins) >= tuner_rep["wins_needed"]
            and all(d["cand_s_per_item"]
                    < tuner_rep["win_ratio"] * d["base_s_per_item"]
                    for d in shadow_wins)
            and election["bank_after_promote"] == "fused"
        ),
        "promotion_faster": bool(
            isinstance(election["speedup"], float)
            and election["speedup"] > 2.0
        ),
        "no_hot_path_compiles": election["hot_path_compiles"] == 0,
        "anomaly_demotes": bool(
            election["demotions"] == 1
            and election["restored_arm"] == "xla"
            and election["demote_cause"] == "mfu_drop"
            and election["serving_delay_s"] == SLOW_S
            and election["bank_after_demote"] == "xla"
        ),
        "replay_consistent": bool(recorded and replay == recorded),
        "bank_isolated": bool(
            isolation["isolated"] and isolation["stale_dropped"]
        ),
    }

    doc = {
        "schema": LIVE_TUNE_REPORT_SCHEMA,
        "ts": time.time(),
        "device_kind": "cpu",
        "config": {
            "knob": KNOB, "slow_s": SLOW_S, "fast_s": FAST_S,
            "bank_file": bank_file,
        },
        "tuner": tuner_rep,
        "disabled": disabled,
        "election": election,
        "fraction": fraction,
        "bank_isolation": isolation,
        "replay": {"recorded": recorded, "replayed": replay},
        "summary": {
            "shadow_fraction": fraction["shadow_fraction"],
            "demote_cause": election["demote_cause"],
            "promotion_speedup": election["speedup"],
            "pre_s_per_req": election["pre_s_per_req"],
            "post_s_per_req": election["post_s_per_req"],
            "bank_final_winner": election["bank_after_demote"],
        },
        "checks": checks,
    }
    problems = validate_live_tune_report(doc)
    if problems:  # self-check: the emitted line must validate
        for p in problems:
            _warn(f"validator: {p}")
        doc["validator_problems"] = problems
    line = json.dumps(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    for name, ok in checks.items():
        if not ok:
            _warn(f"check failed: {name}")
    return 0 if not problems and all(checks.values()) else 1


if __name__ == "__main__":
    sys.exit(main())

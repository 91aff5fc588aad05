"""Reader for the committed bench-history trajectory.

The driver has appended one ``BENCH_r0N.json`` record per round since
round 1, and ``BENCH_LIVE.json`` holds the builders' own last chip run —
but until this module the trajectory had no reader at all: a
regression between rounds was something a human noticed (or did not).
:func:`collect_bench_trend` reduces the history to one validated
``bench_trend/v1`` document — per-round headline img/s + MFU with
provenance (measured / carried / error, matching bench.py's
``carried: true`` outage promotion) and regressions between consecutive
usable rounds flagged against a relative threshold.

``scripts/bench_trend.py`` is the CLI; bench.py embeds the document per
round behind ``TMR_BENCH_TREND=1`` (banked like stage_breakdown, so a
reader wedge can never cost the headline).
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import List, Optional

from tmr_tpu.diagnostics import BENCH_TREND_SCHEMA

#: default relative drop between consecutive usable rounds that counts
#: as a regression (a 21.1 -> 19.9 headline is a flag; measurement
#: jitter at the chained-methodology noise floor is not)
DEFAULT_THRESHOLD = 0.05


def _round_entry(label: str, doc: Optional[dict]) -> dict:
    """One trajectory entry from a driver record's ``parsed`` payload
    (or a live bench record). Provenance: "measured" = the probe's own
    number; "carried" = an older committed measurement promoted through
    an outage record (bench.py ``carried: true`` / the pre-PR-1
    ``last_committed_live`` shape); "error" = no usable number."""
    rec = {"label": label, "value": None, "mfu": None, "source": "error",
           "error": None, "stale_hours": None}
    if not isinstance(doc, dict):
        return rec
    rec["error"] = doc.get("error")
    carried_rec = doc.get("last_committed_live") or doc.get(
        "last_live_uncommitted"
    )

    def _stale(*candidates):
        # a carried headline's AGE travels with it: top-level
        # stale_hours (bench.py's carried-promotion stamp) wins, the
        # carried record's own stamp is the pre-promotion fallback
        for v in candidates:
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                return float(v)
        return None

    value = doc.get("value")
    if value:
        rec["value"] = float(value)
        rec["mfu"] = doc.get("mfu")
        if doc.get("carried") or "error" in doc:
            rec["source"] = "carried"
            rec["stale_hours"] = _stale(
                doc.get("stale_hours"),
                carried_rec.get("stale_hours")
                if isinstance(carried_rec, dict) else None,
            )
        else:
            rec["source"] = "measured"
        if rec["mfu"] is None and isinstance(carried_rec, dict):
            rec["mfu"] = carried_rec.get("mfu")
        return rec
    # pre-promotion outage shape: value 0.0 but a carried record exists
    if isinstance(carried_rec, dict) and carried_rec.get("value"):
        rec["value"] = float(carried_rec["value"])
        rec["mfu"] = carried_rec.get("mfu")
        rec["source"] = "carried"
        rec["stale_hours"] = _stale(carried_rec.get("stale_hours"),
                                    doc.get("stale_hours"))
    return rec


def _read_json(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None


def collect_bench_trend(repo_dir: str,
                        threshold: float = DEFAULT_THRESHOLD,
                        max_carried_age_h: Optional[float] = None) -> dict:
    """Read ``BENCH_r*.json`` + the live bench files under ``repo_dir``
    and return the ``bench_trend/v1`` document.

    ``max_carried_age_h`` arms the staleness audit: carried rounds whose
    ``stale_hours`` exceed it (or carry no age stamp at all — fail
    closed) are listed under ``stale_carried`` and flip the
    ``carried_age_ok`` check. None (the default) adds neither key, so
    existing consumers see the exact pre-audit shape."""
    rounds: List[dict] = []
    numbered = []
    for path in glob.glob(os.path.join(repo_dir, "BENCH_r*.json")):
        # strict name match: a stray BENCH_rerun.json must be skipped,
        # not crash the one-JSON-line contract
        m = re.fullmatch(r"BENCH_(r(\d+))\.json", os.path.basename(path))
        if m:
            numbered.append((int(m.group(2)), m.group(1), path))
    for _n, label, path in sorted(numbered):
        doc = _read_json(path)
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        entry = _round_entry(label, parsed)
        if isinstance(doc, dict):
            entry["rc"] = doc.get("rc")
        rounds.append(entry)

    live = None
    # the watcher's working-tree bench_live.json (newest, uncommitted)
    # wins over the committed BENCH_LIVE.json when both are readable —
    # the same preference order bench.py's carry path applies
    for name in ("bench_live.json", "BENCH_LIVE.json"):
        doc = _read_json(os.path.join(repo_dir, name))
        if isinstance(doc, dict) and doc.get("value") and \
                "error" not in doc:
            live = _round_entry(name, doc)
            break
    if live is not None:
        rounds.append(live)

    if not rounds:
        return {
            "schema": BENCH_TREND_SCHEMA,
            "error": f"no BENCH_r*.json or live bench records under "
                     f"{repo_dir}",
        }

    regressions: List[dict] = []
    for field in ("value", "mfu"):
        prev = None
        for entry in rounds:
            cur = entry.get(field)
            if cur is None or entry["source"] == "error":
                continue
            if prev is not None and prev[1] > 0 \
                    and cur < prev[1] * (1.0 - threshold):
                regressions.append({
                    "field": field,
                    "from_label": prev[0],
                    "to_label": entry["label"],
                    "before": prev[1],
                    "after": cur,
                    "drop_pct": round(
                        (prev[1] - cur) / prev[1] * 100.0, 2
                    ),
                })
            prev = (entry["label"], cur)

    measured = sum(1 for r in rounds if r["source"] == "measured")
    out = {
        "schema": BENCH_TREND_SCHEMA,
        "threshold": threshold,
        "rounds": rounds,
        "regressions": regressions,
        "checks": {
            "rounds_read": len(rounds),
            "measured_rounds": measured,
            "regressed": bool(regressions),
        },
    }
    if max_carried_age_h is not None:
        stale = [
            {"label": r["label"], "stale_hours": r["stale_hours"]}
            for r in rounds if r["source"] == "carried" and (
                r["stale_hours"] is None  # unstamped age: fail closed
                or r["stale_hours"] > float(max_carried_age_h)
            )
        ]
        out["max_carried_age_h"] = float(max_carried_age_h)
        out["stale_carried"] = stale
        out["checks"]["carried_age_ok"] = not stale
    return out


# ----------------------------------------------------------- fleet report


def read_fleet_report(path: str) -> dict:
    """Reduce an ``elastic_serve_report/v1`` document (the
    elastic_serve_probe's one JSON line, or a pretty-printed file) to
    the rc-gating fields: the exactly-once contract — ZERO double-served
    request ids and the exact ``offered == completed + rejected + shed +
    errors`` reconciliation — plus a per-phase summary table.

    Returns ``{"rows": [...], "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable fleet report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"fleet report is an error record: "
                         f"{doc['error']}"}
    acc = doc.get("accounting")
    if not isinstance(acc, dict):
        return {"error": f"no accounting section in {path}"}
    rows: List[dict] = []
    for phase in doc.get("phases") or ():
        if not isinstance(phase, dict):
            continue
        pacc = (phase.get("fleet") or {}).get("accounting") or {}
        rows.append({
            "phase": phase.get("name"),
            "offered": phase.get("offered"),
            "completed": pacc.get("completed"),
            "rejected": pacc.get("rejected"),
            "shed": pacc.get("shed"),
            "errors": pacc.get("errors"),
            "resubmitted": pacc.get("resubmitted"),
            "fenced_results": pacc.get("fenced_results"),
            "double_served": pacc.get("double_served"),
        })

    def _ints(*keys):
        vals = [acc.get(k) for k in keys]
        return vals if all(
            isinstance(v, int) and not isinstance(v, bool) for v in vals
        ) else None

    parts = _ints("offered", "completed", "rejected", "shed", "errors")
    report_checks = doc.get("checks")
    return {
        "rows": rows,
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "zero_double_served": acc.get("double_served") == 0,
            "reconciliation_exact": bool(
                parts is not None
                and parts[0] == parts[1] + parts[2] + parts[3] + parts[4]
            ),
            "probe_checks_pass": bool(
                isinstance(report_checks, dict) and report_checks
                and all(report_checks.values())
            ),
        },
    }


# --------------------------------------------------------- gallery report


def read_gallery_report(path: str) -> dict:
    """Reduce a ``gallery_report/v1`` document (scripts/gallery_bench.py
    output) to the rc-gating fields: the fused-arm exactness pin, the
    backbone-amortization evidence (backbone executions == frames, not
    frames×N), and the prefilter recall/cut checks at the elected
    top-k — plus a per-rung prefilter table. When the document carries
    the OPTIONAL catalog-scale ``n_sweep`` section (``--sweep`` runs),
    its checks (sublinearity, selection recall, the argpartition tie
    contract, and the fleet-probe rc when re-run) gate fail-closed
    too; legacy documents without the section keep the original gate.

    Returns ``{"summary": ..., "rungs": [...], "checks": {...}}`` or
    ``{"error": ...}`` when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable gallery report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"gallery report is an error record: "
                         f"{doc['error']}"}
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        return {"error": f"no checks section in {path}"}
    bb = doc.get("backbone") or {}
    tput = doc.get("throughput") or {}
    pre = doc.get("prefilter") or {}
    rungs = [
        {"topk": r.get("topk"), "recall": r.get("recall"),
         "invocation_cut": r.get("invocation_cut"),
         "full_matches": r.get("full_matches")}
        for r in (pre.get("rungs") or ()) if isinstance(r, dict)
    ]
    out = {
        "summary": {
            "patterns": (doc.get("config") or {}).get("patterns"),
            "frames": (doc.get("config") or {}).get("frames"),
            "speedup_vs_n_loop": checks.get("speedup_vs_n_loop"),
            "backbone_executions": bb.get("executions"),
            "backbone_frames": bb.get("frames"),
            "pattern_frame_pairs": bb.get("pattern_frame_pairs"),
            "gallery_pattern_frames_per_sec": tput.get(
                "gallery_pattern_frames_per_sec"
            ),
            "elected_topk": pre.get("elected_topk"),
        },
        "rungs": rungs,
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "bitwise_exact": checks.get("bitwise_exact") is True,
            "backbone_amortized": checks.get("backbone_amortized")
            is True,
            "prefilter_recall_ok": checks.get("prefilter_recall_ok")
            is True,
            "prefilter_cut_ok": checks.get("prefilter_cut_ok") is True,
        },
    }
    sweep = doc.get("n_sweep")
    if isinstance(sweep, dict):  # optional section => gates activate
        scheck = sweep.get("checks")
        scheck = scheck if isinstance(scheck, dict) else {}
        fit = sweep.get("fit") or {}
        out["summary"]["index_exponent"] = fit.get("index_exponent")
        out["summary"]["linear_exponent"] = fit.get("linear_exponent")
        out["sweep_points"] = [
            {"n": p.get("n"), "linear_ms": p.get("linear_ms"),
             "index_ms": p.get("index_ms"), "recall": p.get("recall")}
            for p in (sweep.get("points") or ()) if isinstance(p, dict)
        ]
        for key in ("index_sublinear", "index_recall_ok",
                    "index_off_exact"):
            out["checks"][key] = scheck.get(key) is True
        if "fleet_probe_ok" in scheck:  # only --fleet-patterns runs
            out["checks"]["fleet_probe_ok"] = \
                scheck.get("fleet_probe_ok") is True
    return out


# ---------------------------------------------------------- stream report


def read_stream_report(path: str) -> dict:
    """Reduce a ``stream_report/v1`` document (scripts/stream_bench.py
    output) to the rc-gating fields: the backbone-amortization witness
    (executions ≪ frames on the bursty stream), the frames/s speedup
    over the frame-independent path, the bitwise-exactness pin on
    every "changed" frame, and the cross-stream isolation count.

    Returns ``{"summary": ..., "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable stream report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"stream report is an error record: "
                         f"{doc['error']}"}
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        return {"error": f"no checks section in {path}"}
    bb = doc.get("backbone") or {}
    tput = doc.get("throughput") or {}
    reuse = doc.get("reuse") or {}
    ex = doc.get("exactness") or {}
    return {
        "summary": {
            "streams": (doc.get("config") or {}).get("streams"),
            "frames": (doc.get("config") or {}).get("frames"),
            "backbone_executions": bb.get("executions"),
            "backbone_frames": bb.get("frames"),
            "reused_frames": reuse.get("reused_frames"),
            "changed_frames": reuse.get("changed_frames"),
            "stream_frames_per_sec": tput.get("stream_frames_per_sec"),
            "independent_frames_per_sec": tput.get(
                "independent_frames_per_sec"
            ),
            "speedup": tput.get("speedup"),
            "changed_frames_checked": ex.get("changed_frames_checked"),
        },
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "backbone_amortized": checks.get("backbone_amortized")
            is True,
            "speedup_ok": checks.get("speedup_ok") is True,
            "changed_frames_exact": checks.get("changed_frames_exact")
            is True,
            "cross_stream_isolated": checks.get("cross_stream_isolated")
            is True,
            "reuse_labeled": checks.get("reuse_labeled") is True,
        },
    }


# ----------------------------------------------------------- chaos report


def read_chaos_report(path: str) -> dict:
    """Reduce a ``serve_chaos_report/v1`` document
    (scripts/serve_chaos_probe.py output) to the rc-gating fields: the
    zero-pattern-loss invariant across repeated primary kills, the
    healthy-fleet fan-out byte-equality pin, and the fault ledger —
    every injected serve-tier fault observed AND accounted for.

    Returns ``{"summary": ..., "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable chaos report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"chaos report is an error record: "
                         f"{doc['error']}"}
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        return {"error": f"no checks section in {path}"}
    patterns = doc.get("patterns") or {}
    kills = doc.get("kills") or {}
    fsec = doc.get("faults") or {}
    injected = [r for r in (fsec.get("injected") or ())
                if isinstance(r, dict)]
    lost = patterns.get("lost")
    return {
        "summary": {
            "patterns_registered": patterns.get("registered"),
            "patterns_survived": patterns.get("survived"),
            "patterns_lost": len(lost) if isinstance(lost, list)
            else None,
            "kill_rounds": kills.get("rounds"),
            "workers_killed": kills.get("workers_killed"),
            "faults_injected": len(injected),
            "faults_fired": sum(int(r.get("fired") or 0)
                                for r in injected),
            "phases": [p.get("name") for p in (doc.get("phases") or ())
                       if isinstance(p, dict)],
        },
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "zero_patterns_lost": bool(
                checks.get("zero_patterns_lost") is True
                and isinstance(lost, list) and not lost
            ),
            "fanout_byte_identical": checks.get("fanout_byte_identical")
            is True,
            "all_faults_observed": bool(
                checks.get("all_faults_observed") is True
                and injected
                and all(int(r.get("fired") or 0) > 0 for r in injected)
            ),
            "all_faults_accounted": bool(
                checks.get("all_faults_accounted") is True
                and injected
                and all(int(r.get("accounted") or 0) > 0
                        for r in injected)
            ),
            "degraded_exactly_labeled": checks.get(
                "degraded_exactly_labeled"
            ) is True,
            "probe_checks_pass": bool(
                isinstance(checks, dict) and checks
                and all(checks.values())
            ),
        },
    }


# ------------------------------------------------------------ fleet obs


def read_fleet_obs_report(path: str) -> dict:
    """Reduce a ``fleet_obs_report/v1`` document
    (scripts/fleet_obs_probe.py output) to the rc-gating fields: the
    cross-process span-chain completeness pin, the exact sum-of-deltas
    metrics reconciliation, the stitched-timeline monotonicity after
    clock-offset correction, the anomaly-exactness pins (slow worker,
    beat gap, calm pass), and the <1% disabled-overhead bound.

    Returns ``{"summary": ..., "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable fleet obs report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"fleet obs report is an error record: "
                         f"{doc['error']}"}
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        return {"error": f"no checks section in {path}"}
    workers = doc.get("workers") or {}
    trace = doc.get("trace") or {}
    recon = doc.get("reconciliation") or {}
    overhead = doc.get("overhead") or {}
    anomalies = doc.get("anomalies") or {}
    chains = doc.get("chains") or {}
    return {
        "summary": {
            "workers": len(workers) if isinstance(workers, dict)
            else None,
            "beats": sum(int(r.get("beats") or 0)
                         for r in workers.values()
                         if isinstance(r, dict))
            if isinstance(workers, dict) else None,
            "trace_events": trace.get("events"),
            "trace_tracks": trace.get("tracks"),
            "complete_chains": chains.get("complete"),
            "counters_checked": recon.get("counters_checked"),
            "beat_errors": doc.get("beat_errors"),
            "anomaly_kinds": sorted({
                rec.get("anomaly")
                for recs in anomalies.values()
                if isinstance(recs, list)
                for rec in recs
                if isinstance(rec, dict)
            }),
            "overhead_disabled_pct": overhead.get(
                "overhead_disabled_pct"
            ),
        },
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "span_chain_complete": checks.get("span_chain_complete")
            is True,
            "metrics_reconciled": bool(
                checks.get("metrics_reconciled") is True
                and recon.get("exact") is True
            ),
            "stitched_monotone": bool(
                checks.get("stitched_monotone") is True
                and trace.get("monotone") is True
            ),
            "slow_worker_exact": checks.get("slow_worker_exact")
            is True,
            "beat_gap_exact": checks.get("beat_gap_exact") is True,
            "calm_quiet": checks.get("calm_quiet") is True,
            "overhead_ok": bool(
                checks.get("overhead_ok") is True
                and isinstance(overhead.get("overhead_disabled_pct"),
                               (int, float))
                and overhead["overhead_disabled_pct"] < 1.0
            ),
        },
    }


# ----------------------------------------------------------- serve sweep


def read_serve_sweep(path: str) -> dict:
    """Reduce a ``serve_bench.py --mesh`` sweep file (JSONL, one
    serve_report/v1 per mesh shape) to a comparable table: per-shape
    throughput, scaling vs the single-device engine, parity mode,
    latency p99, and the AOT cold-compile pin — so a trend reader can
    gate a mesh-scaling regression the same way it gates the headline.

    Returns ``{"rows": [...], "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable mesh rounds."""
    rows: List[dict] = []
    try:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
    except OSError as e:
        return {"error": f"unreadable sweep file {path}: {e}"}
    for ln in lines:
        try:
            doc = json.loads(ln)
        except ValueError:
            continue
        if not isinstance(doc, dict) or "mesh" not in doc:
            continue
        checks = doc.get("checks") or {}
        workloads = doc.get("workloads") or [{}]
        w0 = workloads[0] if isinstance(workloads[0], dict) else {}
        rows.append({
            "spec": (doc["mesh"] or {}).get("spec"),
            "shape": (doc["mesh"] or {}).get("shape"),
            "devices": (doc.get("config") or {}).get("devices"),
            "throughput_img_per_sec": w0.get("throughput_img_per_sec"),
            "single_device_img_per_sec": w0.get(
                "single_device_img_per_sec"
            ),
            "scaling": checks.get("scaling_vs_single_device"),
            "scaling_ok": checks.get("scaling_ok"),
            "parity": checks.get("parity"),
            "exact_match": checks.get("exact_match"),
            "p99_ms": checks.get("p99_ms"),
            "cold_compiles_after_warmup": (
                (doc.get("aot") or {}).get("compile_events_after_warmup")
            ),
        })
    if not rows:
        return {"error": f"no mesh serve_report lines in {path}"}
    return {
        "rows": rows,
        "checks": {
            "shapes_read": len(rows),
            "all_exact": all(bool(r["exact_match"]) for r in rows),
            "all_scaling_ok": all(bool(r["scaling_ok"]) for r in rows),
            # fail CLOSED like all_exact/all_scaling_ok: a line with no
            # AOT evidence (missing section / null count) is NOT warm
            "all_warm": all(
                r["cold_compiles_after_warmup"] == 0 for r in rows
            ),
        },
    }


# ------------------------------------------------------------ live tune


def read_live_tune_report(path: str) -> dict:
    """Reduce a ``live_tune_report/v1`` document
    (scripts/live_tune_probe.py output) to the rc-gating fields: the
    disabled-mode bitwise-identity pin, the shadow-fraction (<1% of
    steady-state device seconds) and budget bounds, the
    promotion-speedup + zero-hot-path-cold-compiles evidence, the
    anomaly-demotion pin with its recorded cause, and the
    decision-log replay-consistency check.

    Returns ``{"summary": ..., "checks": {...}}`` or ``{"error": ...}``
    when the file holds no readable report."""
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError as e:
        return {"error": f"unreadable live tune report {path}: {e}"}
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        for ln in text.splitlines():  # JSONL fallback: first valid line
            try:
                doc = json.loads(ln)
                break
            except ValueError:
                continue
    if not isinstance(doc, dict):
        return {"error": f"no JSON document in {path}"}
    if "error" in doc:
        return {"error": f"live tune report is an error record: "
                         f"{doc['error']}"}
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        return {"error": f"no checks section in {path}"}
    tuner = doc.get("tuner") or {}
    counters = tuner.get("counters") or {}
    summary = doc.get("summary") or {}
    decisions = tuner.get("decisions") or ()
    fraction = summary.get("shadow_fraction")
    return {
        "summary": {
            "device_kind": doc.get("device_kind"),
            "knob": tuner.get("knob"),
            "incumbent": tuner.get("incumbent"),
            "shadow_runs": counters.get("shadow_runs"),
            "shadow_device_s": counters.get("shadow_device_s"),
            "shadow_fraction": fraction,
            "promotions": counters.get("promotions"),
            "demotions": counters.get("demotions"),
            "refusals": counters.get("refusals"),
            "decisions": len(decisions)
            if isinstance(decisions, list) else None,
            "demote_cause": summary.get("demote_cause"),
            "promotion_speedup": summary.get("promotion_speedup"),
        },
        "checks": {
            # fail CLOSED: a missing/garbled field is NOT a pass
            "disabled_identical": checks.get("disabled_identical")
            is True,
            "shadow_fraction_ok": bool(
                checks.get("shadow_fraction_ok") is True
                and isinstance(fraction, (int, float))
                and fraction < 0.01
            ),
            "budget_respected": checks.get("budget_respected") is True,
            "promoted_decisively": checks.get("promoted_decisively")
            is True,
            "promotion_faster": checks.get("promotion_faster") is True,
            "no_hot_path_compiles": checks.get("no_hot_path_compiles")
            is True,
            "anomaly_demotes": bool(
                checks.get("anomaly_demotes") is True
                and summary.get("demote_cause")
            ),
            "replay_consistent": checks.get("replay_consistent")
            is True,
            "bank_isolated": checks.get("bank_isolated") is True,
        },
    }

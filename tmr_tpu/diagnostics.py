"""Shared diagnostic warning types + the structured gate-refusal registry
(dependency-free at import time — importable from any layer: ops, models,
utils).

``FormulationFallbackWarning`` is the structural contract between the
trace-time formulation dispatchers (models/vit.py attention, ops/xcorr.py
correlation) and the measurement harnesses (utils/autotune.py sweeps,
scripts/profile_breakdown.py): when an EXPLICITLY requested formulation is
refused by its gate/dtype precondition and a fallback traces instead, the
dispatcher warns with this category carrying ``env_var`` — so harnesses can
detect by category + attribute (not message substrings) that a timing
recorded under the requested label actually measured the fallback.

The gate-refusal REGISTRY is the machine-readable side of the same story
(round-5 verdict #1: on the live TPU every require_tpu kernel fell back
and the gates swallowed WHY). Every refusal inside the compiled
self-checks (ops/flash_attn._self_check and the gates built on it —
packed_window_ok, packed_global_ok, flash_attention_ok, …) records a ``gate_probe.json``-schema cause here: refusal category,
exception class + message when one was swallowed, the tile/geometry
config the verdict keys on, and the device kind. Consumers drain it:
scripts/gate_probe.py --json emits the causes next to each probe, and the
autotune sweeps attach them to fallback-labeled rows so a "(fallback)"
timing always travels with the reason the requested kernel refused.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import warnings
from typing import Dict, List, Optional, Tuple

#: schema tag stamped on every refusal record and on the gate_probe.py
#: --json document — bump when the record shape changes incompatibly
GATE_PROBE_SCHEMA = "gate_probe/v1"

#: schema tag of the structured map-phase extraction report
#: (parallel/mapreduce.py MapReport, emitted by `map --report_out`) — the
#: gate_probe/v1 pattern applied to fault tolerance: per-shard
#: status/attempts/causes, quarantine and resume lists, skipped-image and
#: non-finite counts, retry totals, wall-clock per shard. bench/CI assert
#: on it via ``validate_map_report`` (scripts/chaos_probe.py).
MAP_REPORT_SCHEMA = "map_report/v1"

#: closed per-shard status vocabulary in a map_report/v1 document
MAP_SHARD_STATUSES = ("ok", "quarantined", "resumed")

#: closed per-attempt failure-cause vocabulary ("timeout" = the per-shard
#: wall-clock budget elapsed; "exception" carries class + message)
MAP_FAILURE_CAUSES = ("timeout", "exception")


#: schema tag of a metrics-registry snapshot (tmr_tpu/obs/metrics.py
#: ``MetricsRegistry.snapshot()``): every named counter/gauge/histogram at
#: one instant. Report emitters attach it under a ``metrics`` key so one
#: JSON line carries latency AND counter state; ``validate_map_report`` /
#: ``validate_serve_report`` validate the attachment when present.
METRICS_REPORT_SCHEMA = "metrics_report/v1"


def validate_metrics_report(doc: dict) -> List[str]:
    """Structural check of a metrics_report/v1 document; returns a list
    of problems (empty == valid). Dependency-free like the others."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != METRICS_REPORT_SCHEMA:
        problems.append(
            f"schema != {METRICS_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    for section in ("counters", "gauges", "histograms"):
        if not isinstance(doc.get(section), dict):
            problems.append(f"{section}: not a dict")
    for name, v in (doc.get("counters") or {}).items():
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            problems.append(f"counters[{name!r}]: not a number")
    for name, v in (doc.get("gauges") or {}).items():
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            problems.append(f"gauges[{name!r}]: not a number")
    for name, h in (doc.get("histograms") or {}).items():
        where = f"histograms[{name!r}]"
        if not isinstance(h, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("buckets_le", "counts", "count", "sum",
                    "p50", "p95", "p99"):
            if key not in h:
                problems.append(f"{where}: missing {key!r}")
        bounds, counts = h.get("buckets_le"), h.get("counts")
        if isinstance(bounds, list) and isinstance(counts, list) \
                and len(counts) != len(bounds) + 1:
            problems.append(
                f"{where}: counts must have len(buckets_le)+1 entries "
                "(overflow bucket)"
            )
    return problems


def _validate_metrics_attachment(doc: dict) -> List[str]:
    """Shared rule for report documents carrying an optional ``metrics``
    key: when present it must be a valid metrics_report/v1."""
    if "metrics" not in doc:
        return []
    return [f"metrics: {p}" for p in validate_metrics_report(doc["metrics"])]


def _validate_mfu_attachment(doc: dict) -> List[str]:
    """Shared rule for report documents carrying an optional ``mfu`` key
    (serve_report/map_report when the flight recorder is on): when
    present it must be a valid mfu_report/v1."""
    if "mfu" not in doc:
        return []
    return [f"mfu: {p}" for p in validate_mfu_report(doc["mfu"])]


#: schema tag of the per-program device-time / MFU accounting document
#: (tmr_tpu/obs/devtime.py ``mfu_report()``): for every executed
#: ``Predictor._compiled`` program — achieved FLOP/s from attributed
#: device seconds, MFU against the platform peak, and a compute- vs
#: memory-bound roofline classification from the program's arithmetic
#: intensity. Attached to serve_report/map_report under ``mfu`` when
#: ``TMR_FLIGHT=1``; scripts/obs_watch.py is the measured proof.
MFU_REPORT_SCHEMA = "mfu_report/v1"

#: closed roofline-classification vocabulary in an mfu_report/v1
#: program record ("unknown" = no bytes-accessed figure was available,
#: so the intensity could not be placed against the ridge)
ROOFLINE_BOUNDS = ("compute", "memory", "unknown")

#: closed cost-model provenance vocabulary: "xla" = the compiled
#: program's own ``cost_analysis()``, "analytic" = the
#: devtime.forward_tflops_per_image model, "none" = neither applied
MFU_COST_SOURCES = ("xla", "analytic", "none")


def validate_mfu_report(doc: dict) -> List[str]:
    """Structural check of an mfu_report/v1 document; returns a list of
    problems (empty == valid). Dependency-free like the others."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != MFU_REPORT_SCHEMA:
        problems.append(
            f"schema != {MFU_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    plat = doc.get("platform")
    if not isinstance(plat, dict):
        problems.append("platform: not a dict")
    else:
        for key in ("backend", "device_kind", "peak_tflops", "peak_gbps",
                    "peak_source"):
            if key not in plat:
                problems.append(f"platform: missing {key!r}")
        pk = plat.get("peak_tflops")
        if not isinstance(pk, (int, float)) or isinstance(pk, bool) \
                or pk <= 0:
            problems.append("platform.peak_tflops: not a positive number")
    programs = doc.get("programs")
    if not isinstance(programs, list):
        problems.append("programs: not a list")
        programs = []
    for i, p in enumerate(programs):
        where = f"programs[{i}]"
        if not isinstance(p, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("kind", "key", "bucket", "calls", "warmup_calls",
                    "dispatch_s", "device_s", "wall_s", "cost_source",
                    "mfu", "bound"):
            if key not in p:
                problems.append(f"{where}: missing {key!r}")
        if p.get("bound") not in ROOFLINE_BOUNDS:
            problems.append(f"{where}: bad bound {p.get('bound')!r}")
        if p.get("cost_source") not in MFU_COST_SOURCES:
            problems.append(
                f"{where}: bad cost_source {p.get('cost_source')!r}"
            )
        mfu = p.get("mfu")
        if mfu is not None and (
            not isinstance(mfu, (int, float)) or isinstance(mfu, bool)
        ):
            problems.append(f"{where}.mfu: not a number or null")
    totals = doc.get("totals")
    if not isinstance(totals, dict):
        problems.append("totals: not a dict")
    else:
        for key in ("device_s", "flops", "achieved_tflops", "mfu"):
            if key not in totals:
                problems.append(f"totals: missing {key!r}")
    return problems


#: closed anomaly vocabulary the flight recorder's health watch can emit
#: (tmr_tpu/obs/flight.py HealthWatch): recompile_storm = key-change
#: compile events over threshold in one window; latency_regression =
#: window p99 beyond factor x rolling baseline; queue_saturation =
#: batcher depth over threshold; cache_hit_collapse = window hit rate
#: collapsed vs rolling baseline; mfu_drop = window achieved FLOP/s
#: below factor x rolling baseline. The fleet kinds (FLEET_ANOMALY_KINDS,
#: tmr_tpu/obs/fleetobs.py FleetHealthWatch over the beat-merged
#: registry) extend the same vocabulary: worker_outlier_latency = one
#: worker's window p95 beyond factor x the median of its peers;
#: partition_skew = one worker drawing a window request share beyond
#: factor x the fair share; fleet_mfu_drop = cluster-summed window
#: FLOP/s below factor x rolling baseline; beat_gap = a live worker's
#: last heartbeat older than factor x the beat interval.
FLEET_ANOMALY_KINDS = (
    "worker_outlier_latency",
    "partition_skew",
    "fleet_mfu_drop",
    "beat_gap",
)

ANOMALY_KINDS = (
    "recompile_storm",
    "latency_regression",
    "queue_saturation",
    "cache_hit_collapse",
    "mfu_drop",
) + FLEET_ANOMALY_KINDS


def validate_anomaly(rec: dict) -> List[str]:
    """Structural check of one anomaly record (gate_refused-style cause
    record: closed-vocabulary kind + message + numeric evidence)."""
    problems: List[str] = []
    if not isinstance(rec, dict):
        return [f"not a dict: {type(rec).__name__}"]
    if rec.get("anomaly") not in ANOMALY_KINDS:
        problems.append(f"anomaly: bad kind {rec.get('anomaly')!r}")
    if not isinstance(rec.get("message"), str) or not rec.get("message"):
        problems.append("message: not a non-empty string")
    if not isinstance(rec.get("evidence"), dict):
        problems.append("evidence: not a dict")
    return problems


#: schema tag of the serving-engine health document
#: (``ServeEngine.health()``): queue depths, per-device occupancy, cache
#: stats, compile-event tallies, and the anomalies the health watch
#: fired this pass — the admission-control input ROADMAP item 3
#: consumes. The heartbeat writer appends one per interval as JSONL.
HEALTH_REPORT_SCHEMA = "health_report/v1"


def validate_health_report(doc: dict) -> List[str]:
    """Structural check of a health_report/v1 document; returns a list
    of problems (empty == valid)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    problems += _validate_quant_attachment(doc)
    if doc.get("schema") != HEALTH_REPORT_SCHEMA:
        problems.append(
            f"schema != {HEALTH_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    for key, typ in (("ts", (int, float)), ("uptime_s", (int, float)),
                     ("closed", bool), ("inflight", int)):
        if not isinstance(doc.get(key), typ) or (
            typ is int and isinstance(doc.get(key), bool)
        ):
            problems.append(f"{key}: not a {typ}")
    queues = doc.get("queues")
    if not isinstance(queues, dict) or not isinstance(
        queues.get("pending"), int
    ) or not isinstance(queues.get("per_bucket"), dict):
        problems.append("queues: missing pending/per_bucket")
    if not isinstance(doc.get("devices"), list):
        problems.append("devices: not a list")
    if not isinstance(doc.get("per_device_batches"), dict):
        problems.append("per_device_batches: not a dict")
    caches = doc.get("caches")
    if not isinstance(caches, dict):
        problems.append("caches: not a dict")
    else:
        for which in ("result", "feature"):
            sub = caches.get(which)
            if not isinstance(sub, dict) or not all(
                k in sub for k in ("hits", "misses", "evictions")
            ):
                problems.append(
                    f"caches.{which}: missing hits/misses/evictions"
                )
    counters = doc.get("counters")
    if not isinstance(counters, dict) or not all(
        isinstance(v, (int, float)) and not isinstance(v, bool)
        for v in counters.values()
    ):
        problems.append("counters: not a dict of numbers")
    compile_rec = doc.get("compile")
    if not isinstance(compile_rec, dict) or not all(
        isinstance(compile_rec.get(k), int)
        for k in ("total", "cold", "key_change")
    ):
        problems.append("compile: missing total/cold/key_change ints")
    anomalies = doc.get("anomalies")
    if not isinstance(anomalies, list):
        problems.append("anomalies: not a list")
    else:
        for i, rec in enumerate(anomalies):
            problems += [f"anomalies[{i}]: {p}" for p in
                         validate_anomaly(rec)]
    # optional overload-control sections (present only when the engine
    # runs with admission/degradation enabled — the default-knobs shape
    # is exactly the PR 8 one)
    if "admission" in doc:
        adm = doc["admission"]
        if not isinstance(adm, dict) or not all(
            k in adm for k in ("enabled", "max_pending", "in_system")
        ):
            problems.append(
                "admission: missing enabled/max_pending/in_system"
            )
    if "degrade" in doc:
        deg = doc["degrade"]
        if not isinstance(deg, dict) or not isinstance(
            deg.get("level"), int
        ) or not isinstance(deg.get("steps"), list):
            problems.append("degrade: missing level/steps")
    # optional mesh-serving sections (present only under a mesh plan —
    # the default-engine shape stays byte-identical to PR 8)
    problems += _validate_mesh_attachment(doc)
    per_group = (queues or {}).get("per_group") if isinstance(
        queues, dict
    ) else None
    if per_group is not None:
        if not isinstance(per_group, dict) or not all(
            isinstance(rec, dict) and isinstance(rec.get("pending"), int)
            and isinstance(rec.get("per_bucket"), dict)
            for rec in per_group.values()
        ):
            problems.append(
                "queues.per_group: not {group: {pending, per_bucket}}"
            )
    return problems


#: schema tag of the flight-recorder probe document emitted by
#: scripts/obs_watch.py: the mfu_report from a measured tiny serve
#: workload (analytic-vs-cost_analysis FLOPs envelope checked), a
#: validated health_report + heartbeat JSONL round-trip, injected
#: recompile-storm and queue-saturation anomaly firings, and the
#: disabled-mode overhead of the whole flight layer. bench_guard wraps
#: the probe, so an error record ({"schema": ..., "error": str}) is
#: contractually valid.
FLIGHT_REPORT_SCHEMA = "flight_report/v1"


def validate_flight_report(doc: dict) -> List[str]:
    """Structural check of a flight_report/v1 document; returns a list
    of problems (empty == valid). An error record is contractually
    valid (the bench_guard wedge path)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != FLIGHT_REPORT_SCHEMA:
        problems.append(
            f"schema != {FLIGHT_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    if not isinstance(doc.get("config"), dict):
        problems.append("config: not a dict")
    problems += [f"mfu: {p}" for p in validate_mfu_report(
        doc.get("mfu") or {}
    )]
    problems += [f"health: {p}" for p in validate_health_report(
        doc.get("health") or {}
    )]
    anomalies = doc.get("anomalies")
    if not isinstance(anomalies, dict):
        problems.append("anomalies: not a dict")
    else:
        for section in ("recompile_storm", "queue_saturation"):
            recs = anomalies.get(section)
            if not isinstance(recs, list):
                problems.append(f"anomalies.{section}: not a list")
                continue
            for i, rec in enumerate(recs):
                problems += [f"anomalies.{section}[{i}]: {p}"
                             for p in validate_anomaly(rec)]
    overhead = doc.get("overhead")
    if not isinstance(overhead, dict):
        problems.append("overhead: not a dict")
    else:
        for key in ("disabled_ns_per_check", "overhead_disabled_pct"):
            if not isinstance(overhead.get(key), (int, float)):
                problems.append(f"overhead: missing {key!r}")
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("mfu_finite", "flops_envelope_ok", "health_valid",
                    "heartbeat_roundtrip", "storm_exact", "queue_exact",
                    "overhead_ok"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the fleet-observability probe document emitted by
#: scripts/fleet_obs_probe.py (plane in tmr_tpu/obs/fleetobs.py): the
#: per-worker + merged beat-folded registries with the exact
#: sum-of-deltas reconciliation, the cross-process span-chain evidence,
#: the stitched-timeline summary (per-track clock offsets + post-
#: correction monotonicity), the fleet HealthWatch firings per phase,
#: and the disabled-mode overhead of the whole plane. bench_guard wraps
#: the probe, so an error record ({"schema": ..., "error": str}) is
#: contractually valid.
FLEET_OBS_REPORT_SCHEMA = "fleet_obs_report/v1"


def validate_fleet_obs_report(doc: dict) -> List[str]:
    """Structural check of a fleet_obs_report/v1 document; returns a
    list of problems (empty == valid). An error record is contractually
    valid (the bench_guard wedge path)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != FLEET_OBS_REPORT_SCHEMA:
        problems.append(
            f"schema != {FLEET_OBS_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    if not isinstance(doc.get("config"), dict):
        problems.append("config: not a dict")
    workers = doc.get("workers")
    if not isinstance(workers, dict):
        problems.append("workers: not a dict")
    else:
        for wid, rec in workers.items():
            if not isinstance(rec, dict):
                problems.append(f"workers[{wid!r}]: not a dict")
                continue
            for key in ("beats", "spans"):
                if not isinstance(rec.get(key), int) or isinstance(
                    rec.get(key), bool
                ):
                    problems.append(f"workers[{wid!r}].{key}: not an int")
            clock = rec.get("clock")
            if clock is not None and (
                not isinstance(clock, dict)
                or not all(isinstance(clock.get(k), (int, float))
                           for k in ("offset_s", "err_s"))
            ):
                problems.append(
                    f"workers[{wid!r}].clock: missing offset_s/err_s"
                )
    problems += [f"merged: {p}" for p in validate_metrics_report(
        doc.get("merged") or {}
    )]
    recon = doc.get("reconciliation")
    if not isinstance(recon, dict) or not isinstance(
        recon.get("exact"), bool
    ):
        problems.append("reconciliation: missing exact bool")
    trace = doc.get("trace")
    if not isinstance(trace, dict):
        problems.append("trace: not a dict")
    else:
        for key in ("events", "tracks"):
            if not isinstance(trace.get(key), int) or isinstance(
                trace.get(key), bool
            ):
                problems.append(f"trace.{key}: not an int")
        if not isinstance(trace.get("monotone"), bool):
            problems.append("trace.monotone: not a bool")
    anomalies = doc.get("anomalies")
    if not isinstance(anomalies, dict):
        problems.append("anomalies: not a dict")
    else:
        for section, recs in anomalies.items():
            if not isinstance(recs, list):
                problems.append(f"anomalies.{section}: not a list")
                continue
            for i, rec in enumerate(recs):
                problems += [f"anomalies.{section}[{i}]: {p}"
                             for p in validate_anomaly(rec)]
    if not isinstance(doc.get("beat_errors"), int) or isinstance(
        doc.get("beat_errors"), bool
    ):
        problems.append("beat_errors: not an int")
    overhead = doc.get("overhead")
    if not isinstance(overhead, dict):
        problems.append("overhead: not a dict")
    else:
        for key in ("disabled_ns_per_check", "overhead_disabled_pct"):
            if not isinstance(overhead.get(key), (int, float)):
                problems.append(f"overhead: missing {key!r}")
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("span_chain_complete", "metrics_reconciled",
                    "stitched_monotone", "slow_worker_exact",
                    "beat_gap_exact", "calm_quiet", "overhead_ok"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the bench-history trend document emitted by
#: scripts/bench_trend.py (core reader in tmr_tpu/utils/bench_trend.py):
#: the committed BENCH_r0*.json driver records plus the live bench
#: files, reduced to one headline/MFU trajectory with regressions
#: between measured rounds flagged. bench.py embeds one per round
#: behind TMR_BENCH_TREND=1.
BENCH_TREND_SCHEMA = "bench_trend/v1"

#: closed per-round provenance vocabulary in a bench_trend/v1 document:
#: "measured" = the round's probe produced its own number, "carried" =
#: the record promoted an older committed measurement (bench.py's
#: ``carried: true`` outage path), "error" = no usable number at all.
BENCH_TREND_SOURCES = ("measured", "carried", "error")


def validate_bench_trend(doc: dict) -> List[str]:
    """Structural check of a bench_trend/v1 document; returns a list of
    problems (empty == valid). An error record ({"schema": ...,
    "error": str}) is contractually valid."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != BENCH_TREND_SCHEMA:
        problems.append(
            f"schema != {BENCH_TREND_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    rounds = doc.get("rounds")
    if not isinstance(rounds, list) or not rounds:
        problems.append("rounds: not a non-empty list")
        rounds = []
    for i, r in enumerate(rounds):
        where = f"rounds[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("label", "source", "value", "mfu"):
            if key not in r:
                problems.append(f"{where}: missing {key!r}")
        if r.get("source") not in BENCH_TREND_SOURCES:
            problems.append(f"{where}: bad source {r.get('source')!r}")
        for key in ("value", "mfu"):
            v = r.get(key)
            if v is not None and (
                not isinstance(v, (int, float)) or isinstance(v, bool)
            ):
                problems.append(f"{where}.{key}: not a number or null")
    regs = doc.get("regressions")
    if not isinstance(regs, list):
        problems.append("regressions: not a list")
        regs = []
    for i, r in enumerate(regs):
        where = f"regressions[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("field", "from_label", "to_label", "before", "after",
                    "drop_pct"):
            if key not in r:
                problems.append(f"{where}: missing {key!r}")
        if r.get("field") not in ("value", "mfu"):
            problems.append(f"{where}: bad field {r.get('field')!r}")
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("measured_rounds", "regressed"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


def validate_map_report(doc: dict) -> List[str]:
    """Structural check of a map_report/v1 document; returns a list of
    problems (empty == valid). Dependency-free so CI harnesses can gate on
    the report without importing the extraction stack."""
    problems: List[str] = []
    problems += _validate_metrics_attachment(doc)
    problems += _validate_mfu_attachment(doc)
    if doc.get("schema") != MAP_REPORT_SCHEMA:
        problems.append(f"schema != {MAP_REPORT_SCHEMA}: {doc.get('schema')!r}")
    shards = doc.get("shards")
    if not isinstance(shards, list):
        return problems + ["shards: not a list"]
    for i, rec in enumerate(shards):
        where = f"shards[{i}]"
        if not isinstance(rec, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("shard", "status", "attempts", "images",
                    "skipped_images", "nonfinite_images", "wall_s"):
            if key not in rec:
                problems.append(f"{where}: missing {key!r}")
        if rec.get("status") not in MAP_SHARD_STATUSES:
            problems.append(f"{where}: bad status {rec.get('status')!r}")
        causes = rec.get("causes", ())
        if not isinstance(causes, (list, tuple)):
            problems.append(f"{where}.causes: not a list")
            causes = ()
        for j, cause in enumerate(causes):
            if not isinstance(cause, dict):
                problems.append(f"{where}.causes[{j}]: not a dict")
            elif cause.get("cause") not in MAP_FAILURE_CAUSES:
                problems.append(
                    f"{where}.causes[{j}]: bad cause {cause.get('cause')!r}"
                )
    totals = doc.get("totals")
    if not isinstance(totals, dict):
        problems.append("totals: not a dict")
    else:
        for key in ("shards", "ok", "quarantined", "resumed", "images",
                    "skipped_images", "nonfinite_images", "retries"):
            if key not in totals:
                problems.append(f"totals: missing {key!r}")
    for key in ("quarantined", "resumed"):
        if not isinstance(doc.get(key), list):
            problems.append(f"{key}: not a list")
    return problems

#: schema tag of the elastic map-phase run document
#: (parallel/elastic.py ElasticCoordinator.report()): per-shard final
#: status + winning worker/epoch, per-worker commit/failure tallies with
#: drain flags, every lease reassignment with a closed-vocab cause,
#: every fenced (stale-epoch) commit rejection, and totals that must
#: reconcile exactly — shards = committed + resumed + quarantined, with
#: reassigned shards counted once under whoever finally committed them.
ELASTIC_REPORT_SCHEMA = "elastic_report/v1"

#: closed reassignment-cause vocabulary shared by the lease-service
#: clients (elastic_report/v1 map shards, elastic_serve_report/v1
#: traffic partitions): stale_heartbeat = the lease's heartbeat went
#: stale past the TTL (dead or paused worker); worker_exit = the
#: worker left while it held the lease — a dropped control connection
#: (kill -9 / crash) or, for fleet workers, a clean ``bye`` that still
#: held partitions (serve leases are held for the worker's lifetime,
#: so a graceful leave releases through the same path); straggler = a
#: speculative duplicate lease was
#: issued because the shard's runtime exceeded the rolling-median-based
#: bound; poison_worker = the worker reported the resource failed
#: (after N distinct such failures the worker is drained); scale_out =
#: a traffic partition moved to a newly recruited serve worker to
#: spread load (fleet rebalance-on-join — never emitted by the map
#: client).
ELASTIC_REASSIGN_CAUSES = (
    "stale_heartbeat", "worker_exit", "straggler", "poison_worker",
    "scale_out",
)

#: the MAP client's subset: validate_elastic_report stays exactly as
#: tight as before the fleet landed — a map-shard reassignment tagged
#: scale_out is a drift the validator must still catch (only the fleet
#: section validator accepts the full shared vocabulary)
MAP_REASSIGN_CAUSES = (
    "stale_heartbeat", "worker_exit", "straggler", "poison_worker",
)

#: closed final per-shard status vocabulary in an elastic_report/v1
ELASTIC_SHARD_STATUSES = ("committed", "resumed", "quarantined")

#: closed fence-op vocabulary: where a stale-epoch writer was rejected
#: ("precommit" = before its marker was written — the normal path;
#: "commit" = at result submission, the narrow in-flight race window)
ELASTIC_FENCE_OPS = ("precommit", "commit")


def validate_elastic_report(doc: dict) -> List[str]:
    """Structural + reconciliation check of an elastic_report/v1
    document; returns a list of problems (empty == valid).
    Dependency-free like the other validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    problems += _validate_metrics_attachment(doc)
    if doc.get("schema") != ELASTIC_REPORT_SCHEMA:
        problems.append(
            f"schema != {ELASTIC_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    shards = doc.get("shards")
    if not isinstance(shards, list):
        problems.append("shards: not a list")
        shards = []
    by_status = {s: 0 for s in ELASTIC_SHARD_STATUSES}
    for i, rec in enumerate(shards):
        where = f"shards[{i}]"
        if not isinstance(rec, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("index", "shard", "status", "worker", "epoch",
                    "assignments", "failures", "images", "wall_s"):
            if key not in rec:
                problems.append(f"{where}: missing {key!r}")
        status = rec.get("status")
        if status not in ELASTIC_SHARD_STATUSES:
            problems.append(f"{where}: bad status {status!r}")
        else:
            by_status[status] += 1
        if status == "committed" and not rec.get("worker"):
            problems.append(f"{where}: committed without a worker")
        fails = rec.get("failures", ())
        if not isinstance(fails, (list, tuple)):
            problems.append(f"{where}.failures: not a list")
            fails = ()
        for j, f in enumerate(fails):
            if not isinstance(f, dict) or "worker" not in f:
                problems.append(f"{where}.failures[{j}]: missing worker")
    workers = doc.get("workers")
    if not isinstance(workers, dict):
        problems.append("workers: not a dict")
        workers = {}
    for wid, w in workers.items():
        if not isinstance(w, dict) or not all(
            k in w for k in ("committed", "failed_shards", "drained")
        ):
            problems.append(
                f"workers[{wid!r}]: missing committed/failed_shards/"
                "drained"
            )
    reassignments = doc.get("reassignments")
    if not isinstance(reassignments, list):
        problems.append("reassignments: not a list")
        reassignments = []
    for i, r in enumerate(reassignments):
        where = f"reassignments[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("shard", "worker", "epoch", "cause"):
            if key not in r:
                problems.append(f"{where}: missing {key!r}")
        if r.get("cause") not in MAP_REASSIGN_CAUSES:
            problems.append(f"{where}: bad cause {r.get('cause')!r}")
    fenced = doc.get("fenced_rejections")
    if not isinstance(fenced, list):
        problems.append("fenced_rejections: not a list")
        fenced = []
    for i, r in enumerate(fenced):
        where = f"fenced_rejections[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("shard", "worker", "epoch", "op"):
            if key not in r:
                problems.append(f"{where}: missing {key!r}")
        if r.get("op") not in ELASTIC_FENCE_OPS:
            problems.append(f"{where}: bad op {r.get('op')!r}")
    for key in ("quarantined", "resumed"):
        if not isinstance(doc.get(key), list):
            problems.append(f"{key}: not a list")
    totals = doc.get("totals")
    if not isinstance(totals, dict):
        problems.append("totals: not a dict")
    else:
        for key in ("shards", "committed", "resumed", "quarantined",
                    "reassignments", "fenced_rejections", "workers",
                    "drained_workers", "wall_s"):
            if key not in totals:
                problems.append(f"totals: missing {key!r}")
        # exact reconciliation: every shard settled exactly once, and the
        # totals agree with the per-shard records and event lists — a
        # reassigned-and-committed shard counts once, under its winner
        if isinstance(shards, list) and shards and not problems:
            if totals.get("shards") != len(shards):
                problems.append("totals.shards != len(shards)")
            for status in ELASTIC_SHARD_STATUSES:
                if totals.get(status) != by_status[status]:
                    problems.append(
                        f"totals.{status} != per-shard {status} count"
                    )
            if (totals.get("committed", 0) + totals.get("resumed", 0)
                    + totals.get("quarantined", 0)) != len(shards):
                problems.append(
                    "totals: committed + resumed + quarantined != shards"
                )
            if totals.get("reassignments") != len(reassignments):
                problems.append(
                    "totals.reassignments != len(reassignments)"
                )
            if totals.get("fenced_rejections") != len(fenced):
                problems.append(
                    "totals.fenced_rejections != len(fenced_rejections)"
                )
    return problems


#: schema tag of the elastic-serving probe document emitted by
#: scripts/elastic_serve_probe.py (the chaos_probe --elastic story
#: applied to the serve fleet, serve/fleet.py): per-phase fleet state
#: (partition leases, workers, cause-tagged reassignments, fenced
#: lease rejections) plus the exactly-once result accounting —
#: ``offered == completed + rejected + shed + errors`` EXACTLY, zero
#: double-served request ids, fenced late results counted — rebalance
#: latency, and the recruitment round. bench_guard wraps the probe, so
#: an error record ({"schema": ..., "error": str}) is contractually
#: valid; scripts/bench_trend.py --fleet rc-gates on the
#: zero-double-served and reconciliation fields.
ELASTIC_SERVE_REPORT_SCHEMA = "elastic_serve_report/v1"

#: the exactly-once accounting fields every fleet/probe accounting
#: record must carry as non-negative ints; the first four reconcile
#: exactly against ``offered``
FLEET_ACCOUNTING_KEYS = (
    "offered", "completed", "rejected", "shed", "errors",
    "resubmitted", "fenced_results", "late_results", "double_served",
)


def _validate_fleet_accounting(acc, where: str) -> List[str]:
    """The exactly-once contract as a validation rule: every key a
    non-negative int and offered == completed + rejected + shed +
    errors EXACTLY (resubmissions/fenced/late commits are bookkeeping,
    never extra terminals)."""
    if not isinstance(acc, dict):
        return [f"{where}: not a dict"]
    problems: List[str] = []
    for key in FLEET_ACCOUNTING_KEYS:
        v = acc.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{where}.{key}: not a non-negative int")
    if not problems and acc["offered"] != (
        acc["completed"] + acc["rejected"] + acc["shed"] + acc["errors"]
    ):
        problems.append(
            f"{where}: offered != completed + rejected + shed + errors"
        )
    return problems


def _validate_fleet_section(fleet, where: str) -> List[str]:
    """One ServeFleet.report() document (embedded per probe phase)."""
    if not isinstance(fleet, dict):
        return [f"{where}: not a dict"]
    problems: List[str] = []
    partitions = fleet.get("partitions")
    if not isinstance(partitions, list) or not partitions:
        problems.append(f"{where}.partitions: not a non-empty list")
        partitions = []
    for i, rec in enumerate(partitions):
        sub = f"{where}.partitions[{i}]"
        if not isinstance(rec, dict):
            problems.append(f"{sub}: not a dict")
            continue
        for key in ("index", "partition", "status", "worker", "epoch",
                    "assignments"):
            if key not in rec:
                problems.append(f"{sub}: missing {key!r}")
    if not isinstance(fleet.get("workers"), dict):
        problems.append(f"{where}.workers: not a dict")
    for section, vocab_key, vocab in (
        ("reassignments", "cause", ELASTIC_REASSIGN_CAUSES),
        ("fenced_rejections", "op", ELASTIC_FENCE_OPS),
    ):
        recs = fleet.get(section)
        if not isinstance(recs, list):
            problems.append(f"{where}.{section}: not a list")
            continue
        for i, r in enumerate(recs):
            sub = f"{where}.{section}[{i}]"
            if not isinstance(r, dict):
                problems.append(f"{sub}: not a dict")
                continue
            for key in ("partition", "worker", "epoch", vocab_key):
                if key not in r:
                    problems.append(f"{sub}: missing {key!r}")
            if r.get(vocab_key) not in vocab:
                problems.append(
                    f"{sub}: bad {vocab_key} {r.get(vocab_key)!r}"
                )
    problems += _validate_fleet_accounting(
        fleet.get("accounting"), f"{where}.accounting"
    )
    return problems


def validate_elastic_serve_report(doc: dict) -> List[str]:
    """Structural + reconciliation check of an elastic_serve_report/v1
    document; returns a list of problems (empty == valid). An error
    record is contractually valid (the bench_guard wedge path).
    Dependency-free like the other validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != ELASTIC_SERVE_REPORT_SCHEMA:
        problems.append(
            f"schema != {ELASTIC_SERVE_REPORT_SCHEMA}: "
            f"{doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    if not isinstance(doc.get("config"), dict):
        problems.append("config: not a dict")
    phases = doc.get("phases")
    if not isinstance(phases, list) or not phases:
        problems.append("phases: not a non-empty list")
        phases = []
    for i, phase in enumerate(phases):
        where = f"phases[{i}]"
        if not isinstance(phase, dict):
            problems.append(f"{where}: not a dict")
            continue
        if not isinstance(phase.get("name"), str) or not phase["name"]:
            problems.append(f"{where}.name: not a non-empty string")
        if not isinstance(phase.get("offered"), int) \
                or isinstance(phase.get("offered"), bool):
            problems.append(f"{where}.offered: not an int")
        outcomes = phase.get("outcomes")
        if not isinstance(outcomes, dict) or not all(
            isinstance(outcomes.get(k), int)
            and not isinstance(outcomes.get(k), bool)
            for k in ("completed", "rejected", "shed", "errors")
        ):
            problems.append(
                f"{where}.outcomes: missing completed/rejected/shed/"
                "errors ints"
            )
        elif isinstance(phase.get("offered"), int) and \
                sum(outcomes[k] for k in ("completed", "rejected",
                                          "shed", "errors")) \
                != phase["offered"]:
            problems.append(
                f"{where}: probe-side outcomes do not reconcile with "
                "offered"
            )
        problems += _validate_fleet_section(phase.get("fleet"),
                                            f"{where}.fleet")
    problems += _validate_fleet_accounting(doc.get("accounting"),
                                           "accounting")
    rebalance = doc.get("rebalance")
    if not isinstance(rebalance, dict) or not all(
        isinstance(rebalance.get(k), (int, float))
        and not isinstance(rebalance.get(k), bool)
        for k in ("count", "max_latency_s", "bound_s")
    ):
        problems.append("rebalance: missing count/max_latency_s/bound_s")
    recruit = doc.get("recruitment")
    if not isinstance(recruit, dict) or not all(
        isinstance(recruit.get(k), int)
        and not isinstance(recruit.get(k), bool)
        for k in ("rounds", "workers_before", "workers_after",
                  "degrade_level", "degrade_max_seen")
    ):
        problems.append(
            "recruitment: missing rounds/workers_before/workers_after/"
            "degrade_level/degrade_max_seen ints"
        )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("futures_terminal", "zero_double_served",
                    "accounting_exact_probe", "accounting_exact_fleet",
                    "results_correct", "fenced_late_result",
                    "rebalance_bounded", "recruitment_absorbed",
                    "degrade_level0"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the serve-tier chaos gauntlet emitted by
#: scripts/serve_chaos_probe.py (the chaos_probe story extended past
#: the map/elastic layer into the replicated gallery fleet,
#: serve/gallery_fleet.py): phase-by-phase pattern accounting across
#: repeated primary kill -9s (zero registered patterns lost — journal
#: + replica promotion), healthy-fleet fan-out-vs-single-bank byte
#: equality, and a fault ledger proving every injected serve-tier
#: fault (severed links, corrupt replica payloads, beats delayed past
#: the lease window) was observed, accounted for, and surfaced as a
#: labeled degrade step. bench_guard wraps the probe, so an error
#: record ({"schema": ..., "error": str}) is contractually valid;
#: scripts/bench_trend.py --chaos rc-gates fail-closed on the
#: zero-loss and all-faults-accounted invariants.
SERVE_CHAOS_REPORT_SCHEMA = "serve_chaos_report/v1"

#: the closed serve-tier fault-point vocabulary a serve_chaos_report
#: may inject/observe — the serve slice of faults.POINTS
SERVE_CHAOS_FAULT_POINTS = (
    "serve.link", "gallery.replica", "gallery.beat", "journal",
)

#: the checks every serve_chaos_report/v1 must carry — the probe's
#: acceptance invariants, each a bool (rc-gated by the probe itself
#: and re-gated fail-closed by bench_trend --chaos)
SERVE_CHAOS_CHECK_KEYS = (
    "zero_patterns_lost", "fanout_byte_identical",
    "all_faults_observed", "all_faults_accounted",
    "degraded_exactly_labeled", "degrade_heals",
    "replication_recovered", "env_schedule_delivered",
)


def validate_serve_chaos_report(doc: dict) -> List[str]:
    """Structural + reconciliation check of a serve_chaos_report/v1
    document; returns a list of problems (empty == valid). An error
    record is contractually valid (the bench_guard wedge path).
    Dependency-free like the other validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != SERVE_CHAOS_REPORT_SCHEMA:
        problems.append(
            f"schema != {SERVE_CHAOS_REPORT_SCHEMA}: "
            f"{doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    config = doc.get("config")
    if not isinstance(config, dict):
        problems.append("config: not a dict")
    else:
        for key in ("shards", "workers", "replicas", "patterns"):
            v = config.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                problems.append(f"config.{key}: not a positive int")
    phases = doc.get("phases")
    if not isinstance(phases, list) or not phases:
        problems.append("phases: not a non-empty list")
        phases = []
    for i, phase in enumerate(phases):
        where = f"phases[{i}]"
        if not isinstance(phase, dict):
            problems.append(f"{where}: not a dict")
            continue
        if not isinstance(phase.get("name"), str) or not phase["name"]:
            problems.append(f"{where}.name: not a non-empty string")
        if not isinstance(phase.get("ok"), bool):
            problems.append(f"{where}.ok: not a bool")
    patterns = doc.get("patterns")
    if not isinstance(patterns, dict):
        problems.append("patterns: not a dict")
    else:
        for key in ("registered", "survived"):
            v = patterns.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(
                    f"patterns.{key}: not a non-negative int"
                )
        lost = patterns.get("lost")
        if not isinstance(lost, list):
            problems.append("patterns.lost: not a list")
        elif not problems and isinstance(patterns.get("registered"), int):
            # exact pattern reconciliation: every registered pattern is
            # either survived or named in the lost list — no third bin
            if patterns["registered"] != patterns["survived"] + len(lost):
                problems.append(
                    "patterns: registered != survived + len(lost)"
                )
    kills = doc.get("kills")
    if not isinstance(kills, dict) or not all(
        isinstance(kills.get(k), int) and not isinstance(kills.get(k),
                                                         bool)
        for k in ("rounds", "workers_killed")
    ):
        problems.append("kills: missing rounds/workers_killed ints")
    elif kills["rounds"] < 1:
        problems.append("kills.rounds: no kill rounds ran")
    faults_sec = doc.get("faults")
    if not isinstance(faults_sec, dict):
        problems.append("faults: not a dict")
    else:
        injected = faults_sec.get("injected")
        if not isinstance(injected, list) or not injected:
            problems.append("faults.injected: not a non-empty list")
            injected = []
        inj_points = set()
        for i, rec in enumerate(injected):
            where = f"faults.injected[{i}]"
            if not isinstance(rec, dict):
                problems.append(f"{where}: not a dict")
                continue
            point = rec.get("point")
            if point not in SERVE_CHAOS_FAULT_POINTS:
                problems.append(f"{where}.point: bad point {point!r}")
            else:
                inj_points.add(point)
            if not isinstance(rec.get("schedule"), str) \
                    or not rec["schedule"]:
                problems.append(
                    f"{where}.schedule: not a non-empty string"
                )
            for key in ("fired", "accounted"):
                v = rec.get(key)
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    problems.append(
                        f"{where}.{key}: not a non-negative int"
                    )
        observed = faults_sec.get("observed")
        if not isinstance(observed, dict):
            problems.append("faults.observed: not a dict")
        else:
            for point, n in observed.items():
                if point not in SERVE_CHAOS_FAULT_POINTS:
                    problems.append(
                        f"faults.observed: bad point {point!r}"
                    )
                if not isinstance(n, int) or isinstance(n, bool) \
                        or n < 0:
                    problems.append(
                        f"faults.observed[{point!r}]: not a "
                        "non-negative int"
                    )
            # every injected point must have been OBSERVED firing at
            # least once — a schedule that never fired proves nothing
            for point in inj_points:
                if not observed.get(point):
                    problems.append(
                        f"faults: injected point {point!r} never "
                        "observed firing"
                    )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in SERVE_CHAOS_CHECK_KEYS:
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the serving-layer benchmark document emitted by
#: scripts/serve_bench.py (offered-load sweep over tmr_tpu/serve): per-
#: workload throughput + latency percentiles + batch-occupancy histogram +
#: cache hit rates, plus the acceptance checks (speedup vs the sequential
#: Predictor loop, bitwise exactness, p99 bound, cache hit). bench_guard
#: wraps the script, so a hung run yields {"schema": ..., "error":
#: ...} — also a valid document per ``validate_serve_report``.
SERVE_REPORT_SCHEMA = "serve_report/v1"

#: closed workload-mode vocabulary in a serve_report/v1 document
SERVE_WORKLOAD_MODES = ("closed", "open")


#: closed vocabularies of the optional ``quant`` provenance attachment
#: (engine stats()/health() and serve_report/v1): which numerics tier
#: the serving programs ran — "mode" is the in-program TMR_QUANT arm,
#: "storage" whether the param tree itself was offline-quantized
#: (TMR_QUANT_STORAGE). Absent = fully exact weights.
QUANT_STAMP_MODES = ("off", "int8")


def _validate_quant_attachment(doc: dict) -> List[str]:
    """Optional ``quant`` attachment: results served from a quantized
    (and/or storage-quantized) engine carry their numerics provenance
    the way degraded results carry ``degrade_steps``."""
    if "quant" not in doc:
        return []
    q = doc["quant"]
    if not isinstance(q, dict):
        return ["quant: not a dict"]
    problems: List[str] = []
    if q.get("mode") not in QUANT_STAMP_MODES:
        problems.append(f"quant.mode: bad value {q.get('mode')!r}")
    if q.get("storage") not in QUANT_STAMP_MODES:
        problems.append(f"quant.storage: bad value {q.get('storage')!r}")
    if q.get("storage") == "int8":
        if not isinstance(q.get("digest"), str) or not q.get("digest"):
            problems.append("quant.digest: not a non-empty string under "
                            "storage=int8")
        for key in ("quantized_leaves", "weight_bytes",
                    "f32_weight_bytes"):
            v = q.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                problems.append(f"quant.{key}: not a positive int")
    return problems


def _validate_mesh_attachment(doc: dict) -> List[str]:
    """Optional ``mesh`` attachment of a serve_report/v1 (and the
    engine's health/stats views): the serving-mesh description one
    sweep round ran under — spec string, axis shape, axis names, and
    the replica groups by device. Absent = the unsharded engine."""
    if "mesh" not in doc:
        return []
    problems: List[str] = []
    mesh = doc["mesh"]
    if not isinstance(mesh, dict):
        return ["mesh: not a dict"]
    if not isinstance(mesh.get("spec"), str) or not mesh.get("spec"):
        problems.append("mesh.spec: not a non-empty string")
    shape = mesh.get("shape")
    if not isinstance(shape, dict) or not shape or not all(
        isinstance(v, int) and not isinstance(v, bool) and v >= 1
        for v in shape.values()
    ):
        problems.append("mesh.shape: not a {axis: size>=1} dict")
    names = mesh.get("axis_names")
    if not isinstance(names, list) or not all(
        isinstance(n, str) for n in names
    ):
        problems.append("mesh.axis_names: not a list of strings")
    groups = mesh.get("replica_groups")
    if not isinstance(groups, list) or not groups or not all(
        isinstance(g, list) and g and all(isinstance(d, str) for d in g)
        for g in groups
    ):
        problems.append(
            "mesh.replica_groups: not a non-empty list of non-empty "
            "device-string lists"
        )
    return problems


def validate_serve_report(doc: dict) -> List[str]:
    """Structural check of a serve_report/v1 document; returns a list of
    problems (empty == valid). Dependency-free so CI harnesses can gate on
    the report without importing the serving stack. An error record
    ({"schema": ..., "error": str}) is contractually valid."""
    problems: List[str] = []
    problems += _validate_metrics_attachment(doc)
    problems += _validate_mfu_attachment(doc)
    problems += _validate_mesh_attachment(doc)
    problems += _validate_quant_attachment(doc)
    if doc.get("schema") != SERVE_REPORT_SCHEMA:
        problems.append(
            f"schema != {SERVE_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    cfg = doc.get("config")
    if not isinstance(cfg, dict):
        problems.append("config: not a dict")
    else:
        for key in ("batch", "max_wait_ms", "image_size"):
            if key not in cfg:
                problems.append(f"config: missing {key!r}")
    workloads = doc.get("workloads")
    if not isinstance(workloads, list) or not workloads:
        problems.append("workloads: not a non-empty list")
        workloads = []
    for i, w in enumerate(workloads):
        where = f"workloads[{i}]"
        if not isinstance(w, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("name", "mode", "requests", "throughput_img_per_sec",
                    "latency_ms", "batch_occupancy", "cache"):
            if key not in w:
                problems.append(f"{where}: missing {key!r}")
        if w.get("mode") not in SERVE_WORKLOAD_MODES:
            problems.append(f"{where}: bad mode {w.get('mode')!r}")
        lat = w.get("latency_ms", {})
        if not isinstance(lat, dict):
            problems.append(f"{where}.latency_ms: not a dict")
        else:
            for q in ("p50", "p95", "p99"):
                if not isinstance(lat.get(q), (int, float)):
                    problems.append(f"{where}.latency_ms: missing {q!r}")
        occ = w.get("batch_occupancy", {})
        if not isinstance(occ, dict) or not all(
            isinstance(v, int) for v in occ.values()
        ):
            problems.append(f"{where}.batch_occupancy: not {{size: count}}")
        cache = w.get("cache", {})
        if not isinstance(cache, dict):
            problems.append(f"{where}.cache: not a dict")
        else:
            for which in ("result_cache", "feature_cache"):
                sub = cache.get(which)
                if not isinstance(sub, dict) or not all(
                    k in sub for k in ("hits", "misses", "evictions")
                ):
                    problems.append(
                        f"{where}.cache.{which}: missing hits/misses/"
                        "evictions"
                    )
        # optional per-workload admission/shed/degrade tallies (attached
        # by serve_bench since the overload PR so open-loop rounds under
        # pressure stay interpretable; absent on older documents)
        if "admission" in w:
            adm = w["admission"]
            if not isinstance(adm, dict):
                problems.append(f"{where}.admission: not a dict")
            else:
                for key in ("rejected", "shed", "degraded",
                            "reject_rate"):
                    v = adm.get(key)
                    if not isinstance(v, (int, float)) \
                            or isinstance(v, bool):
                        problems.append(
                            f"{where}.admission: missing {key!r}"
                        )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("speedup_vs_sequential", "speedup_ok", "exact_match",
                    "p99_bounded", "cache_hit"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the gallery-tier benchmark document emitted by
#: scripts/gallery_bench.py (tmr_tpu/serve/gallery.py): patterns×frames
#: throughput of the one-backbone-pass gallery tier vs the N-loop of
#: predict_multi_exemplar on identical (frame, pattern) pairs, the
#: backbone-amortization evidence (devtime program-call counts:
#: backbone executions == frames, never frames×N), the fused-arm
#: bitwise-exactness pin, and the coarse-prefilter sweep
#: (recall-vs-full-match + full-match invocation cut per top-k rung,
#: with the elected winner). bench_guard wraps the script, so an error
#: record ({"schema": ..., "error": str}) is contractually valid;
#: scripts/bench_trend.py --gallery rc-gates on exactness +
#: backbone-amortization + the prefilter checks.
GALLERY_REPORT_SCHEMA = "gallery_report/v1"

#: the boolean acceptance checks a usable gallery_report/v1 must carry
GALLERY_REPORT_CHECKS = (
    "bitwise_exact", "backbone_amortized", "prefilter_recall_ok",
    "prefilter_cut_ok",
)

#: the boolean checks the OPTIONAL ``n_sweep`` section (the
#: catalog-scale sketch-index sweep, scripts/gallery_bench.py --sweep)
#: must carry when present; legacy documents without the section stay
#: valid
GALLERY_SWEEP_CHECKS = (
    "index_sublinear", "index_recall_ok", "index_off_exact",
)


def validate_gallery_report(doc: dict) -> List[str]:
    """Structural check of a gallery_report/v1 document; returns a list
    of problems (empty == valid). An error record is contractually
    valid (the bench_guard wedge path). Dependency-free like the other
    validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != GALLERY_REPORT_SCHEMA:
        problems.append(
            f"schema != {GALLERY_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    cfg = doc.get("config")
    if not isinstance(cfg, dict):
        problems.append("config: not a dict")
    else:
        for key in ("image_size", "patterns", "frames"):
            v = cfg.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                problems.append(f"config.{key}: not a positive int")
    bank = doc.get("bank")
    if not isinstance(bank, dict) or not isinstance(
        bank.get("groups"), list
    ):
        problems.append("bank: missing groups list")
    tput = doc.get("throughput")
    if not isinstance(tput, dict):
        problems.append("throughput: not a dict")
    else:
        for key in ("gallery_pattern_frames_per_sec",
                    "n_loop_pattern_frames_per_sec", "speedup"):
            v = tput.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                problems.append(f"throughput.{key}: not a number")
    bb = doc.get("backbone")
    if not isinstance(bb, dict):
        problems.append("backbone: not a dict")
    else:
        for key in ("frames", "executions", "pattern_frame_pairs"):
            v = bb.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"backbone.{key}: not a non-neg int")
        if not isinstance(bb.get("by_program"), dict):
            problems.append("backbone.by_program: not a dict")
    pre = doc.get("prefilter")
    if not isinstance(pre, dict):
        problems.append("prefilter: not a dict")
    else:
        rungs = pre.get("rungs")
        if not isinstance(rungs, list):
            problems.append("prefilter.rungs: not a list")
        else:
            for i, r in enumerate(rungs):
                where = f"prefilter.rungs[{i}]"
                if not isinstance(r, dict):
                    problems.append(f"{where}: not a dict")
                    continue
                for key in ("topk", "recall", "invocation_cut",
                            "full_matches"):
                    if key not in r:
                        problems.append(f"{where}: missing {key!r}")
        elected = pre.get("elected_topk")
        if elected is not None and (
            not isinstance(elected, int) or isinstance(elected, bool)
            or elected <= 0
        ):
            problems.append(
                "prefilter.elected_topk: not a positive int or null"
            )
    sweep = doc.get("n_sweep")
    if sweep is not None:  # OPTIONAL: only --sweep runs carry it
        if not isinstance(sweep, dict):
            problems.append("n_sweep: not a dict")
        else:
            pts = sweep.get("points")
            if not isinstance(pts, list) or not pts:
                problems.append("n_sweep.points: not a non-empty list")
                pts = []
            for i, p in enumerate(pts):
                where = f"n_sweep.points[{i}]"
                if not isinstance(p, dict):
                    problems.append(f"{where}: not a dict")
                    continue
                v = p.get("n")
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v <= 0:
                    problems.append(f"{where}.n: not a positive int")
                for key in ("linear_ms", "index_ms"):
                    v = p.get(key)
                    if not isinstance(v, (int, float)) \
                            or isinstance(v, bool) or v < 0:
                        problems.append(
                            f"{where}.{key}: not a non-negative number"
                        )
                v = p.get("recall")
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool) or not 0.0 <= v <= 1.0:
                    problems.append(f"{where}.recall: not in [0, 1]")
            if not isinstance(sweep.get("fit"), dict):
                problems.append("n_sweep.fit: not a dict")
            scheck = sweep.get("checks")
            if not isinstance(scheck, dict):
                problems.append("n_sweep.checks: not a dict")
            else:
                for key in GALLERY_SWEEP_CHECKS:
                    if key not in scheck:
                        problems.append(
                            f"n_sweep.checks: missing {key!r}"
                        )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in GALLERY_REPORT_CHECKS + ("speedup_vs_n_loop",):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the streaming-video bench document emitted by
#: scripts/stream_bench.py: a synthetic bursty multi-stream workload
#: through StreamRouter (serve/streams.py) with the devtime
#: program-call witness that backbone executions ≪ frames, measured
#: frames/s vs the frame-independent path, the bitwise-exactness pin
#: on every frame the delta check called "changed", and the
#: cross-stream isolation count. bench_guard wraps the script, so an
#: error record ({"schema": ..., "error": str}) is contractually
#: valid; scripts/bench_trend.py --stream rc-gates the checks
#: fail-closed.
STREAM_REPORT_SCHEMA = "stream_report/v1"

#: the boolean acceptance checks a usable stream_report/v1 must carry
STREAM_REPORT_CHECKS = (
    "backbone_amortized", "speedup_ok", "changed_frames_exact",
    "cross_stream_isolated", "reuse_labeled",
)


def validate_stream_report(doc: dict) -> List[str]:
    """Structural check of a stream_report/v1 document; returns a list
    of problems (empty == valid). An error record is contractually
    valid (the bench_guard wedge path). Dependency-free like the other
    validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != STREAM_REPORT_SCHEMA:
        problems.append(
            f"schema != {STREAM_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    cfg = doc.get("config")
    if not isinstance(cfg, dict):
        problems.append("config: not a dict")
    else:
        for key in ("image_size", "streams", "frames_per_stream",
                    "frames"):
            v = cfg.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                problems.append(f"config.{key}: not a positive int")
        d = cfg.get("delta")
        if not isinstance(d, (int, float)) or isinstance(d, bool):
            problems.append("config.delta: not a number")
    tput = doc.get("throughput")
    if not isinstance(tput, dict):
        problems.append("throughput: not a dict")
    else:
        for key in ("stream_frames_per_sec",
                    "independent_frames_per_sec", "speedup"):
            v = tput.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                problems.append(f"throughput.{key}: not a number")
    bb = doc.get("backbone")
    if not isinstance(bb, dict):
        problems.append("backbone: not a dict")
    else:
        for key in ("frames", "executions"):
            v = bb.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"backbone.{key}: not a non-neg int")
        if not isinstance(bb.get("by_program"), dict):
            problems.append("backbone.by_program: not a dict")
    reuse = doc.get("reuse")
    if not isinstance(reuse, dict):
        problems.append("reuse: not a dict")
    else:
        for key in ("reused_frames", "changed_frames", "first_frames"):
            v = reuse.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"reuse.{key}: not a non-neg int")
    ex = doc.get("exactness")
    if not isinstance(ex, dict):
        problems.append("exactness: not a dict")
    else:
        for key in ("changed_frames_checked", "mismatches"):
            v = ex.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"exactness.{key}: not a non-neg int")
    iso = doc.get("isolation")
    if not isinstance(iso, dict):
        problems.append("isolation: not a dict")
    else:
        v = iso.get("cross_stream_hits")
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(
                "isolation.cross_stream_hits: not a non-neg int"
            )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in STREAM_REPORT_CHECKS:
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the overload-robustness probe document emitted by
#: scripts/overload_probe.py: measured capacity, a >=5x offered-load
#: round against a bounded-admission engine (admitted-traffic latency
#: percentiles, exact reject/shed/complete accounting vs offers), a
#: deterministic deadline-shed burst, the degrade ladder's recorded
#: steps plus its auto escalation/cooldown trajectory, and a
#: mid-overload close() timing. bench_guard wraps the probe, so an
#: error record ({"schema": ..., "error": str}) is contractually valid.
OVERLOAD_REPORT_SCHEMA = "overload_report/v1"


def validate_overload_report(doc: dict) -> List[str]:
    """Structural check of an overload_report/v1 document; returns a
    list of problems (empty == valid). Dependency-free like the other
    validators; an error record is contractually valid."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != OVERLOAD_REPORT_SCHEMA:
        problems.append(
            f"schema != {OVERLOAD_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    if not isinstance(doc.get("config"), dict):
        problems.append("config: not a dict")
    cap = doc.get("capacity")
    if not isinstance(cap, dict) or not isinstance(
        cap.get("img_per_sec"), (int, float)
    ):
        problems.append("capacity: missing img_per_sec")
    over = doc.get("overload")
    if not isinstance(over, dict):
        problems.append("overload: not a dict")
    else:
        for key in ("offered", "completed", "rejected", "shed",
                    "errors", "degraded"):
            v = over.get(key)
            if not isinstance(v, int) or isinstance(v, bool):
                problems.append(f"overload.{key}: not an int")
        if not isinstance(over.get("offered_img_per_sec"), (int, float)):
            problems.append("overload.offered_img_per_sec: not a number")
        lat = over.get("latency_ms")
        if not isinstance(lat, dict) or not all(
            isinstance(lat.get(q), (int, float))
            for q in ("p50", "p95", "p99")
        ):
            problems.append("overload.latency_ms: missing p50/p95/p99")
        causes = over.get("reject_causes")
        if causes is not None and not isinstance(causes, dict):
            problems.append("overload.reject_causes: not a dict")
    close_rec = doc.get("close")
    if not isinstance(close_rec, dict) or not all(
        isinstance(close_rec.get(k), (int, float))
        for k in ("wall_s", "timeout_s")
    ):
        problems.append("close: missing wall_s/timeout_s")
    deg = doc.get("degrade")
    if not isinstance(deg, dict):
        problems.append("degrade: not a dict")
    else:
        if not isinstance(deg.get("steps_seen"), list):
            problems.append("degrade.steps_seen: not a list")
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("p99_bounded", "accounting_exact",
                    "rejected_nonzero", "shed_before_device",
                    "degrade_steps_recorded", "degrade_auto_ladder",
                    "close_bounded"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the telemetry probe document emitted by
#: scripts/obs_probe.py: per-stage span counts and span-derived
#: p50/p95/p99 for the serve pipeline and the map phase, the compile
#: events observed (kind/key/wall/cause), a metrics_report/v1 registry
#: snapshot, and the measured disabled-mode tracing overhead — the
#: before/after instrument every later perf PR reads. bench_guard wraps
#: the probe, so an error record ({"schema": ..., "error": str}) is
#: contractually valid here too.
TRACE_REPORT_SCHEMA = "trace_report/v1"

#: the serve pipeline stages obs_probe requires as spans, in pipeline
#: order — submit through future resolution, one trace id per request
TRACE_SERVE_STAGES = (
    "serve.submit",
    "serve.queue_wait",
    "serve.batch_assemble",
    "serve.stage",
    "serve.execute",
    "serve.postprocess",
    "serve.resolve",
)

#: closed compile-cause vocabulary (obs/compile.py): "cold" = first
#: program of its kind this process, "key-change" = the recompile-storm
#: signature (same kind, new key)
COMPILE_EVENT_CAUSES = ("cold", "key-change")


def validate_trace_report(doc: dict) -> List[str]:
    """Structural check of a trace_report/v1 document; returns a list of
    problems (empty == valid). An error record is contractually valid."""
    problems: List[str] = []
    if doc.get("schema") != TRACE_REPORT_SCHEMA:
        problems.append(
            f"schema != {TRACE_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    problems += _validate_metrics_attachment(doc)
    if "metrics" not in doc:
        problems.append("metrics: missing")
    if not isinstance(doc.get("config"), dict):
        problems.append("config: not a dict")
    for section in ("serve", "map"):
        sec = doc.get(section)
        if not isinstance(sec, dict):
            problems.append(f"{section}: not a dict")
            continue
        stages = sec.get("stages")
        if not isinstance(stages, dict):
            problems.append(f"{section}.stages: not a dict")
            continue
        for name, rec in stages.items():
            where = f"{section}.stages[{name!r}]"
            if not isinstance(rec, dict):
                problems.append(f"{where}: not a dict")
                continue
            for key in ("count", "p50_ms", "p95_ms", "p99_ms"):
                if not isinstance(rec.get(key), (int, float)):
                    problems.append(f"{where}: missing {key!r}")
    events = doc.get("compile_events")
    if not isinstance(events, list):
        problems.append("compile_events: not a list")
    else:
        for i, e in enumerate(events):
            where = f"compile_events[{i}]"
            if not isinstance(e, dict):
                problems.append(f"{where}: not a dict")
                continue
            for key in ("kind", "key", "wall_s", "cause"):
                if key not in e:
                    problems.append(f"{where}: missing {key!r}")
            if e.get("cause") not in COMPILE_EVENT_CAUSES:
                problems.append(f"{where}: bad cause {e.get('cause')!r}")
    overhead = doc.get("overhead")
    if not isinstance(overhead, dict):
        problems.append("overhead: not a dict")
    else:
        for key in ("disabled_ns_per_span", "overhead_disabled_pct"):
            if not isinstance(overhead.get(key), (int, float)):
                problems.append(f"overhead: missing {key!r}")
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("stages_complete", "compile_event_recorded",
                    "trace_roundtrip", "overhead_ok"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: keys a valid ``stage_breakdown`` record (bench.py embeds one per
#: round; utils/stage_bench.measure_stage_breakdown emits it): the
#: formulations that actually traced plus one ``<stage>_s`` seconds/iter
#: or ``<stage>_error`` string per tail stage. Not a standalone
#: ``*_REPORT_SCHEMA`` document — it rides inside the bench record, so it
#: carries no schema tag of its own.
STAGE_BREAKDOWN_STAGES = ("decoder_heads", "decode_tail")


def validate_stage_breakdown(doc: dict) -> List[str]:
    """Structural check of a bench ``stage_breakdown`` record; returns a
    list of problems (empty == valid). Each stage must carry EITHER its
    measured ``<stage>_s`` seconds (non-negative number) or a
    ``<stage>_error`` string — never both, never neither — alongside the
    formulation stamp (decoder_impl/quant/decode_tail) that says what the
    timing measured. A bare ``{"error": str}`` record is also valid: the
    whole harness failed before any stage could stamp (bench.py's
    fallback — the headline must survive a mid-stage wedge), so there is
    nothing stage-wise to check. Dependency-free like the report
    validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if set(doc) == {"error"}:
        if not isinstance(doc["error"], str) or not doc["error"]:
            return ["error: not a non-empty string"]
        return []
    for key, legal in (("decoder_impl", ("xla", "fused")),
                       ("quant", ("off", "int8")),
                       ("decode_tail", ("host", "device"))):
        if doc.get(key) not in legal:
            problems.append(f"{key}: {doc.get(key)!r} not in {legal}")
    # optional storage stamps (absent on pre-storage records)
    if "quant_storage" in doc and doc["quant_storage"] not in \
            QUANT_STAMP_MODES:
        problems.append(
            f"quant_storage: {doc['quant_storage']!r} not in "
            f"{QUANT_STAMP_MODES}"
        )
    if "quant_kernel" in doc and doc["quant_kernel"] not in (
        "dequant", "int8dot", "pallas"
    ):
        problems.append(f"quant_kernel: {doc['quant_kernel']!r} bad")
    for stage in STAGE_BREAKDOWN_STAGES:
        sec, err = doc.get(f"{stage}_s"), doc.get(f"{stage}_error")
        if sec is None and err is None:
            problems.append(f"{stage}: neither {stage}_s nor {stage}_error")
        elif sec is not None and err is not None:
            problems.append(f"{stage}: both {stage}_s and {stage}_error")
        elif err is None:
            if not isinstance(sec, (int, float)) or isinstance(sec, bool) \
                    or sec < 0:
                problems.append(f"{stage}_s: not a non-negative number")
        elif not isinstance(err, str) or not err:
            problems.append(f"{stage}_error: not a non-empty string")
    return problems


#: schema tag of the static-analysis + program-audit document emitted by
#: scripts/analyze.py (tmr_tpu/analysis): AST-tier findings (rule id +
#: file:line + message, suppression-baseline applied), per-rule tallies,
#: and the program-tier audit record (jaxpr invariants of the bucketed
#: production programs: no-S² attention, no-f64, quant-widen, transfer
#: guard). CI gates on ``checks.clean``.
ANALYSIS_REPORT_SCHEMA = "analysis_report/v1"


def validate_analysis_report(doc: dict) -> List[str]:
    """Structural check of an analysis_report/v1 document; returns a
    list of problems (empty == valid). An error record
    ({"schema": ..., "error": str}) is contractually valid (the
    bench_guard wrapper's wedge path). Dependency-free like the other
    validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != ANALYSIS_REPORT_SCHEMA:
        problems.append(
            f"schema != {ANALYSIS_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    rules = doc.get("rules")
    if not isinstance(rules, list) or not all(
        isinstance(r, str) for r in rules
    ) or not rules:
        problems.append("rules: not a non-empty list of rule ids")
    findings = doc.get("findings")
    if not isinstance(findings, list):
        problems.append("findings: not a list")
        findings = []
    for i, f in enumerate(findings):
        where = f"findings[{i}]"
        if not isinstance(f, dict):
            problems.append(f"{where}: not a dict")
            continue
        for key in ("rule", "file", "line", "message"):
            if key not in f:
                problems.append(f"{where}: missing {key!r}")
        if isinstance(rules, list) and rules \
                and f.get("rule") not in rules:
            problems.append(f"{where}: unknown rule {f.get('rule')!r}")
    if not isinstance(doc.get("baselined_count"), int):
        problems.append("baselined_count: not an int")
    if not isinstance(doc.get("counts_by_rule"), dict):
        problems.append("counts_by_rule: not a dict")
    prog = doc.get("program_audit")
    if prog is not None:
        if not isinstance(prog, dict):
            problems.append("program_audit: not a dict")
        else:
            for key in ("platform", "states", "problems", "ok"):
                if key not in prog:
                    problems.append(f"program_audit: missing {key!r}")
            for i, st in enumerate(prog.get("states") or ()):
                where = f"program_audit.states[{i}]"
                if not isinstance(st, dict) or "programs" not in st \
                        or "gate_state" not in st:
                    problems.append(
                        f"{where}: missing gate_state/programs"
                    )
                    continue
                for j, rec in enumerate(st["programs"]):
                    if not isinstance(rec, dict) or not {
                        "name", "ok", "problems", "device_put",
                        "callbacks",
                    } <= set(rec):
                        problems.append(
                            f"{where}.programs[{j}]: missing "
                            "name/ok/problems/device_put/callbacks"
                        )
    checks = doc.get("checks")
    if not isinstance(checks, dict):
        problems.append("checks: not a dict")
    else:
        for key in ("ast_clean", "program_ok", "clean"):
            if key not in checks:
                problems.append(f"checks: missing {key!r}")
    return problems


#: schema tag of the per-device-generation winner bank file
#: (tmr_tpu/autotune_live.py): one validated document holding live- and
#: offline-elected formulation winners keyed
#: ``device_kind|knob|geometry``, every entry stamped with the sweep
#: revision it was measured under (autotune's ``_SWEEP_REV`` staleness
#: discipline — a stale entry falls back to the offline cache instead of
#: electing). Written only via atomicio.atomic_write.
WINNER_BANK_SCHEMA = "winner_bank/v1"

#: entry provenance vocabulary: "offline" = seeded from the autotune
#: cache's sweep winners; "live" = elected (or restored by a demotion)
#: from shadow-measured production traffic.
WINNER_BANK_SOURCES = ("offline", "live")


def validate_winner_bank(doc: dict) -> List[str]:
    """Structural check of a winner_bank/v1 document; returns a list of
    problems (empty == valid). Dependency-free like the other
    validators — semantic checks that need autotune's variant sets
    (winner membership, key/entry agreement) live in
    ``autotune_live.load_bank``, which also degrades best-effort."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != WINNER_BANK_SCHEMA:
        problems.append(
            f"schema != {WINNER_BANK_SCHEMA}: {doc.get('schema')!r}"
        )
    if not isinstance(doc.get("sweep_rev"), str) or not doc.get("sweep_rev"):
        problems.append("sweep_rev: not a non-empty string")
    if not isinstance(doc.get("ts"), (int, float)) \
            or isinstance(doc.get("ts"), bool):
        problems.append("ts: not a number")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        problems.append("entries: not a dict")
        entries = {}
    for key, entry in entries.items():
        where = f"entries[{key!r}]"
        if not isinstance(entry, dict):
            problems.append(f"{where}: not a dict")
            continue
        for field in ("device_kind", "knob", "geometry", "winner",
                      "sweep_rev"):
            if not isinstance(entry.get(field), str):
                problems.append(f"{where}.{field}: not a string")
        if entry.get("source") not in WINNER_BANK_SOURCES:
            problems.append(
                f"{where}.source: bad source {entry.get('source')!r}"
            )
        if not isinstance(entry.get("wins"), int) \
                or isinstance(entry.get("wins"), bool):
            problems.append(f"{where}.wins: not an int")
        if not isinstance(entry.get("ts"), (int, float)) \
                or isinstance(entry.get("ts"), bool):
            problems.append(f"{where}.ts: not a number")
        per_item = entry.get("device_s_per_item")
        if per_item is not None and (
            not isinstance(per_item, dict) or not all(
                isinstance(v, (int, float)) and not isinstance(v, bool)
                for v in per_item.values()
            )
        ):
            problems.append(
                f"{where}.device_s_per_item: not a dict of numbers"
            )
    return problems


#: schema tag of the continuous-autotune probe/report document
#: (scripts/live_tune_probe.py over tmr_tpu/autotune_live.py): the
#: tuner's replayable decision log (every shadow measurement, oracle
#: refusal, promotion, and demotion with cause), its shadow-budget
#: accounting, and the probe's fail-closed checks — disabled-mode
#: bitwise identity, <1% shadow fraction, promotion speedup with zero
#: hot-path cold compiles, anomaly demotion, replay consistency.
#: ``bench_trend.py --live-tune`` rc-gates on ``checks``.
LIVE_TUNE_REPORT_SCHEMA = "live_tune_report/v1"

#: closed decision-event vocabulary of the replayable log: "shadow" =
#: one symmetric incumbent-vs-candidate measurement; "refusal" = the
#: oracle rejected the candidate's result (arm disqualified);
#: "promote" / "demote" = an election changed the serving formulation.
LIVE_TUNE_EVENTS = ("shadow", "refusal", "promote", "demote")


def validate_live_tune_report(doc: dict) -> List[str]:
    """Structural check of a live_tune_report/v1 document; returns a
    list of problems (empty == valid). An error record
    ({"schema": ..., "error": str}) is contractually valid (the probe's
    wedge path). Dependency-free like the other validators."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"not a dict: {type(doc).__name__}"]
    if doc.get("schema") != LIVE_TUNE_REPORT_SCHEMA:
        problems.append(
            f"schema != {LIVE_TUNE_REPORT_SCHEMA}: {doc.get('schema')!r}"
        )
    if "error" in doc:
        if not isinstance(doc["error"], str) or not doc["error"]:
            problems.append("error: not a non-empty string")
        return problems
    if not isinstance(doc.get("device_kind"), str) \
            or not doc.get("device_kind"):
        problems.append("device_kind: not a non-empty string")
    tuner = doc.get("tuner")
    if not isinstance(tuner, dict):
        problems.append("tuner: not a dict")
    else:
        for field in ("knob", "incumbent"):
            if not isinstance(tuner.get(field), str) \
                    or not tuner.get(field):
                problems.append(f"tuner.{field}: not a non-empty string")
        counters = tuner.get("counters")
        if not isinstance(counters, dict) or not all(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            for v in counters.values()
        ):
            problems.append("tuner.counters: not a dict of numbers")
        decisions = tuner.get("decisions")
        if not isinstance(decisions, list):
            problems.append("tuner.decisions: not a list")
        else:
            for i, rec in enumerate(decisions):
                where = f"tuner.decisions[{i}]"
                if not isinstance(rec, dict):
                    problems.append(f"{where}: not a dict")
                    continue
                if rec.get("event") not in LIVE_TUNE_EVENTS:
                    problems.append(
                        f"{where}.event: bad event {rec.get('event')!r}"
                    )
                for field in ("knob", "arm"):
                    if not isinstance(rec.get(field), str):
                        problems.append(f"{where}.{field}: not a string")
                if not isinstance(rec.get("ts"), (int, float)) \
                        or isinstance(rec.get("ts"), bool):
                    problems.append(f"{where}.ts: not a number")
                if rec.get("event") == "demote" and (
                    not isinstance(rec.get("cause"), str)
                    or not rec.get("cause")
                ):
                    problems.append(
                        f"{where}.cause: demote without a cause"
                    )
    summary = doc.get("summary")
    if not isinstance(summary, dict):
        problems.append("summary: not a dict")
    checks = doc.get("checks")
    if not isinstance(checks, dict) or not checks or not all(
        isinstance(v, bool) for v in checks.values()
    ):
        problems.append("checks: not a non-empty dict of booleans")
    return problems


#: registry bound: the attention gates are lru_cached (one record per
#: config) but pallas_xcorr_ok's pre-cache refusals (kill-switch /
#: backend / shape) record on EVERY call — a long-lived process that
#: never drains must not grow without bound, so the oldest records roll
#: off past this many. Consumers drain far below it in practice.
_MAX_GATE_REFUSALS = 256

_GATE_REFUSALS: List[dict] = []


class FormulationFallbackWarning(UserWarning):
    """An explicitly requested kernel formulation fell back at trace time.

    ``env_var`` names the knob whose request was refused (e.g.
    "TMR_XCORR_IMPL", "TMR_XCORR_IMPL_SMALL", "TMR_QUANT")."""

    def __init__(self, env_var: str, message: str):
        super().__init__(message)
        self.env_var = env_var


def record_gate_refusal(
    gate: str,
    cause: str,
    message: str = "",
    exception: Optional[str] = None,
    config: Optional[Dict[str, object]] = None,
) -> dict:
    """Append one structured refusal record and return it.

    ``cause`` is a small closed vocabulary so consumers can branch without
    parsing messages: "kill-switch" (env force-disable), "backend" (wrong
    default backend), "forward-mismatch" / "grad-mismatch" (numerics
    disagreed with the oracle beyond tolerance), "unsupported-shape" (a
    static rule, the chip compiler's refusal of a retired kernel
    included), "partitioned" (a Mosaic kernel asked for inside a program
    XLA partitions — :func:`mosaic_kernels_off`), "exception" (the check
    raised — ``exception`` then carries the class name and ``message`` the
    stringified error, Mosaic lowering failures included). ``config`` is
    the gate's cache key made explicit: geometry plus whatever the verdict
    is scoped to (tile sizes, window group, scores dtype).

    Note the gates are lru_cached: a refusal records only when the check
    actually RUNS (cache miss). Diagnostics consumers that need causes for
    a previously cached False must ``cache_clear()`` first — exactly what
    scripts/gate_probe.py does.
    """
    rec: dict = {
        "schema": GATE_PROBE_SCHEMA,
        "gate": gate,
        "cause": cause,
        "message": message,
        "exception": exception,
        "config": dict(config or {}),
    }
    try:  # backend identity is best-effort: never let diagnostics raise
        import jax

        rec["backend"] = jax.default_backend()
        rec["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        rec["backend"] = None
        rec["device_kind"] = None
    _GATE_REFUSALS.append(rec)
    if len(_GATE_REFUSALS) > _MAX_GATE_REFUSALS:
        del _GATE_REFUSALS[:-_MAX_GATE_REFUSALS]
    return rec


def gate_refused(
    gate: str,
    reason: str,
    cause: str,
    config: Optional[Dict[str, object]] = None,
    exception: Optional[str] = None,
) -> bool:
    """record_gate_refusal + the TMR_GATE_DEBUG stderr line, returning
    False so gate checks can ``return gate_refused(...)`` — the one
    definition of the refuse-and-say-why move every oracle gate makes
    (fused_heads / quant / postprocess use it; the older attention and
    xcorr gates predate it)."""
    record_gate_refusal(gate, cause, message=reason, exception=exception,
                        config=config)
    _debug(f"{gate}: refused — {reason}")
    return False


_MOSAIC_OFF = threading.local()


@contextlib.contextmanager
def mosaic_kernels_off(reason: str):
    """While active on this thread, every Mosaic gate (:func:`mosaic_gate`)
    answers no with cause "partitioned" and ``reason``. For the traces of
    programs XLA partitions by itself (GSPMD, more than one device): JAX
    refuses to lower a Pallas TPU kernel there ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map."), so
    such a trace has to take the XLA formulations."""
    prev = mosaic_off_reason()
    _MOSAIC_OFF.reason = reason
    try:
        yield
    finally:
        _MOSAIC_OFF.reason = prev


def mosaic_off_reason() -> Optional[str]:
    """The reason of the :func:`mosaic_kernels_off` context active on this
    thread, or None."""
    return getattr(_MOSAIC_OFF, "reason", None)


def mosaic_gate(fn):
    """``functools.lru_cache`` for a gate that admits a Pallas TPU kernel,
    honouring :func:`mosaic_kernels_off` ahead of the cache (a verdict
    reached for a one-device program must not admit the kernel into a
    partitioned one, nor the reverse).

    While ``fn`` runs, this thread knows which gate is being asked and with
    what arguments: the self-check ``fn`` reaches through
    :func:`run_outside_trace` is then answered from the result an earlier
    process kept beside the compile cache (see there). Everything ``fn``
    decides ahead of that call is decided anew in every process.
    ``cache_clear()`` also turns the kept results away for the rest of the
    process: the next call of each argument set runs its self-check and
    replaces the file."""
    import inspect

    _check_context()  # made while modules are imported, ahead of any trace
    signature = inspect.signature(fn)
    refresh = False

    @functools.wraps(fn)
    def asked(*args, **kw):
        bound = signature.bind(*args, **kw)
        bound.apply_defaults()
        prev = getattr(_ASKED, "gate", None)
        _ASKED.gate = {
            "name": fn.__name__, "refresh": refresh,
            "args": {k: repr(v) for k, v in bound.arguments.items()},
        }
        try:
            return fn(*args, **kw)
        finally:
            _ASKED.gate = prev

    cached = functools.lru_cache(maxsize=None)(asked)

    @functools.wraps(fn)
    def gate(*args, **kw):
        reason = mosaic_off_reason()
        if reason is not None:
            return gate_refused(fn.__name__, reason, "partitioned",
                                config={"args": list(args), **kw})
        return cached(*args, **kw)

    def cache_clear():
        nonlocal refresh
        refresh = True
        cached.cache_clear()

    gate.cache_clear = cache_clear
    gate.cache_info = cached.cache_info
    return gate


# --------------------------------------------------------------------------
# A self-check's result, kept beside the compile cache.
#
# A gate's compiled self-check costs a process seconds of tracing and
# lowering that no compile cache can skip (its key is the lowered text),
# and its answer is a function of nothing that changes between two starts
# on one machine. So what the check returned is kept as one small file in
# the directory of JAX's persistent cache, under a key that holds all the
# answer depends on: the gate and its arguments, the backend, device kind,
# jax, jaxlib and libtpu, the contents (not the paths) of the source the
# checks run, and the trace-time knobs that source reads. Only what passed
# is kept (``True``, or the number the gate then holds to its own tolerance
# in every process): a refusal's cause would be lost and an exception may
# be the machine's. Kept and taken on a TPU only, where the checks compile;
# with no cache directory, or the cache switched off, nothing is read or
# written. Deleting the ``tmr-gate-*.json`` files, or ``cache_clear()``,
# asks again.
# --------------------------------------------------------------------------

GATE_RESULT_SCHEMA = "tmr_gate_result/v1"

#: the :func:`mosaic_gate` call this thread is inside, if any
_ASKED = threading.local()

#: the package's directory; the digested files are named relative to it
_PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))

#: the knobs among the digested source's that change no program
_KEY_BLIND_KNOBS = ("TMR_GATE_DEBUG",)


@functools.lru_cache(maxsize=None)
def _source_digest(root: str) -> Tuple[str, Tuple[str, ...]]:
    """(sha256 over the relative names and contents of every ``.py`` under
    ``ops/`` and ``models/`` plus this file, the ``TMR_*`` names they
    hold), once a process: an edit to any kernel or oracle asks every gate
    again, as it sends the programs past the compile cache."""
    import hashlib
    import re

    names = ["diagnostics.py"]
    for sub in ("ops", "models"):
        for at, _, files in os.walk(os.path.join(root, sub)):
            names += [os.path.relpath(os.path.join(at, f), root)
                      for f in files if f.endswith(".py")]
    sha, knobs = hashlib.sha256(), set()
    for name in sorted(n.replace(os.sep, "/") for n in names):
        with open(os.path.join(root, name), "rb") as f:
            text = f.read()
        sha.update(name.encode() + b"\0" + text + b"\0")
        knobs.update(re.findall(rb"TMR_[A-Z0-9_]+", text))
    return sha.hexdigest(), tuple(sorted(k.decode() for k in knobs))


def _backend_identity() -> Optional[Dict[str, str]]:
    """What a compiled check's answer depends on outside this repo, or None
    off a TPU (there every Mosaic gate refuses ahead of its check, and the
    interpreter-mode checks of tests must leave nothing behind)."""
    import jax
    import jaxlib

    # the device's own word: tests lift a gate's backend test by patching
    # ``jax.default_backend``, and must not be taken for a chip here
    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return {
        "backend": "tpu", "device_kind": device.device_kind,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "platform_version": device.client.platform_version,
    }


def _gate_result_file(asked: dict) -> Tuple[Optional[str], Optional[dict]]:
    """(file, key) of the result of the self-check that the gate ``asked``
    (what :func:`mosaic_gate` left on this thread) is running, or (None,
    None) where nothing is kept: a gate's second self-check (one key cannot
    name two results), no TPU, no cache directory. Never raises."""
    import hashlib
    import json

    if asked.get("used"):
        return None, None
    asked["used"] = True
    gate = asked["name"]
    try:
        from tmr_tpu.utils.cache import persistent_cache_dir

        where = persistent_cache_dir()
        identity = _backend_identity() if where else None
        if identity is None:
            return None, None
        digest, knobs = _source_digest(_PACKAGE_DIR)
        key = {
            "schema": GATE_RESULT_SCHEMA, "gate": gate,
            "args": asked["args"], **identity, "source": digest,
            "env": {k: os.environ[k] for k in knobs
                    if k in os.environ and k not in _KEY_BLIND_KNOBS},
        }
        name = hashlib.sha256(
            json.dumps(key, sort_keys=True).encode()).hexdigest()[:32]
        return os.path.join(where, f"tmr-gate-{gate}-{name}.json"), key
    except Exception as e:  # a cache is a nicety; never a crash
        warnings.warn(f"gate results not kept: {type(e).__name__}: {e}")
        return None, None


def _storable(result) -> bool:
    """A pass: ``True``, or a finite number for the gate's tolerance."""
    import math

    return result is True or (
        type(result) in (int, float) and math.isfinite(result))


def _read_gate_result(path: str, key: dict):
    """The result kept at ``path`` under exactly ``key``, or None: a file
    that is missing, torn, another key's or no pass is a miss."""
    import json

    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("key") != key:
        return None
    return doc["result"] if _storable(doc.get("result")) else None


def _keep_gate_result(path: str, key: dict, result) -> None:
    """Leave ``path`` holding ``result`` if it is a pass, else absent.
    Written under a temporary name and renamed, so that two processes
    cannot tear it; a directory that cannot be written is a warning."""
    import json
    import tempfile

    try:
        if not _storable(result):
            if os.path.exists(path):
                os.remove(path)
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), prefix=".tmr-gate-", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"key": key, "result": result}, f, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            os.remove(tmp)
            raise
    except OSError as e:
        warnings.warn(f"gate result not kept at {path}: {e}")


_CHECK_CONTEXT = None
_CHECK_CONTEXT_LOCK = threading.Lock()


def _check_context():
    """The trace context of the self-checks (``jax.make_user_context``, part
    of every tracing cache's key), made once a process. A check runs under a
    value of its own, so that nothing it traces is shared with a later trace:
    JAX keeps the jaxpr of every jitted helper (``jnp.where`` and the like)
    by shape, with the call stack of whoever traced it first, and a Mosaic
    kernel's serialized body carries those stacks. A program traced after a
    check would hold other bytes, and another compile-cache key, than the
    same program in a process whose gates were answered from disk."""
    global _CHECK_CONTEXT
    with _CHECK_CONTEXT_LOCK:
        if _CHECK_CONTEXT is None:
            import jax

            _CHECK_CONTEXT = jax.make_user_context()
    return _CHECK_CONTEXT


def run_outside_trace(fn, gate: str = ""):
    """Run ``fn()`` with a clean JAX trace state and return its result.

    The gates are first asked while a model is being traced (under
    ``jax.jit``), and their self-checks have to execute compiled programs
    on concrete values. JAX's trace state is thread-local, so a fresh
    thread starts outside every ambient trace: jitted calls there compile
    and run — Pallas kernels included, which
    ``jax.ensure_compile_time_eval`` cannot evaluate. It runs under a trace
    context of its own (:func:`_check_context`), so what it traces leaves
    no mark on a program traced after it. Exceptions propagate to the
    caller.

    Called by the :func:`mosaic_gate` gate named ``gate``, the result is
    taken from the file an earlier process kept (above) and no thread is
    started; else ``fn`` runs and a pass is kept. ``gate.result.disk`` /
    ``gate.result.check`` count the two.

    The wait is a ``gate.selfcheck`` set-up span named for ``gate``,
    recorded on the calling thread: asked inside a program's first call,
    it is that ``compile`` span's child (obs/tracing.py). Under a
    ``mosaic_gate`` call its ``source`` says which it was: ``"disk"``
    (the span covers computing the key and reading the file) or
    ``"check"``."""
    from concurrent.futures import ThreadPoolExecutor

    from tmr_tpu.obs import metrics
    from tmr_tpu.obs.tracing import span

    asked = getattr(_ASKED, "gate", None)
    if asked is not None and asked["name"] != gate:
        asked = None  # a check of another name is not that gate's own
    with span("gate.selfcheck", scope="setup", gate=gate) as record:
        path, key = _gate_result_file(asked) if asked else (None, None)
        if path is not None and not asked["refresh"]:
            kept = _read_gate_result(path, key)
            if kept is not None:
                record.set_attr(source="disk")
                metrics.counter("gate.result.disk").inc()
                _debug(f"{gate}: answered from disk, {path}")
                return kept
        if asked:
            record.set_attr(source="check")
        metrics.counter("gate.result.check").inc()
        context = _check_context()

        def isolated():
            with context("gate.selfcheck"):
                return fn()

        result = None  # what an exception leaves: no pass
        try:
            with ThreadPoolExecutor(max_workers=1) as pool:
                result = pool.submit(isolated).result()
        finally:
            if path is not None:
                _keep_gate_result(path, key, result)
        if path is not None:
            _debug(f"{gate}: self-check ran, result "
                   f"{'kept at ' + path if _storable(result) else 'not kept'}")
        return result


def _debug(line: str) -> None:
    if os.environ.get("TMR_GATE_DEBUG"):
        import sys

        print(f"[gate] {line}", file=sys.stderr)


def gate_refusals() -> List[dict]:
    """Snapshot of the recorded refusals (oldest first), not cleared."""
    return list(_GATE_REFUSALS)


def drain_gate_refusals() -> List[dict]:
    """Return all recorded refusals and clear the registry — the harness
    protocol: drain before a measurement to discard stale records, drain
    after to attribute fresh ones to that measurement."""
    out = list(_GATE_REFUSALS)
    _GATE_REFUSALS.clear()
    return out

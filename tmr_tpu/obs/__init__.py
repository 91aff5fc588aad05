"""Unified telemetry: span tracing, metrics registry, compile accounting.

The observability layer every serving/map/train component records into —
see tracing.py (always-on coarse set-up and batch spans, request-scoped
spans under ``TMR_TRACE=1`` -> Chrome trace JSON + xprof
TraceAnnotations), metrics.py (named
counters/gauges/histograms, ``metrics_report/v1`` snapshots),
compile.py (per-trace/compile events with cold vs key-change causes),
devtime.py (per-program device-time attribution + MFU/roofline
accounting, ``mfu_report/v1``), and flight.py (the ``TMR_FLIGHT``
recorder ring, the anomaly-detecting HealthWatch, and the health
heartbeat), and fleetobs.py (the ``TMR_FLEET_OBS`` fleet-wide plane:
cross-process trace propagation, heartbeat metrics rollup, the
stitched cluster timeline, and the fleet HealthWatch).
``scripts/obs_probe.py``, ``scripts/obs_watch.py``, and
``scripts/fleet_obs_probe.py`` are the measured proofs;
QUICKSTART_RUN.md "Observability", "Performance accounting & health
watch", and "Fleet observability" document the knobs.
Import-light on purpose: nothing here imports jax at module load, so
any layer (ops, data, utils) can instrument itself.
"""

from tmr_tpu.obs.compile import (
    compile_event_seq,
    compile_events,
    compile_events_since,
    drain_compile_events,
    record_compile_event,
    take_answer,
    track_compile,
)
from tmr_tpu.obs.devtime import (
    attribute_call,
    forward_tflops_per_image,
    mfu_report,
    platform_peak,
    track_devtime,
)
from tmr_tpu.obs.fleetobs import (
    FleetHealthWatch,
    FleetObs,
    WorkerObs,
    fleet_obs_enabled,
    stitch_chrome_traces,
)
from tmr_tpu.obs.fleetobs import configure as fleet_obs_configure
from tmr_tpu.obs.flight import (
    FlightRecorder,
    Heartbeat,
    HealthWatch,
    flight_enabled,
)
from tmr_tpu.obs.flight import configure as flight_configure
from tmr_tpu.obs.flight import get_recorder as flight_recorder
from tmr_tpu.obs.flight import record as flight_record
from tmr_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    get_registry,
    histogram,
)
from tmr_tpu.obs.tracing import (
    add_span,
    chrome_trace,
    clear,
    configure,
    dropped_spans,
    new_trace_id,
    save_chrome_trace,
    span,
    spans,
    spans_ns,
    stage_batch,
    tracing_enabled,
)

__all__ = [
    "Counter",
    "FleetHealthWatch",
    "FleetObs",
    "FlightRecorder",
    "Gauge",
    "HealthWatch",
    "Heartbeat",
    "Histogram",
    "MetricsRegistry",
    "WorkerObs",
    "add_span",
    "attribute_call",
    "chrome_trace",
    "clear",
    "compile_event_seq",
    "compile_events",
    "compile_events_since",
    "configure",
    "counter",
    "drain_compile_events",
    "dropped_spans",
    "fleet_obs_configure",
    "fleet_obs_enabled",
    "flight_configure",
    "flight_enabled",
    "flight_record",
    "flight_recorder",
    "forward_tflops_per_image",
    "gauge",
    "get_registry",
    "histogram",
    "mfu_report",
    "new_trace_id",
    "platform_peak",
    "record_compile_event",
    "save_chrome_trace",
    "span",
    "spans",
    "spans_ns",
    "stage_batch",
    "stitch_chrome_traces",
    "take_answer",
    "tracing_enabled",
    "track_compile",
    "track_devtime",
]

"""Tracing / profiling / protocol logging — a first-class subsystem.

The reference has none of this (SURVEY §5.1: no profiler hooks, no timing
instrumentation); its only observability artifacts are the Hadoop mapper's
timestamped stderr logs (reference ``logs/mapper_debug_*.txt``) and the
``[INFO]/[WARNING]/[ERROR]/[PROGRESS]`` stderr protocol of ``reducer.py:29-94``.
This module supplies the TPU-native versions of both, plus what a real
framework needs:

- :func:`trace` — capture an XLA/TPU profiler trace (view with
  TensorBoard/xprof) around any region.
- :func:`annotate` / :func:`step_annotation` — named trace regions that show
  up on the TPU timeline inside a capture.
- :class:`PhaseTimer` — cheap host-side per-phase wall-clock accounting with
  an aggregate report (count / total / mean), used by the training loop and
  the streaming pipeline.
- :func:`log_info` etc. — the reference's stderr logging protocol, kept
  line-compatible (``[LEVEL] message``) so log-scraping tooling carries over.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, Iterator, Optional


# ----------------------------------------------------------------- logging
def _emit(level: str, msg: str) -> None:
    """stderr protocol line, format-compatible with reducer.py:29-94."""
    print(f"[{level}] {msg}", file=sys.stderr, flush=True)


def log_info(msg: str) -> None:
    _emit("INFO", msg)


def log_warning(msg: str) -> None:
    _emit("WARNING", msg)


def log_error(msg: str) -> None:
    _emit("ERROR", msg)


def log_progress(msg: str) -> None:
    _emit("PROGRESS", msg)


# ----------------------------------------------------------------- tracing
@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """Capture a device profiler trace into ``logdir`` (no-op when None).

    Wraps ``jax.profiler.trace`` so callers don't import jax at module load;
    the resulting trace includes XLA HLO timelines, TPU step markers, and any
    :func:`annotate` regions entered inside.
    """
    if not logdir:
        yield
        return
    import jax

    with jax.profiler.trace(logdir):
        yield


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named region on the profiler timeline (TraceAnnotation)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def step_annotation(name: str, step: int) -> Iterator[None]:
    """Step marker (StepTraceAnnotation) — lets xprof group per-step work."""
    import jax

    with jax.profiler.StepTraceAnnotation(name, step_num=step):
        yield


# ---------------------------------------------------- device microbenchmark
def measure_rtt_floor(samples: int = 3) -> float:
    """Dispatch + scalar-fetch round-trip floor of the current backend.

    Subtracted from chained timings; the canonical copy
    used by bench.py, scripts/profile_breakdown.py and utils/autotune.py.
    """
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1.0)
    z = jnp.zeros((), jnp.float32)
    _ = jax.device_get(tiny(z))
    t0 = time.perf_counter()
    for _ in range(samples):
        _ = jax.device_get(tiny(z))
    return (time.perf_counter() - t0) / samples


def chained_seconds_per_iter(step, *args, iters: int = 5, rtt: float = 0.0):
    """Steady-state sec/iter of ``step(*args, fb) -> (out, fb')``.

    The trailing scalar feedback forces back-to-back device execution
    (``jax.block_until_ready`` is advisory on some remote transports);
    timing closes with ONE scalar fetch and subtracts the measured
    round-trip floor. First call (compile + warmup) happens outside the
    timed window.

    When the whole chain finishes inside ~3x the RTT floor the subtraction
    is noise (a ~1 ms/iter op under a 67 ms RTT used to bank 0.0 —
    indistinguishable from free), so the chain length doubles until the
    elapsed window dominates the RTT or a 4096-iter cap is hit. Fast ops
    are exactly the ones that can afford the extra iterations.
    """
    import jax
    import jax.numpy as jnp

    fb = jnp.zeros((), jnp.float32)
    out, fb = step(*args, fb)
    while True:
        fb = fb * 0.0
        _ = jax.device_get(fb)
        t0 = time.perf_counter()
        for _ in range(iters):
            out, fb = step(*args, fb)
        _ = jax.device_get(fb)
        elapsed = time.perf_counter() - t0
        if elapsed >= 3.0 * rtt or iters >= 4096:
            return max((elapsed - rtt) / iters, 1e-9)
        iters = min(
            4096, max(iters * 2, int(iters * 4.0 * rtt / (elapsed + 1e-9)))
        )


# ------------------------------------------------------------------ timing
class PhaseTimer:
    """Host-side wall-clock accounting by phase name.

    Usage::

        timers = PhaseTimer()
        with timers.phase("data"):
            batch = next(it)
        with timers.phase("step"):
            state, losses = train_step(state, batch)
        print(timers.report(), file=sys.stderr)

    Thread-safe: serve and map time phases from worker threads, so each
    phase is an obs.metrics Histogram (locked instruments) rather than
    the old private float dict; ``totals``/``counts`` remain readable as
    dict snapshots. ``report(registry=...)`` renders the table AND folds
    the aggregates into a metrics registry (``time/<phase>`` histograms)
    so per-epoch timers land in the process-wide ``metrics_report/v1``.
    With ``span_prefix`` set, every phase also opens an obs tracing span
    (``<span_prefix><name>``) — free when ``TMR_TRACE=0``.

    Device work is async under jit; a phase that must include device time
    should block (e.g. ``jax.block_until_ready``) before exiting — the train
    loop's loss readback already does this implicitly.
    """

    def __init__(self, span_prefix: Optional[str] = None) -> None:
        from tmr_tpu.obs.metrics import Histogram

        self._Histogram = Histogram
        self._lock = threading.Lock()
        self._hist: Dict[str, Histogram] = {}
        self._span_prefix = span_prefix

    def _h(self, name: str):
        with self._lock:
            h = self._hist.get(name)
            if h is None:
                h = self._Histogram()
                self._hist[name] = h
            return h

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        span_cm = None
        if self._span_prefix is not None:
            from tmr_tpu import obs

            if obs.tracing_enabled():
                span_cm = obs.span(self._span_prefix + name)
                span_cm.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if span_cm is not None:
                span_cm.__exit__(None, None, None)
            self._h(name).observe(dt)

    # dict-shaped views, back-compat with the pre-registry PhaseTimer
    @property
    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {n: h.sum for n, h in self._hist.items() if h.count}

    @property
    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {n: h.count for n, h in self._hist.items() if h.count}

    def mean(self, name: str) -> float:
        h = self._h(name)
        return h.sum / max(h.count, 1)

    def as_dict(self, prefix: str = "time/") -> Dict[str, float]:
        """Totals keyed for the metrics CSV (``time/<phase>`` seconds)."""
        return {f"{prefix}{k}": v for k, v in self.totals.items()}

    def to_registry(self, registry, prefix: str = "time/") -> None:
        """Fold every phase's distribution into ``registry`` histograms
        (``<prefix><phase>``). Call once per timer lifetime (a fresh
        per-epoch timer merged at epoch end) — merging twice would
        double-count."""
        with self._lock:
            items = list(self._hist.items())
        for name, h in items:
            registry.histogram(f"{prefix}{name}").merge(h)

    def report(self, registry=None, prefix: str = "time/") -> str:
        """Aggregate table (and, with ``registry``, a to_registry flush)."""
        if registry is not None:
            self.to_registry(registry, prefix=prefix)
        totals, counts = self.totals, self.counts
        rows = [f"{'PHASE':<16} | {'CALLS':>6} | {'TOTAL_S':>9} | {'MEAN_MS':>9}"]
        rows.append("-" * 51)
        for name in sorted(totals):
            mean = totals[name] / max(counts[name], 1)
            rows.append(
                f"{name:<16} | {counts[name]:>6} | "
                f"{totals[name]:>9.3f} | {mean * 1e3:>9.2f}"
            )
        return "\n".join(rows)

    def reset(self) -> None:
        with self._lock:
            self._hist.clear()

"""Host-side span tracing: always-on coarse spans, request spans on demand.

``span("stage", **attrs)`` is a context manager that records one complete
event (name, scope, start, duration, thread, trace/span/parent IDs,
attributes) into a per-thread ring buffer; ``add_span`` records an event
with explicit timestamps for stages whose boundaries were stamped
elsewhere (the batcher's queue-wait window, a batch's staging window).

Every span has a ``scope``, and the scope decides whether it is recorded:

- ``"setup"`` (a handful a process: a gate's self-check, a program's
  first call) and ``"batch"`` (a few a batch: the Predictor's stage /
  dispatch / fetch / unpack, the serve pipeline's four batch stages) are
  *coarse* and ALWAYS recorded. They are what a benchmark's traced run
  reads (``benchmarks/reducers/program_span_ms.py``), and being on in the
  untraced run too, its end-to-end comparison prices them.
- ``"request"`` (the default) is recorded only under ``TMR_TRACE=1``.

The Predictor's four spans of one batch share an id, ``batch``
(:func:`stage_batch` at ``predict.stage``, :func:`take_batch` at
``predict.dispatch``; obs/compile.py carries it to the fetch of that
batch's answer, which may come after the next batch's dispatch).

Exports:

- :func:`chrome_trace` — Chrome trace-event JSON (open in Perfetto /
  ``chrome://tracing``): one ``ph: "X"`` event per span plus thread-name
  metadata, trace/span/parent IDs under ``args``.
- every entered span also enters ``jax.profiler.TraceAnnotation``, so the
  SAME host spans appear on the TPU timeline inside an xprof capture —
  host-side stage boundaries line up against device execution.

Cost model (the load-bearing contract, pinned by tests/test_obs.py):

- ``TMR_TRACE=0`` (the default): a request-scope ``span()`` is one
  module-global bool check returning a shared no-op context manager — a
  few hundred ns per enter/exit, nothing allocated, nothing locked. A
  request-scope span is paid thousands of times a second, so hot paths
  that would pay even for building kwargs guard on
  :func:`tracing_enabled` first.
- coarse spans, at either setting: one small object, two clock readings
  and one dict appended to the thread's own ring — a few microseconds.
  A batch is 0.5-1 s of device work and leaves about five of them, a
  process a handful of set-up ones, so they need no switch. They mirror
  into ``TraceAnnotation`` only under ``TMR_TRACE=1``.
- each thread appends to its OWN ring buffer (no cross-thread locking on
  the record path; the global lock is touched once per thread lifetime,
  at ring registration) and the ring overwrites its oldest events rather
  than growing — a long-lived server is memory-bounded by
  ``TMR_TRACE_RING`` events per thread, traced or not.

Trace IDs: a request's trace id is minted at submit
(:func:`new_trace_id`), travels WITH the request object through queueing,
coalescing, staging, execution and resolution, and every stage span
carries it — "where did this request's 40 ms go" is one filter in
Perfetto. Spans opened without an explicit trace id inherit the enclosing
span's (per-thread stack), so nested host phases group naturally.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional


def _env_flag(name: str, default: bool = False) -> bool:
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "off", "false", "no")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


#: module-global fast path: the ONLY thing a disabled span() touches.
#: None = not yet resolved — the TMR_TRACE* knobs are read LAZILY on
#: first use (analysis rule knob-import-time: an import-time read would
#: freeze the knobs before a consumer process could set them); after
#: first resolution the disabled path stays one bool check.
_ENABLED: Optional[bool] = None
_ANNOTATE_WANTED: Optional[bool] = None
_RING: Optional[int] = None


def _resolve_env_unlocked() -> None:
    """Fill any still-unset knob from the environment. Caller MUST hold
    ``_REG_LOCK``: an unsynchronized first-span resolve racing a
    ``configure(enabled=True)`` could re-check ``is None`` stale and
    overwrite the explicit setting with the env default."""
    global _ENABLED, _ANNOTATE_WANTED, _RING
    if _ENABLED is None:
        _ENABLED = _env_flag("TMR_TRACE")
    if _ANNOTATE_WANTED is None:
        _ANNOTATE_WANTED = _env_flag("TMR_TRACE_ANNOTATE", True)
    if _RING is None:
        _RING = max(_env_int("TMR_TRACE_RING", 8192), 16)


def _resolve_env() -> None:
    """Lazy first-use resolution (an explicit :func:`configure` value is
    never overwritten). Cost: taken only while ``_ENABLED is None`` —
    after the first resolution the disabled span path is back to one
    global bool check."""
    with _REG_LOCK:
        _resolve_env_unlocked()

_REG_LOCK = threading.Lock()
_ALL_BUFS: List["_Buf"] = []
_SPAN_IDS = itertools.count(1)  # .__next__ is atomic under the GIL
#: a batch's identity: one process-wide increasing integer shared by the
#: four ``predict.*`` spans of a batch (obs/compile.py joins them)
_BATCH_IDS = itertools.count(1)

#: resolved jax.profiler.TraceAnnotation class, None = not yet resolved,
#: False = unavailable/disabled
_ANN_CLS: Any = None


def _annotation_cls():
    global _ANN_CLS
    if _ANN_CLS is None:
        if not _ANNOTATE_WANTED:
            _ANN_CLS = False
        else:
            try:
                from jax.profiler import TraceAnnotation

                _ANN_CLS = TraceAnnotation
            except Exception:
                _ANN_CLS = False
    return _ANN_CLS


class _Buf:
    """One thread's span ring. Only its owner thread writes; readers
    snapshot under the registry lock at export time (a torn read of the
    newest slot is possible and acceptable — exports are diagnostics,
    the write path must never wait)."""

    __slots__ = ("tid", "thread_name", "cap", "events", "write", "stack")

    def __init__(self, cap: int) -> None:
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        self.cap = cap
        self.events: List[dict] = []
        self.write = 0
        self.stack: List[tuple] = []  # (span_id, trace_id) of open spans

    def record(self, rec: dict) -> None:
        # one local reference for the whole operation: clear() (any
        # thread, the drain-before-measure protocol) swaps self.events
        # for a fresh list — a check-then-index against the attribute
        # could len() the full old list and index the new empty one
        # (IndexError on the RECORDING thread, which may be a pipeline
        # thread that must never die). With the local ref the racing
        # record lands entirely in the old list and is simply dropped
        # with it.
        events = self.events
        if len(events) < self.cap:
            events.append(rec)
        else:
            events[self.write % self.cap] = rec
        self.write += 1

    def snapshot(self) -> List[dict]:
        n = len(self.events)
        if n < self.cap or self.write <= n:
            return list(self.events)
        i = self.write % self.cap
        return self.events[i:] + self.events[:i]

    def dropped(self) -> int:
        return max(0, self.write - self.cap)


class _Local(threading.local):
    buf: Optional[_Buf] = None
    #: the batch id ``predict.stage`` left for this thread's next dispatch
    batch: Optional[int] = None


_TLS = _Local()


def _buf() -> _Buf:
    b = _TLS.buf
    if b is None:
        b = _Buf(_RING)
        _TLS.buf = b
        with _REG_LOCK:
            _ALL_BUFS.append(b)
    return b


def tracing_enabled() -> bool:
    if _ENABLED is None:
        _resolve_env()
    return _ENABLED


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def next_span_id() -> int:
    """Mint a span id WITHOUT recording anything — for spans whose id
    must be advertised before they close (a fleet front door sends
    ``parent_span_id`` to a worker while its own root span is still
    open; obs/fleetobs.py). Ids are process-local: cross-process
    consumers must key by (process, span)."""
    return next(_SPAN_IDS)


def stage_batch() -> int:
    """Mint a batch's id at its ``predict.stage`` and leave it for the next
    ``predict.dispatch`` on this thread (:func:`take_batch`)."""
    _TLS.batch = batch = next(_BATCH_IDS)
    return batch


def take_batch() -> int:
    """The id the stage on this thread left, once; a dispatch that no stage
    came before (the trainer's eval step, ``ServeEngine._run_batch``) mints
    its own."""
    batch, _TLS.batch = _TLS.batch, None
    return next(_BATCH_IDS) if batch is None else batch


def configure(enabled: Optional[bool] = None,
              annotate: Optional[bool] = None,
              ring: Optional[int] = None) -> None:
    """Programmatic override of the TMR_TRACE / TMR_TRACE_ANNOTATE /
    TMR_TRACE_RING env knobs (probes and tests flip tracing without
    re-execing). ``ring`` applies to rings created after the call."""
    global _ENABLED, _ANNOTATE_WANTED, _ANN_CLS, _RING
    with _REG_LOCK:  # explicit settings and lazy env resolution must
        if enabled is not None:  # never interleave (first-span race)
            _ENABLED = bool(enabled)
        if annotate is not None:
            _ANNOTATE_WANTED = bool(annotate)
            _ANN_CLS = None  # re-resolve lazily
        if ring is not None:
            _RING = max(int(ring), 16)
        _resolve_env_unlocked()  # anything not explicitly set -> env


class _NoopSpan:
    """The shared disabled-mode span: enter/exit do nothing, one instance
    serves every call site — zero allocation on the disabled path."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "scope", "attrs", "trace_id", "span_id",
                 "parent_id", "t0", "_ann", "_b")

    def __init__(self, name: str, trace_id: Optional[str], scope: str,
                 attrs: Dict[str, Any]) -> None:
        self.name = name
        self.trace_id = trace_id
        self.scope = scope
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        b = _buf()
        self._b = b
        parent = b.stack[-1] if b.stack else None
        if self.trace_id is None:
            # a coarse root belongs to no request: no id is minted for it
            self.trace_id = parent[1] if parent else (
                new_trace_id() if self.scope == "request" else "")
        self.span_id = next(_SPAN_IDS)
        self.parent_id = parent[0] if parent else 0
        b.stack.append((self.span_id, self.trace_id))
        # a coarse span of an untraced process stays off the profiler
        ann_cls = _annotation_cls() if _ENABLED else None
        self._ann = ann_cls(self.name) if ann_cls else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set_attr(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __exit__(self, exc_type=None, exc=None, tb=None) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        b = self._b
        if b.stack and b.stack[-1][0] == self.span_id:
            b.stack.pop()
        b.record({
            "name": self.name,
            "scope": self.scope,
            "ts": self.t0,
            "dur": t1 - self.t0,
            "tid": b.tid,
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "attrs": self.attrs,
        })
        return False


def span(name: str, trace_id: Optional[str] = None,
         scope: str = "request", **attrs):
    """Context manager timing one named stage. A request-scope span is a
    no-op (shared singleton) when tracing is disabled; a coarse one
    (``scope="setup"`` / ``"batch"``) always records. Records a complete
    event on exit; under ``TMR_TRACE=1`` also mirrors the region into
    ``jax.profiler.TraceAnnotation``."""
    if _ENABLED is None:
        _resolve_env()
    if not _ENABLED and scope == "request":
        return _NOOP
    return _Span(name, trace_id, scope, attrs)


def add_span(name: str, t0: float, t1: float,
             trace_id: Optional[str] = None, parent: int = 0,
             span_id: Optional[int] = None, scope: str = "request",
             **attrs) -> None:
    """Record a complete event whose boundaries were stamped elsewhere
    (``time.perf_counter`` values) — queue-wait windows, a batch's
    staging and execute windows. Does not touch the nesting stack.
    ``scope`` decides as in :func:`span` whether it is recorded.
    ``span_id`` records under a pre-minted id (:func:`next_span_id`);
    ``parent`` may be a remote process's span id (cross-process context
    propagation parents receiver spans under the sender's id)."""
    if _ENABLED is None:
        _resolve_env()
    if not _ENABLED and scope == "request":
        return
    b = _buf()
    b.record({
        "name": name,
        "scope": scope,
        "ts": t0,
        "dur": max(t1 - t0, 0.0),
        "tid": b.tid,
        "trace": trace_id or "",
        "span": next(_SPAN_IDS) if span_id is None else int(span_id),
        "parent": parent,
        "attrs": attrs,
    })


def spans() -> List[dict]:
    """Every recorded span (all threads), oldest first."""
    with _REG_LOCK:
        bufs = list(_ALL_BUFS)
    out: List[dict] = []
    for b in bufs:
        out.extend(b.snapshot())
    out.sort(key=lambda r: r["ts"])
    return out


def spans_ns(names=None) -> List[list]:
    """Recorded spans as ``[name, t0_ns, t1_ns]`` rows on
    ``time.perf_counter_ns``'s clock, oldest first; ``names`` keeps only
    those names. The shape of ``benchmarks/trace.py:Spans.records``, so a
    driver can hand the program's spans to ``trace.gaps_add`` as they
    are. A span that stamped when its answer became ready (``ready_ts``:
    ``predict.fetch``) is followed by its two halves, ``<name>.wait`` up
    to the stamp and ``<name>.copy`` from it, so that a gap can be given
    to the half it fell in."""
    rows = []
    for r in spans():
        t0, t1 = round(r["ts"] * 1e9), round((r["ts"] + r["dur"]) * 1e9)
        rows.append([r["name"], t0, t1])
        ready = r["attrs"].get("ready_ts")
        if ready is not None:
            ready = round(ready * 1e9)
            rows.append([r["name"] + ".wait", t0, ready])
            rows.append([r["name"] + ".copy", ready, t1])
    return [row for row in rows if names is None or row[0] in names]


def dropped_spans() -> int:
    with _REG_LOCK:
        return sum(b.dropped() for b in _ALL_BUFS)


def clear() -> None:
    """Discard recorded spans (rings stay registered; open spans keep
    nesting state) — the drain-before-measure harness protocol."""
    with _REG_LOCK:
        for b in _ALL_BUFS:
            b.events = []
            b.write = 0


def chrome_trace() -> dict:
    """Chrome trace-event JSON (the ``traceEvents`` array format) —
    ``json.dump`` the result and load it in Perfetto. Timestamps are
    perf_counter microseconds (a shared monotonic base; only relative
    placement is meaningful)."""
    pid = os.getpid()
    events: List[dict] = []
    with _REG_LOCK:
        bufs = list(_ALL_BUFS)
    for b in bufs:
        events.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": b.tid,
            "args": {"name": b.thread_name},
        })
    for rec in spans():
        args = {"trace": rec["trace"], "span": rec["span"],
                "parent": rec["parent"], "scope": rec["scope"]}
        args.update(rec["attrs"])
        events.append({
            "ph": "X",
            "name": rec["name"],
            "pid": pid,
            "tid": rec["tid"],
            "ts": rec["ts"] * 1e6,
            "dur": rec["dur"] * 1e6,
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def save_chrome_trace(path: str) -> str:
    import json

    with open(path, "w") as f:
        json.dump(chrome_trace(), f)
    return path

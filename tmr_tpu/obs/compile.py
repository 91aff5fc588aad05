"""Compile-event accounting for the bucketed-jit program caches.

A recompile storm is invisible in the counters PRs 2-3 kept: it shows up
only as a latency cliff. This module makes every ``_compiled``-cache miss
in tmr_tpu/inference.py an explicit, attributable event: the compile key,
the program kind, wall time of the first (trace + XLA compile) call, and
a cause —

- ``cold``: this (kind, key) was never compiled in this process — first
  program of a kind, or a fresh Predictor re-compiling a key an earlier
  instance already paid for (expected: warmup);
- ``key-change``: this kind compiled before but never under THIS key —
  the signature of a storm (numpy-int key drift, an unexpected new
  bucket, a fresh donate/loss_fn flavor) that should be a cache hit.

Events land in two places at once: a bounded in-process log
(:func:`compile_events` / :func:`drain_compile_events`, the gate-refusal
registry pattern) and the process-wide metrics registry
(``compile.total``, ``compile.cold``, ``compile.key_change`` counters +
``compile.wall_s`` histogram). A program's first call is also a
``compile`` span (``scope="setup"``, always recorded: obs/tracing.py) on
the thread that paid the wall time, open while the call runs, so a gate
self-check asked inside the model's trace
(``diagnostics.run_outside_trace``) is its child; it carries the windowed
attention formulation the program traced with (``win_attn``) and how many
blocks took it (``win_attn_blocks``), from the ``vit.win_attn.*`` counters,
the same of the global blocks (``global_attn``, ``global_attn_blocks``, from
``vit.global_attn.*``), and for a trunk of typed layers
(``models/lm_trunk.py``) ``trunk_kda``, ``trunk_mla``, ``trunk_ssm``,
``trunk_gqa``, ``trunk_swa``, ``trunk_short_conv``, ``trunk_moe``,
``trunk_hc`` and ``experts_held`` from ``trunk.*``.

:func:`track_compile`'s wrapper is also the one seam every Predictor
program is called through, so it records each call as a
``predict.dispatch`` span (``scope="batch"``): ``Predictor.__call__``, the
trainer's eval step and ``ServeEngine._run_batch`` all get it from here.
The span carries the batch's id (the one ``predict.stage`` left on this
thread, else its own), and the wrapper notes beside the answer it returns
what it knew of it (id, program, bucket, rows, the stamp at which the
dispatch returned) for ``detections_to_numpy`` to take
(:func:`take_answer`), so that the fetch of a batch names the batch.

The wall time is measured on the wrapped program's FIRST call, not at
cache-insert: jit wrappers are lazy, and the first call is where trace +
compile (the seconds that matter) actually happen. A program that is
built but never called records nothing.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Iterator, List, Optional

from tmr_tpu.obs import metrics as _metrics
from tmr_tpu.obs import tracing as _tracing

#: prefix of the counters of windowed blocks traced, by formulation
_WIN_ATTN = "vit.win_attn."
#: the same of the global blocks (models/vit.py:_global_formulation)
_GLOBAL_ATTN = "vit.global_attn."

#: prefix of the counters of trunk layers traced (models/lm_trunk.py):
#: ``trunk.<kda|mla|ssm|gqa|swa|short_conv|moe|hc>.<formulation>`` and
#: ``trunk.experts_held``
_TRUNK = "trunk."
#: the run-time routing counters under the same prefix (inference.py)
_RUN_TIME = ("", "tokens", "pairs_here", "pairs_busiest")

#: bounded like diagnostics._GATE_REFUSALS: a long-lived server that
#: never drains must not grow without bound
_MAX_EVENTS = 512

_LOCK = threading.Lock()
_EVENTS: List[dict] = []
#: monotonic event sequence (never trimmed, never drained): consumers
#: that window the log (ServeEngine.health's recompile-storm detector)
#: key on it instead of list offsets — an absolute index goes blind the
#: moment the bounded log trims or another harness drains it
_SEQ = 0
#: kind -> set of key reprs ever compiled: the cause is decided per
#: (kind, key) — a second Predictor re-compiling an already-seen key is
#: "cold" (expected instance warmup), only a genuinely NEW key of a
#: known kind is "key-change" (the storm signature)
_SEEN_KEYS: dict = {}

#: answers dispatched and not yet fetched, oldest first: ``id`` of an
#: answer's ``boxes`` array -> (weak reference to it, what its dispatch
#: knew). Keyed by the array and not by a key of the dict, so callers find
#: in an answer what they found before; weak and bounded, so an answer
#: nobody fetches (the serve engine reads its own) pins nothing and rolls
#: off
_MAX_ANSWERS = 64
_ANSWERS: "OrderedDict[int, tuple]" = OrderedDict()
#: one id a tracked program: two Predictors' programs of one name and
#: bucket are two programs to whoever compares a batch with its program's
#: others (inference.py:_BatchClock)
_PROGRAM_IDS = itertools.count(1)


def record_compile_event(kind: str, key: Any, t0: float, t1: float,
                         bucket: Optional[dict] = None) -> dict:
    """Record one trace/compile occurrence in the log and the registry;
    returns the event record. The ``compile`` span is
    :func:`track_compile`'s, which holds it open over the call."""
    global _SEQ
    key_repr = repr(key)
    with _LOCK:
        seen = _SEEN_KEYS.setdefault(kind, set())
        cause = "key-change" if (seen and key_repr not in seen) else "cold"
        seen.add(key_repr)
        _SEQ += 1
        rec = {
            "kind": kind,
            "key": key_repr,
            "bucket": dict(bucket or {}),
            "wall_s": t1 - t0,
            "cause": cause,
            "seq": _SEQ,
        }
        _EVENTS.append(rec)
        if len(_EVENTS) > _MAX_EVENTS:
            del _EVENTS[:-_MAX_EVENTS]
    reg = _metrics.get_registry()
    reg.counter("compile.total").inc()
    reg.counter("compile.cold" if cause == "cold"
                else "compile.key_change").inc()
    reg.histogram("compile.wall_s").observe(rec["wall_s"])
    return rec


def _trunk_attrs(before: dict, after: dict) -> dict:
    """The ``compile`` span's ``trunk_kda`` / ``trunk_mla`` / ``trunk_ssm`` /
    ``trunk_gqa`` / ``trunk_swa`` / ``trunk_short_conv`` / ``trunk_moe`` /
    ``trunk_pairs`` (formulation x layers traced), ``trunk_hc`` (x
    sub-layers) and
    ``experts_held``, from what the trace-time counters ``trunk.*`` gained
    over a program's first call."""
    traced = {n: v - before.get(n, 0)  # names come without the prefix
              for n, v in after.items() if v > before.get(n, 0)}
    attrs = {}
    for name, count in sorted(traced.items()):
        kind, _, formulation = name.partition(".")
        if (kind in ("kda", "mla", "ssm", "gqa", "swa", "short_conv", "moe",
                     "pairs", "hc")
                and formulation not in _RUN_TIME):
            attrs[f"trunk_{kind}"] = f"{formulation} x{count}"
    layers = sum(c for n, c in traced.items() if n.startswith("moe."))
    if layers and "experts_held" in traced:
        attrs["experts_held"] = traced["experts_held"] // layers
    return attrs


def _detections(out) -> Iterator[dict]:
    """The detections in a program's answer: the answer itself, or inside
    the tuples a program with a loss or a feedback scalar returns."""
    if isinstance(out, dict):
        if "boxes" in out:
            yield out
    elif isinstance(out, tuple):
        for part in out:
            yield from _detections(part)


def _note_answer(out, known: dict) -> None:
    for dets in _detections(out):
        boxes = dets["boxes"]
        with _LOCK:
            _ANSWERS.pop(id(boxes), None)  # a dead array's id, reused
            _ANSWERS[id(boxes)] = (weakref.ref(boxes), known)
            while len(_ANSWERS) > _MAX_ANSWERS:
                _ANSWERS.popitem(last=False)


def take_answer(dets) -> Optional[dict]:
    """What the dispatch that returned ``dets`` noted of it, once: its
    ``batch``, ``program``, bucket, ``rows``, ``compiled`` (the tracked
    program's own id) and ``returned`` (the ``time.perf_counter`` stamp at
    which the dispatch returned). None for
    an answer no tracked program returned as it stands (numpy arrays, a
    dict put together by hand) and for one that rolled off."""
    boxes = dets.get("boxes") if isinstance(dets, dict) else None
    with _LOCK:
        entry = _ANSWERS.pop(id(boxes), None)
    if entry is None or entry[0]() is not boxes:
        return None
    return entry[1]


def compile_events() -> List[dict]:
    """Snapshot of recorded events (oldest first), not cleared."""
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def compile_event_seq() -> int:
    """The latest event's monotonic sequence number (0 = none ever) —
    the cursor a windowing consumer snapshots at construction."""
    with _LOCK:
        return _SEQ


def compile_events_since(seq: int):
    """``(events with .seq > seq, latest seq)`` — the cursor-based
    window read. Unlike slicing :func:`compile_events` by offset, this
    keeps working after the bounded log trims its head or a harness
    drains it (events that rolled off before being read are simply
    missed; the returned cursor still advances past them)."""
    with _LOCK:
        return [dict(e) for e in _EVENTS if e["seq"] > seq], _SEQ


def drain_compile_events() -> List[dict]:
    """Return and clear — the harness drain-before/after protocol. The
    (kind, key) cause memory is NOT cleared (it is process history,
    not measurement state)."""
    with _LOCK:
        out = list(_EVENTS)
        _EVENTS.clear()
    return out


def track_compile(fn, kind: str, key: Any,
                  bucket: Optional[dict] = None,
                  batch_arg: Optional[int] = None):
    """Wrap a freshly built jitted program so its first call records a
    compile event, and every call a ``predict.dispatch`` span with the
    batch's id, the program's name, its ``bucket`` and, where ``batch_arg``
    names the positional argument that carries the batch, its ``rows``;
    the same is noted for the answer's fetch (:func:`take_answer`). The
    wrapped callable is what goes into the ``_compiled`` cache, so every
    consumer sees the same accounting exactly once per cache entry."""
    done: List[bool] = []
    lock = threading.Lock()
    static = dict(bucket or {}, program=getattr(fn, "__name__", kind))
    compiled = next(_PROGRAM_IDS)

    def wrapped(*args, **kw):
        attrs = dict(static, batch=_tracing.take_batch())
        if batch_arg is not None:
            attrs["rows"] = int(args[batch_arg].shape[0])
        with _tracing.span("predict.dispatch", scope="batch", **attrs):
            out = fn(*args, **kw) if done else first_call(args, kw)
            _note_answer(out, dict(attrs, compiled=compiled,
                                   returned=time.perf_counter()))
        return out

    def first_call(args, kw):
        with _tracing.span("compile", scope="setup", kind=kind,
                           key=repr(key)) as sp:
            # models/vit.py counts each windowed block it traces by
            # the formulation taken: the difference over the first
            # call is this program's own
            counts = _metrics.get_registry().counters
            before = {"win_attn": counts(_WIN_ATTN),
                      "global_attn": counts(_GLOBAL_ATTN)}
            before_trunk = counts(_TRUNK)
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            t1 = time.perf_counter()
            for attr, prefix in (("win_attn", _WIN_ATTN),
                                 ("global_attn", _GLOBAL_ATTN)):
                traced = {n: v - before[attr].get(n, 0)
                          for n, v in counts(prefix).items()
                          if v > before[attr].get(n, 0)}
                if traced:
                    sp.set_attr(**{
                        attr: "+".join(sorted(traced)),
                        f"{attr}_blocks": sum(traced.values())})
            sp.set_attr(**_trunk_attrs(before_trunk, counts(_TRUNK)))
            with lock:
                if not done:
                    done.append(True)
                    rec = record_compile_event(kind, key, t0, t1,
                                               bucket=bucket)
                    sp.set_attr(cause=rec["cause"])
        return out

    wrapped.__wrapped__ = fn
    return wrapped

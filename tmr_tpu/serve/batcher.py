"""Dynamic micro-batching: coalesce single-image requests into bucket
programs under a latency bound.

The queue discipline (the StreamFlow lesson from PAPERS.md applied to the
eval path): requests accumulate per bucket key (Predictor.bucket_key — one
compiled program per key) and a batch is released when EITHER

- a bucket reaches its size bound (``bound_for(bucket)`` — by default the
  measured throughput-optimal batch from bench_extra's sweep via the
  autotune cache, see engine.py), or
- the OLDEST request in a bucket has waited ``max_wait_ms`` (the latency
  bound: a lone request is never held hostage to batch-filling).

Ragged releases (timeout flushes, close-time drains) are padded up to the
bound by the staging layer so every dispatch hits the one compiled program
shape per bucket.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from tmr_tpu import obs


@dataclass
class Request:
    """One in-flight inference request riding the batching pipeline."""

    image: Any  # host (S, S, 3) float32
    exemplars: Any  # host (K, 4) float32 (multi: padded to k_bucket)
    bucket: tuple  # Predictor.bucket_key(...)
    futures: List[Any] = field(default_factory=list)  # resolved together
    t_submit: float = field(default_factory=time.perf_counter)
    k_real: int = 1  # multi path: real exemplar rows
    image_digest: str = ""
    result_key: Optional[tuple] = None  # exemplar/result-cache key
    features: Any = None  # cached device features (heads path, hit)
    needs_features: bool = False  # heads path, promotion fill
    trace_id: str = ""  # per-request span correlation (obs.tracing)
    batch: int = 0  # id of the batch that carried it (set at release)
    group: Any = None  # replica-group queue id (mesh serving; None = the
    # single ungrouped pipeline, the pre-mesh behavior)
    priority: int = 0  # class-weighted scheduling (higher = sooner)
    deadline: Optional[float] = None  # absolute perf_counter seconds;
    # coalesced duplicates inherit the EARLIEST deadline of the group
    admitted: bool = False  # holds one admission slot until terminal
    degrade_steps: tuple = ()  # ladder steps applied to THIS request

    def expired(self, now: Optional[float] = None) -> bool:
        """Past its deadline? Expired requests are shed by the next
        pipeline stage instead of burning device time."""
        if self.deadline is None:
            return False
        return (time.perf_counter() if now is None else now) > self.deadline

    def resolve(self, value) -> None:
        for f in self.futures:
            if not f.done():
                f.set_result(value)

    def fail(self, exc: BaseException) -> None:
        for f in self.futures:
            if not f.done():
                f.set_exception(exc)


class MicroBatcher:
    """Per-bucket request queue with size- and latency-bounded release.

    ``next_batch()`` blocks until a batch is due and returns
    ``(bucket, [Request, ...])`` — or None once the batcher is closed AND
    drained (the consumer thread's shutdown signal). Thread-safe: any
    number of producers (``put``), one consumer.
    """

    def __init__(self, max_wait_ms: float,
                 bound_for: Callable[[tuple], int],
                 class_weight: Optional[Callable[[int], float]] = None,
                 groups: Optional[List[Any]] = None):
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.bound_for = bound_for
        #: priority-class weight for pop ordering (serve/admission.py's
        #: class_weight_fn in production); None -> all classes equal,
        #: which reproduces the PR 3 discipline exactly
        self.class_weight = class_weight
        #: replica-group queue ids (mesh serving): when set, requests
        #: queue per (group, bucket) and each group's consumer thread
        #: calls ``next_batch(group=...)`` — one engine saturates every
        #: group concurrently. None (the default) is the single
        #: ungrouped pipeline, behavior byte-identical to pre-mesh.
        self.groups = list(groups) if groups else None
        # ordered so the flush scan visits buckets in first-use order —
        # no bucket can be starved behind a constantly-full sibling;
        # grouped mode keys by (group, bucket)
        self._pending: "OrderedDict[tuple, deque]" = OrderedDict()
        #: highest priority currently waiting per queue key (entries
        #: only for nonzero priorities): the weighted full-bucket
        #: election and the priority-pop guard read this in O(1) instead
        #: of scanning the backlog — under overload the consumer thread
        #: must not pay O(total pending) per released batch
        self._maxp: Dict[tuple, int] = {}
        self._cond = threading.Condition()
        self._closed = False
        #: batch ids, local to this batcher's engine: minted where a batch
        #: is formed, carried by its requests and named by every span the
        #: batch causes (``batch=<id>``)
        self._batch_ids = itertools.count(1)
        #: released-batch size histogram {occupied_slots: count} — the
        #: serve report's batch-occupancy evidence
        self.occupancy: Counter = Counter()
        #: per-group occupancy (grouped mode only; the health report's
        #: per-replica-group evidence)
        self.occupancy_by_group: Dict[Any, Counter] = (
            {g: Counter() for g in self.groups} if self.groups else {}
        )

    def _key(self, req: Request) -> tuple:
        if self.groups is None:
            return req.bucket
        if req.group not in self.occupancy_by_group:
            raise ValueError(
                f"request group {req.group!r} not in batcher groups "
                f"{self.groups}"
            )
        return (req.group, req.bucket)

    def _bucket_of(self, key: tuple):
        """The Predictor bucket inside a queue key (grouped keys are
        (group, bucket))."""
        return key[1] if self.groups is not None else key

    def put(self, req: Request) -> None:
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            key = self._key(req)
            self._pending.setdefault(key, deque()).append(req)
            if req.priority > self._maxp.get(key, 0):
                self._maxp[key] = req.priority
            # grouped mode has one consumer PER group parked on the
            # shared condition: notify_all so the right one wakes
            # (notify() could wake a consumer whose group got nothing)
            if self.groups is None:
                self._cond.notify()
            else:
                self._cond.notify_all()

    def close(self) -> None:
        """Stop accepting; pending requests still drain via next_batch."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def _pop(self, key: tuple, n: int) -> Tuple[tuple, List[Request]]:
        bucket = self._bucket_of(key)
        dq = self._pending[key]
        n = min(n, len(dq))
        if self._maxp.get(key, 0):
            # class-weighted pop: release the n highest-priority
            # requests (FIFO within a class). Queues stay arrival-
            # ordered — put() is O(1) and rule 1's oldest-request
            # deadline scan keeps reading dq[0] — so priority is a
            # pop-side SELECTION, not an insertion order. The OLDEST
            # request (dq[0]) always rides: rule 1's max_wait flush
            # fires on ITS age, and leaving it behind for heavier
            # classes would starve low classes indefinitely — priority
            # reorders who ELSE fills the batch, never whether the
            # contractual-maximum waiter finally goes.
            picked = sorted(
                range(1, len(dq)),
                key=lambda i: (-dq[i].priority, dq[i].t_submit),
            )[:n - 1]
            picked_set = {0, *picked}
            out = [dq[i] for i in sorted(picked_set)]
            rest = [r for i, r in enumerate(dq) if i not in picked_set]
            dq.clear()
            dq.extend(rest)
        else:
            out = [dq.popleft() for _ in range(n)]
        if not dq:
            del self._pending[key]
            self._maxp.pop(key, None)
        else:
            if self._maxp.get(key, 0):
                # leftover scan only during priority traffic (the
                # default path never enters this branch)
                mp = max(r.priority for r in dq)
                if mp > 0:
                    self._maxp[key] = mp
                else:
                    self._maxp.pop(key, None)
            # rotate a bucket that released but still holds requests to the
            # back of the scan order: a sustained-load bucket must not
            # monopolize rule 2's full-bucket scan while siblings queue
            self._pending.move_to_end(key)
        self.occupancy[len(out)] += 1
        if self.groups is not None:
            self.occupancy_by_group[key[0]][len(out)] += 1
        batch = next(self._batch_ids)
        for r in out:
            r.batch = batch
        if obs.tracing_enabled():
            # queue wait = submit -> release, per request: the window was
            # stamped at submit, so it is recorded retroactively here.
            # Guarded: this runs on the consumer thread OUTSIDE the
            # engine's isolation try blocks — telemetry must never kill
            # the thread that forms batches.
            try:
                now = time.perf_counter()
                for r in out:
                    obs.add_span("serve.queue_wait", r.t_submit, now,
                                 trace_id=r.trace_id or None,
                                 bucket=str(bucket), batch=batch)
            except Exception:
                pass
        return bucket, out

    def next_batch(self, group: Any = None
                   ) -> Optional[Tuple[tuple, List[Request]]]:
        """Block until a batch is due and return ``(bucket, requests)``.

        Grouped mode: each replica group's consumer thread passes its
        ``group`` and sees only that group's queues — the scan/wait
        logic below is per group, so one saturated group never blocks a
        sibling's consumer. Ungrouped (``group=None``, the default
        single-pipeline engine): exactly the original discipline."""
        if (group is None) != (self.groups is None):
            raise ValueError(
                "grouped batchers need next_batch(group=...); ungrouped "
                "ones take none"
            )
        with self._cond:
            while True:
                # 1. an EXPIRED latency deadline releases first — the
                # max_wait_ms bound holds even while a sibling bucket is
                # kept full by sustained load (full buckets can wait one
                # round; an expired lone request has already waited its
                # contractual maximum)
                now = time.perf_counter()
                deadline = None
                due = None
                for key, dq in self._pending.items():
                    if group is not None and key[0] != group:
                        continue
                    t = dq[0].t_submit + self.max_wait_s
                    if deadline is None or t < deadline:
                        deadline, due = t, key
                if deadline is not None and now >= deadline:
                    return self._pop(
                        due,
                        max(1, int(self.bound_for(self._bucket_of(due)))),
                    )
                # 2. any full bucket releases immediately. With a class
                # weighting, the full bucket holding the heaviest-class
                # request wins the slot (ties keep first-use order,
                # rotated by _pop so equals take turns); priority can
                # only reorder WHICH full bucket goes first — rule 1's
                # expired-deadline preemption still bounds every
                # class's wait at max_wait_ms, so no bucket starves.
                best = None
                best_bound = 0
                best_w = 0.0
                for key, dq in self._pending.items():
                    if group is not None and key[0] != group:
                        continue
                    bound = max(
                        1, int(self.bound_for(self._bucket_of(key)))
                    )
                    if len(dq) < bound:
                        continue
                    if self.class_weight is None:
                        return self._pop(key, bound)
                    # O(1) per bucket via the tracked per-bucket max
                    # priority (weights are monotone in class, default
                    # ladder included) — never O(backlog) per release
                    w = self.class_weight(self._maxp.get(key, 0))
                    if best is None or w > best_w:
                        best, best_bound, best_w = key, bound, w
                if best is not None:
                    return self._pop(best, best_bound)
                if self._closed:
                    # drain: flush partial buckets oldest-first
                    for key in self._pending:
                        if group is not None and key[0] != group:
                            continue
                        return self._pop(
                            key,
                            max(1, int(
                                self.bound_for(self._bucket_of(key))
                            )),
                        )
                    return None
                # 3. else sleep until the earliest deadline (or new work)
                self._cond.wait(
                    timeout=None if deadline is None else deadline - now
                )

    def pending(self) -> int:
        with self._cond:
            return sum(len(d) for d in self._pending.values())

    def depth_snapshot(self) -> Dict[tuple, int]:
        """Per-bucket queue depths right now — the health report's
        queue evidence (``ServeEngine.health()``). Grouped batchers
        merge groups per bucket here; :meth:`depth_by_group` carries
        the per-replica-group split."""
        with self._cond:
            out: Dict[tuple, int] = {}
            for key, dq in self._pending.items():
                bucket = self._bucket_of(key)
                out[bucket] = out.get(bucket, 0) + len(dq)
            return out

    def depth_by_group(self) -> Dict[Any, Dict[str, Any]]:
        """Per-replica-group queue depths: ``{group: {"pending": n,
        "per_bucket": {bucket: n}}}`` — the evidence HealthWatch's
        per-group ``queue_saturation`` detector consumes. Empty when
        ungrouped."""
        if self.groups is None:
            return {}
        with self._cond:
            out: Dict[Any, Dict[str, Any]] = {
                g: {"pending": 0, "per_bucket": {}} for g in self.groups
            }
            for (g, bucket), dq in self._pending.items():
                rec = out[g]
                rec["pending"] += len(dq)
                rec["per_bucket"][bucket] = (
                    rec["per_bucket"].get(bucket, 0) + len(dq)
                )
            return out

    def occupancy_snapshot(self, group: Any = None) -> Dict[int, int]:
        with self._cond:
            if group is not None:
                return dict(self.occupancy_by_group.get(group, {}))
            return dict(self.occupancy)

"""ServeEngine: throughput-oriented serving on top of Predictor's bucketed
jitted programs.

Pipeline (one thread per stage, bounded queues between them):

    submit() -> [result cache / in-flight coalescing / feature routing]
        -> MicroBatcher (dynamic micro-batching under max_wait_ms)
        -> staging thread (pad + stack + device_put, round-robin devices,
           depth-2 queue = double-buffered prefetch)
        -> dispatch thread (the bucket's jitted program; async dispatch)
        -> completion thread (one fetch per batch, unpad, resolve futures,
           populate caches)

Contracts:

- **Exactness**: a request served through the fused batched path returns
  detections bitwise-identical to ``Predictor.__call__`` /
  ``predict_multi_exemplar`` on the same inputs — padded slots are
  dropped, real rows are untouched (tests/test_serve.py pins this across
  bucket boundaries). The feature-cached path (``_get_heads_fn``) recompiles
  the tail as its own XLA program and may differ at the last ULP; cold
  traffic never takes it (promotion starts at an image's second sighting).
- **Isolation**: a request that cannot be served fails only its own
  future. Malformed requests are rejected at submit; a batch-level failure
  falls back to per-request execution so one poison request cannot sink
  its batch-mates.
- **Measured defaults**: the batch bound defaults to the measured
  throughput-optimal batch persisted by bench_extra's sweep
  (utils/autotune.measured_bench_batch), then ``TMR_SERVE_BATCH``/the
  constructor argument override it.
- **Observable**: every counter lives in a per-engine obs metrics
  registry (``stats()`` keeps its original shape; ``metrics_snapshot()``
  is the metrics_report/v1 view); the four batch stages (batch_assemble,
  stage, execute, postprocess) are always recorded once a batch as
  ``scope="batch"`` spans with the batch's id, rows and padded slots
  (``StagedBatch.record_stage``), and with ``TMR_TRACE=1`` each request's
  trace id follows it through spans for all seven pipeline stages
  (submit, queue_wait, batch_assemble, stage, execute, postprocess,
  resolve), each naming its batch — scripts/obs_probe.py is the measured
  proof.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from tmr_tpu import obs
from tmr_tpu.obs.metrics import MetricsRegistry
from tmr_tpu.serve.admission import (
    AdmissionController,
    RejectedError,
    class_weight_fn,
)
from tmr_tpu.serve.batcher import MicroBatcher, Request
from tmr_tpu.serve.caches import LRUCache, array_digest
from tmr_tpu.serve.degrade import DegradeController, downscale_image
from tmr_tpu.serve.meshplan import (
    MeshPlan,
    refuse_cached_subslice_tp,
    resolve_plan,
)
from tmr_tpu.serve.staging import DeviceStager, StagedBatch, _PAD_BOX

_DET_FIELDS = ("boxes", "scores", "refs", "valid")


def _det_fields(dets: dict) -> tuple:
    """The detection keys to copy host-side: the fixed four, plus the
    device decode tail's ``count`` vector when the program exported one
    (TMR_DECODE_TAIL=device) — dropping it would silently put every
    served request back on the full valid-mask scan the knob exists to
    eliminate (detections_to_numpy's prefix-slice fast path keys on it).
    """
    return _DET_FIELDS + (("count",) if "count" in dets else ())

#: the engine's counter names — the PR 3 ``counters`` dict keys, now
#: backed by the per-engine metrics registry as ``serve.<name>`` (the
#: ``stats()`` shape is unchanged; tests/test_obs.py pins it)
_COUNTER_NAMES = (
    "submitted", "completed", "errors", "rejected", "coalesced",
    "batches", "padded_slots", "batch_fallbacks", "heads_batches",
    "feature_fills",
)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class ServeEngine:
    """Batched, cached, multi-device request serving for one Predictor.

    Parameters
    ----------
    predictor: an initialized tmr_tpu.inference.Predictor (params loaded).
    batch: per-bucket coalescing bound. None resolves, in order:
        ``TMR_SERVE_BATCH`` env -> the measured bench_extra batch-sweep
        winner for this (device kind, image size) -> 4.
    max_wait_ms: latency bound a lone request waits for batch-mates
        (None -> ``TMR_SERVE_MAX_WAIT_MS``, default 10).
    devices: explicit device list for round-robin data-parallel dispatch.
        None -> all local devices on TPU; the first device elsewhere
        (virtual CPU devices share host threads — round-robin over them
        buys compilations, not throughput).
    exemplar_cache / feature_cache: LRU capacities (None -> env knobs
        ``TMR_SERVE_EXEMPLAR_CACHE`` (default 256) /
        ``TMR_SERVE_FEATURE_CACHE`` (default 8); 0 disables).
    donate: donate staged image buffers to the program (None -> only on
        backends that implement donation: tpu/gpu).
    feature_client: optional disaggregated match-tier mode
        (serve/feature_tier.py): an object with ``holds(size)`` and
        ``fetch(image, digest, size)``. When set, single-exemplar
        requests whose size partition has a live feature worker route
        through the heads-only programs on REMOTELY extracted features
        (the documented heads-path ULP exception); frames with no
        holder, and rows whose fetch fails mid-flight, fall back to
        local execution — counted (``feature_tier.cold_frames`` /
        ``feature_tier.fallback_frames``), never silent, and their futures
        always resolve.
    """

    def __init__(self, predictor, *, batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 devices: Optional[Sequence[Any]] = None,
                 exemplar_cache: Optional[int] = None,
                 feature_cache: Optional[int] = None,
                 donate: Optional[bool] = None,
                 admission: Optional[AdmissionController] = None,
                 degrade: Optional[DegradeController] = None,
                 watch: Optional[Any] = None,
                 mesh: Optional[str] = None,
                 warmup_buckets: Optional[Sequence[tuple]] = None,
                 aot: Optional[bool] = None,
                 feature_client: Optional[Any] = None):
        import jax

        if predictor.params is None:
            raise RuntimeError("predictor has no params loaded")
        self._pred = predictor
        self._explicit_batch = batch
        self.max_wait_ms = (
            _env_float("TMR_SERVE_MAX_WAIT_MS", 10.0)
            if max_wait_ms is None else float(max_wait_ms)
        )
        backend = jax.default_backend()
        #: the mesh execution plan (serve/meshplan.py): mesh= argument >
        #: TMR_SERVE_MESH env > None = the unsharded round-robin engine
        #: (byte-identical to pre-mesh behavior, every new code path off)
        self._plan: Optional[MeshPlan] = resolve_plan(
            mesh, devices=devices if devices is not None
            else jax.local_devices(),
        )
        if self._plan is not None:
            self._validate_plan_tp()
            refuse_cached_subslice_tp(self._plan)
            devices = [d for t in self._plan.group_targets
                       for d in t.devices]
        elif devices is None:
            local = jax.local_devices()
            # accelerators round-robin across every local device; only the
            # CPU backend pins to one (virtual host "devices" share the
            # same threads — round-robin there buys compiles, not speed)
            devices = local if backend in ("tpu", "gpu") else local[:1]
        self.devices = list(devices)
        self.donate = (
            backend in ("tpu", "gpu") if donate is None else bool(donate)
        )
        #: per-engine metrics registry: every counter the engine (and its
        #: caches) keeps, snapshot()-able as one metrics_report/v1 — each
        #: engine gets its own so concurrent engines never cross-count
        self.metrics = MetricsRegistry()
        self.result_cache = LRUCache(
            _env_int("TMR_SERVE_EXEMPLAR_CACHE", 256)
            if exemplar_cache is None else exemplar_cache,
            registry=self.metrics, name="serve.cache.result",
        )
        # optional HBM-residency bound on the device feature cache
        # (TMR_SERVE_FEATURE_CACHE_MB): gallery/large-frame workloads
        # can blow memory through a count-only bound — when set, inserts
        # evict by tracked bytes too and stats() reports `bytes`
        feat_mb = _env_float("TMR_SERVE_FEATURE_CACHE_MB", 0.0)
        self.feature_cache = LRUCache(
            _env_int("TMR_SERVE_FEATURE_CACHE", 8)
            if feature_cache is None else feature_cache,
            registry=self.metrics, name="serve.cache.feature",
            max_bytes=int(feat_mb * (1 << 20)) if feat_mb > 0 else None,
        )
        # image digests seen once: the second sighting promotes the image
        # into the feature cache (cold traffic stays on the bitwise-exact
        # fused path; hot images amortize one split-path fill)
        self._seen = LRUCache(max(4 * self.feature_cache.capacity, 16))
        #: disaggregated match-tier mode (serve/feature_tier.py) —
        #: None keeps every routing decision byte-identical to before
        self._feature_client = feature_client
        #: optional pattern-search backend: a GalleryBank
        #: (serve/gallery.py) or a replicated-fleet front door
        #: (serve/gallery_fleet.py GalleryFleetClient). None — the
        #: default — keeps the engine byte-identical to before;
        #: ``attach_gallery`` arms ``search_gallery``.
        self._gallery: Optional[Any] = None
        #: feature-cache key provenance: (params digest, backbone
        #: formulation) — a checkpoint/knob swap can never serve stale
        #: features (predictors without the stamp key on image alone,
        #: the pre-PR-16 behavior)
        fstamp = getattr(predictor, "feature_stamp", None)
        self._feat_stamp = tuple(fstamp()) if callable(fstamp) else ()

        self._batch_bounds: Dict[int, int] = {}
        self._lock = threading.Lock()
        self._inflight: Dict[tuple, Request] = {}
        self._closed = False
        self._t_start = time.time()
        #: anomaly detector fed by health() passes (obs/flight.py);
        #: default thresholds — probes inject their own HealthWatch
        #: (``watch=``) when they need deterministic ones
        self._watch = obs.HealthWatch() if watch is None else watch
        #: continuous-autotune shadow tuner (tmr_tpu/autotune_live.py),
        #: attached only under TMR_LIVE_TUNE=1 — None (the default)
        #: keeps serving bitwise-identical: the hot path pays one
        #: ``is None`` check per completed batch
        self._tuner: Optional[Any] = None
        #: bounded admission (TMR_ADMIT* knobs; default disabled = the
        #: PR 3 unbounded behavior) and the adaptive degrade ladder
        #: (TMR_DEGRADE; default off). Probes pass their own controllers.
        self._admission = AdmissionController() if admission is None \
            else admission
        self._degrade = DegradeController() if degrade is None else degrade
        #: default per-request deadline (TMR_SERVE_DEADLINE_MS; 0/unset
        #: = none) — submit(deadline_ms=...) overrides per request
        self._default_deadline_ms = _env_float("TMR_SERVE_DEADLINE_MS", 0.0)
        #: close() drain bound (TMR_SERVE_DRAIN_TIMEOUT_S): past it,
        #: leftover futures resolve with a structured shutdown
        #: rejection instead of hanging their callers
        self._drain_timeout_s = _env_float("TMR_SERVE_DRAIN_TIMEOUT_S",
                                           300.0)
        self._drain_timed_out = False
        #: overload counters (admission rejections, per-stage sheds,
        #: degrade steps), created LAZILY on first event so the
        #: default-off metrics/stats shapes stay byte-identical to PR 3
        self._mx: Dict[str, Any] = {}
        # detection windows start NOW: compile events a warm process
        # paid before this engine existed (autotune sweeps, a prior
        # engine) must not fire a spurious storm on the first health()
        # pass. A monotonic sequence cursor, not a list offset — the
        # bounded event log trims and other harnesses drain it.
        self._compile_seen = obs.compile_event_seq()
        self._heartbeat = None
        self._m = {
            name: self.metrics.counter(f"serve.{name}")
            for name in _COUNTER_NAMES
        }
        self._lat = self.metrics.histogram("serve.request_latency_s")
        self._per_device: Dict[str, int] = {}

        #: per-replica-group completion-timestamp windows: the measured
        #: drain rate per group (requests/s), summed into the admission
        #: controller's capacity signal — the retry_after hint then
        #: reflects the real multi-chip drain instead of the
        #: single-pipeline release window
        self._drain_lock = threading.Lock()
        self._drain: Dict[str, Any] = {}
        self._group_rr = 0
        #: AOT warmup accounting (stats()/health() expose it when run)
        self._warmup_stats: Optional[Dict[str, Any]] = None

        #: quant provenance stamp (mode + storage + tree digest), set
        #: when the predictor runs int8 numerics or stored-int8 trees —
        #: rides stats()/health() and the serve_report/v1 attachment so
        #: a served result's numerics tier is always attributable
        #: (the degrade_steps pattern applied to quantization). None
        #: (fully exact) adds no key: the default-off stats()/health()
        #: shapes stay byte-identical.
        stamp = getattr(predictor, "quant_stamp", None)
        self._quant_stamp = stamp() if callable(stamp) else None

        groups = self._plan.group_ids() if self._plan else None
        self._batcher = MicroBatcher(self.max_wait_ms, self._bound_for,
                                     class_weight=class_weight_fn(),
                                     groups=groups)
        # the stager stages the tree the compiled programs consume: the
        # stored int8 tree under TMR_QUANT_STORAGE (weight H2D + HBM
        # bytes genuinely drop 4x for the quantized leaves), else the
        # f32 params unchanged
        exec_params = getattr(predictor, "exec_params", None)
        self._stager = DeviceStager(
            self.devices,
            exec_params() if callable(exec_params) else predictor.params,
            predictor.refiner_params,
        )
        if self._plan is None:
            self._staged_q: "queue.Queue" = queue.Queue(maxsize=2)
            self._done_q: "queue.Queue" = queue.Queue(maxsize=2)
            self._threads = [
                threading.Thread(target=self._stage_loop,
                                 name="serve-stage", daemon=True),
                threading.Thread(target=self._dispatch_loop,
                                 name="serve-dispatch", daemon=True),
                threading.Thread(target=self._complete_loop,
                                 name="serve-complete", daemon=True),
            ]
        else:
            # one stage + dispatch pipeline PER queue group (each
            # replica group and, when dp > 1, the full-mesh dp target),
            # all feeding one completion thread: every group's chips
            # stay busy concurrently — the per-replica-group queue
            # architecture of ROADMAP item 1
            self._group_staged: Dict[str, "queue.Queue"] = {
                g: queue.Queue(maxsize=2) for g in groups
            }
            self._done_q = queue.Queue(maxsize=max(2 * len(groups), 2))
            self._threads = []
            for g in groups:
                self._threads.append(threading.Thread(
                    target=self._stage_loop, args=(g,),
                    name=f"serve-stage-{g}", daemon=True,
                ))
                self._threads.append(threading.Thread(
                    target=self._dispatch_loop, args=(g,),
                    name=f"serve-dispatch-{g}", daemon=True,
                ))
            self._threads.append(threading.Thread(
                target=self._complete_loop, args=(len(groups),),
                name="serve-complete", daemon=True,
            ))
        self._aot_warmup(warmup_buckets, aot)
        for t in self._threads:
            t.start()
        if self._plan is not None:
            self._admission.attach_drain_source(self._drain_total)

    # -------------------------------------------------------------- gallery
    def attach_gallery(self, gallery: Any) -> None:
        """Arm ``search_gallery`` with a pattern-search backend — any
        object with the bank surface (``search(image) -> {name:
        dets}``): a local :class:`~tmr_tpu.serve.gallery.GalleryBank`
        or a replicated fleet's
        :class:`~tmr_tpu.serve.gallery_fleet.GalleryFleetClient`.
        Detached (the default) nothing in the engine changes."""
        with self._lock:
            self._gallery = gallery

    # ------------------------------------------------------ live autotune
    def attach_live_tuner(self, tuner: Any) -> bool:
        """Arm continuous autotune: completed batches are OFFERED to the
        tuner (a sampling decision + bounded non-blocking enqueue; the
        shadow execution runs on the tuner's own thread), and the
        engine's health watch feeds it anomalies for demotion
        (``HealthWatch.add_listener``). Refuses (returns False) unless
        ``TMR_LIVE_TUNE=1`` — the default-off pin: a detached engine is
        bitwise-identical to one that never heard of live tuning."""
        from tmr_tpu import autotune_live

        if not autotune_live.live_tune_enabled():
            return False
        with self._lock:
            self._tuner = tuner
        self._watch.add_listener(tuner.observe_anomalies)
        tuner.start()
        return True

    def search_gallery(self, image, **kw) -> Dict[str, dict]:
        """Match every registered pattern against one frame through
        the attached backend. Degrade labeling is the backend's
        contract (``degrade_steps: ["partition_unavailable"]`` on
        fleet partitions that are dead mid-search); the counter is
        created lazily so default-off metrics shapes are unchanged."""
        with self._lock:
            gallery = self._gallery
        if gallery is None:
            raise RuntimeError(
                "no gallery attached (ServeEngine.attach_gallery)"
            )
        self.metrics.counter("serve.gallery.searches").inc()
        return gallery.search(image, **kw)

    # -------------------------------------------------------------- sizing
    def _bound_device(self, bucket: tuple) -> int:
        """PER-DEVICE coalescing bound for a bucket: explicit arg >
        TMR_SERVE_BATCH > measured bench_extra winner for this image
        size > 4.

        ``_batch_bounds`` is touched under ``self._lock``: this runs on
        the batcher's consumer thread while ``stats()`` iterates the
        dict from caller threads — an unlocked insert could blow up that
        iteration mid-walk (the lock-discipline analysis finding this
        method used to be). The resolve itself happens outside the lock;
        it is idempotent, so a racing double-resolve is benign."""
        size = bucket[1]
        with self._lock:
            if size in self._batch_bounds:
                return self._batch_bounds[size]
        if self._explicit_batch is not None:
            bound = int(self._explicit_batch)
        else:
            bound = _env_int("TMR_SERVE_BATCH", 0)
            if bound <= 0:
                from tmr_tpu.utils.autotune import measured_bench_batch

                bound = measured_bench_batch(size) or 4
        bound = max(1, bound)
        with self._lock:
            self._batch_bounds[size] = bound
        return bound

    def _bound_for(self, bucket: tuple) -> int:
        """The batcher's release bound: the per-device bound, times the
        dp width for buckets the mesh plan fans out data-parallel (one
        dp dispatch feeds every replica group its measured per-device
        batch — releasing at the single-device bound would ship
        batches that leave dp-1 groups padding)."""
        bound = self._bound_device(bucket)
        if self._plan is not None and \
                self._plan.mode_for(bucket) == "dp":
            return bound * self._plan.dp
        return bound

    def _feature_key(self, digest: str, size: int) -> tuple:
        """The feature-cache key for one frame: image digest + size +
        the predictor's (params digest, backbone formulation) stamp, so
        reuse can never cross a checkpoint or formulation swap."""
        return (digest, size) + self._feat_stamp

    def _count(self, name: str, n: int = 1) -> None:
        """Lazily created overload counters (``serve.<name>``): the
        admission/shed/degrade tallies exist in the registry only once
        the first such event fires, so a default-knobs engine's
        metrics snapshot and stats() stay byte-identical to PR 3."""
        with self._lock:
            c = self._mx.get(name)
            if c is None:
                c = self._mx[name] = self.metrics.counter(f"serve.{name}")
        c.inc(n)

    # ---------------------------------------------------------------- mesh
    def _validate_plan_tp(self) -> None:
        """Refuse a tensor-parallel plan the backbone widths cannot
        shard evenly (the training-side validate_tp rule applied to the
        serving mesh) — a misfit must fail engine construction, not
        silently pad shards."""
        if self._plan.tp <= 1:
            return
        from tmr_tpu.parallel.sharding import validate_tp

        bb = self._pred.model.backbone
        embed_dim = getattr(bb, "embed_dim", None)
        num_heads = getattr(bb, "num_heads", None)
        if embed_dim and num_heads:
            validate_tp(self._plan.group_targets[0].mesh,
                        int(embed_dim), int(num_heads), axis="tp")

    def _assign_group(self, bucket: tuple) -> str:
        """The replica-group queue a request joins: dp-mode buckets go
        to the full-mesh queue; group-mode buckets round-robin across
        replica groups (each group has its own pipeline, so successive
        batches execute concurrently)."""
        plan = self._plan
        if plan.mode_for(bucket) == "dp":
            return plan.dp_target.name
        with self._lock:
            i = self._group_rr
            self._group_rr = (i + 1) % len(plan.group_targets)
        return plan.group_targets[i].name

    def _record_drain(self, group: Optional[str], n: int = 1) -> None:
        """Completion timestamps per replica group (bounded windows) —
        the measured drain-rate evidence."""
        from collections import deque

        g = group or "default"
        now = time.monotonic()
        with self._drain_lock:
            win = self._drain.get(g)
            if win is None:
                win = self._drain[g] = deque(maxlen=128)
            for _ in range(max(int(n), 1)):
                win.append(now)

    #: a drain window whose NEWEST completion is older than this reads
    #: as rate 0.0: an idle group must not keep advertising its historic
    #: rate forever, or the admission controller's retry_after hints
    #: would be computed from capacity that no longer drains anything —
    #: a zero from a stale source makes the controller fall back to its
    #: own release-window estimate (the documented PR 12 fallback, now
    #: pinned by tests/test_overload.py)
    _DRAIN_STALE_S = 60.0

    def drain_snapshot(self) -> Dict[str, float]:
        """Measured per-replica-group drain rate (requests/s over each
        group's recent completion window; 0.0 once the window goes
        stale — see ``_DRAIN_STALE_S``)."""
        out: Dict[str, float] = {}
        now = time.monotonic()
        with self._drain_lock:
            for g, win in self._drain.items():
                if len(win) < 2 or now - win[-1] > self._DRAIN_STALE_S:
                    out[g] = 0.0
                    continue
                span = win[-1] - win[0]
                out[g] = (len(win) - 1) / span if span > 0 else 0.0
        return out

    def _drain_total(self) -> float:
        """Summed per-group drain rate — the AdmissionController's
        capacity signal under a mesh plan (admission.attach_drain_source
        wires it at engine start)."""
        return sum(self.drain_snapshot().values())

    # ---------------------------------------------------------- AOT warmup
    def _aot_warmup(self, warmup_buckets, aot) -> None:
        """Ahead-of-time compilation + warmup of the bucketed program
        set at engine start: every (bucket, padded-shape, mesh-target)
        program the declared buckets can reach executes ONCE on zero
        inputs before the engine serves traffic. The first execution is
        where jit traces + XLA compiles, so each program's compile event
        records HERE (through PR 8's track_compile, visible to the
        compile-event cursor) and steady-state serving never eats a
        cold-compile cliff — scripts/serve_bench.py pins zero cold
        events after warmup.

        Enablement: ``aot`` argument > ``TMR_SERVE_AOT`` env > on when
        a mesh plan or an explicit ``warmup_buckets`` list is present.
        The bucket set is ``warmup_buckets`` (Predictor.bucket_key
        tuples) or one derived default (the config image size at the
        smallest template bucket). ``TMR_SERVE_WARMUP_TIMEOUT_S``
        bounds the whole pass — past it remaining programs are skipped
        (counted) and compile lazily like before."""
        if aot is None:
            flag = os.environ.get("TMR_SERVE_AOT", "")
            if flag in ("0", "false", "off"):
                return
            if not flag and self._plan is None and not warmup_buckets:
                return
        elif not aot:
            return
        buckets = list(warmup_buckets or ())
        if not buckets:
            cfg = self._pred.cfg
            buckets = [("single", int(cfg.image_size),
                        int(cfg.template_buckets[0]), 1)]
        timeout_s = _env_float("TMR_SERVE_WARMUP_TIMEOUT_S", 600.0)
        t0 = time.perf_counter()
        stats = {"programs": 0, "skipped": 0,
                 "timeout_s": timeout_s, "wall_s": 0.0}
        for bucket in buckets:
            if bucket[0] == "heads":
                # the heads path warms through its fill traffic; it
                # must not inflate the warmed-program count either
                continue
            for target in self._warmup_targets(bucket):
                for shape in self._warmup_shapes(bucket, target):
                    if time.perf_counter() - t0 > timeout_s:
                        stats["skipped"] += 1
                        continue
                    try:
                        self._warmup_one(bucket, target, shape)
                        stats["programs"] += 1
                    except Exception:
                        # warmup is an optimization: a bucket that
                        # cannot warm (unsupported shape) compiles
                        # lazily on first real traffic instead
                        stats["skipped"] += 1
        stats["wall_s"] = round(time.perf_counter() - t0, 3)
        self._warmup_stats = stats

    def _warmup_targets(self, bucket: tuple) -> List[Any]:
        if self._plan is None:
            return [None]
        if self._plan.mode_for(bucket) == "dp":
            return [self._plan.dp_target]
        return list(self._plan.group_targets)

    def _warmup_shapes(self, bucket: tuple, target) -> List[int]:
        """The padded batch shapes this bucket's traffic can produce on
        ``target``: the power-of-two sub-bucket ladder up to the bound
        (times dp for the fan-out target) — exactly the shapes
        staging._pad_to emits, so no real batch meets an uncompiled
        shape."""
        bound = self._bound_device(bucket)
        ladder = []
        s = 1
        while s < bound:
            ladder.append(s)
            s *= 2
        ladder.append(bound)
        mult = target.dp if (target is not None and target.mode == "dp") \
            else 1
        return sorted({x * mult for x in ladder})

    def _warmup_one(self, bucket: tuple, target, shape: int) -> None:
        """Build + execute one (bucket, target, padded-shape) program on
        zero inputs, blocking until outputs are ready."""
        import jax
        import numpy as np_  # shadow-proof alias (np is module-level)

        kind, size, cap, k = bucket
        images = np_.zeros((shape, size, size, 3), np_.float32)
        exemplars = np_.tile(
            np_.asarray(_PAD_BOX, np_.float32), (shape, k, 1)
        )
        if target is None:
            device = self._stager.next_device()
            params, rparams = self._stager.params_for(device)
            placement = device
        else:
            params, rparams = self._run_params(target, kind)
            placement = self._stager.batch_sharding(target)
        img_d = jax.device_put(images, placement)
        ex_d = jax.device_put(exemplars, placement)
        if kind == "multi":
            k_real = jax.device_put(
                np_.ones((shape,), np_.int32), placement
            )
            fn = self._program_for(("multi", size, cap, k), target)
            out = fn(params, rparams, img_d, ex_d, k_real)
        else:
            fn = self._program_for(("single", size, cap, k), target)
            out = fn(params, rparams, img_d, ex_d)
        jax.block_until_ready(out)

    # -------------------------------------------------------------- submit
    def submit(self, image, exemplars, multi: bool = False,
               k_real: Optional[int] = None,
               priority: int = 0,
               deadline_ms: Optional[float] = None,
               features: Optional[Any] = None) -> Future:
        """Enqueue one request; returns a Future resolving to the
        fixed-slot detections dict (numpy, leading dim 1 — treat as
        read-only, results may be shared with the cache).

        ``priority`` is the request's class (higher = scheduled sooner
        under the class weighting; admission bounds apply per class).
        ``deadline_ms`` bounds the request's useful lifetime from this
        call: a request still unserved past it is SHED by the next
        pipeline stage (its future raises RejectedError cause
        "deadline") instead of burning device time on an answer nobody
        is waiting for. None -> ``TMR_SERVE_DEADLINE_MS`` (unset = no
        deadline, the PR 3 behavior). Identical concurrent requests
        coalesce into ONE group that inherits the EARLIEST deadline of
        its riders — a shed therefore fails every rider together, a
        deadline-free rider included (one execution, one fate; a rider
        that must not expire should not share a deadline-bearing
        group's exact inputs mid-flight).

        ``features`` is the stream-session reuse hook
        (serve/streams.py): a precomputed (1, h, w, C) backbone feature
        map for THIS frame. The request then skips the encoder entirely
        (heads-only program) and its result — cache entry included —
        carries ``degrade_steps: ["temporal_reuse"]`` under its own
        result-cache key, so a reused answer can never be served to a
        frame-independent query.

        A request that cannot be served (bad shapes, an exemplar needing a
        template bucket beyond cfg.template_buckets, ...) fails only its
        own future; a request the admission controller bounces fails with
        a structured :class:`RejectedError` (cause, class, retry-after)."""
        fut: Future = Future()
        if self._closed:
            fut.set_exception(RuntimeError("engine is closed"))
            return fut
        rej = self._admission.try_admit(priority)
        if rej is not None:
            self._count("admit_rejected")
            self._count(f"admit_rejected.{rej.cause}")
            fut.set_exception(rej)
            return fut
        # one trace id per request, minted here and carried through every
        # pipeline stage's span (queue wait, staging, execute, resolve)
        tid = obs.new_trace_id() if obs.tracing_enabled() else ""
        with obs.span("serve.submit", trace_id=tid or None):
            try:
                req = self._make_request(image, exemplars, multi, k_real,
                                         fut, tid, priority, deadline_ms,
                                         features)
            except Exception as e:  # isolation: reject this request alone
                self._admission.release_class(priority)
                self._m["rejected"].inc()
                fut.set_exception(e)
                return fut
            if req is None:  # resolved from cache / coalesced: the slot
                self._admission.release_class(priority)  # frees now
                return fut
            req.admitted = self._admission.enabled
            if self._plan is not None:
                req.group = self._assign_group(req.bucket)
            try:
                self._batcher.put(req)
            except Exception as e:  # closed mid-submit: a rejection, not
                self._drop_inflight(req)  # traffic
                self._admission.release(req)
                self._m["rejected"].inc()
                fut.set_exception(e)
                return fut
            self._m["submitted"].inc()
        return fut

    def predict(self, image, exemplars, **kw) -> dict:
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(image, exemplars, **kw).result()

    def _make_request(self, image, exemplars, multi, k_real,
                      fut, trace_id: str = "", priority: int = 0,
                      deadline_ms: Optional[float] = None,
                      features: Optional[Any] = None
                      ) -> Optional[Request]:
        image = np.asarray(image, np.float32)
        if image.ndim == 4 and image.shape[0] == 1:
            image = image[0]
        if image.ndim != 3 or image.shape[0] != image.shape[1] \
                or image.shape[2] != 3:
            raise ValueError(
                f"expected one square (S, S, 3) image, got {image.shape}"
            )
        ex = np.asarray(exemplars, np.float32).reshape(-1, 4)
        size = int(image.shape[0])
        k = int(k_real) if k_real is not None else len(ex)
        if not 1 <= k <= len(ex):
            raise ValueError(
                f"k_real={k} out of range for {len(ex)} exemplar rows"
            )
        # ---- adaptive degradation (serve/degrade.py; default OFF = the
        # bitwise PR 3 path). Steps apply BEFORE the bucket/digest are
        # computed, so the result-cache key describes exactly what ran —
        # a degraded result can never be served to an undegraded query.
        steps = self._degrade.active_steps()
        applied = []
        if features is not None:
            if multi:
                raise ValueError(
                    "features= (temporal reuse) supports single-exemplar "
                    "requests only"
                )
            # temporal reuse (serve/streams.py): keyed + counted like a
            # degrade step BEFORE the cache lookup, so a reused result
            # lives under its own cache/coalesce namespace and can never
            # be served to a frame-independent query
            applied.append("temporal_reuse")
        if "downscale" in steps and size // 2 >= self._degrade.min_size:
            image = downscale_image(image)
            size = int(image.shape[0])
            applied.append("downscale")
        if "truncate_k" in steps and multi and k > 1:
            k = 1
            k_real = 1
            applied.append("truncate_k")
        bucket = self._pred.bucket_key(size, ex[:k] if multi else ex,
                                       multi=multi, k_real=k_real)
        if multi:
            ex = ex[:k]
            k_bucket = bucket[3]
            ex = np.concatenate(
                [ex, np.tile(ex[-1:], (k_bucket - k, 1))], axis=0
            )
        digest = array_digest(image)
        result_key = (bucket, digest, array_digest(ex[:k] if multi else ex),
                      k if multi else None)
        if applied:
            # degraded traffic lives under its OWN cache/coalesce keys:
            # sharing the honest key would let a degraded query hit an
            # unlabeled honest result (silent degradation — the one
            # thing the ladder contract forbids) or an honest query a
            # degraded one. Counting happens HERE, before the lookup,
            # so a cache-hit serve of a degraded request is still an
            # exactly-accounted degraded serve.
            result_key = result_key + (tuple(applied),)
            self._count("degraded")
            for step in applied:
                self._count(f"degrade.{step}")

        cached = self.result_cache.get(result_key)
        if cached is not None:
            fut.set_result(cached)
            self._m["submitted"].inc()
            self._m["completed"].inc()
            return None

        deadline_ms = (
            (self._default_deadline_ms or None)
            if deadline_ms is None else float(deadline_ms)
        )
        req = Request(image=image, exemplars=ex, bucket=bucket,
                      futures=[fut], k_real=k, image_digest=digest,
                      result_key=result_key, trace_id=trace_id,
                      priority=max(int(priority), 0))
        if deadline_ms is not None:
            req.deadline = req.t_submit + deadline_ms / 1000.0
        if features is not None:
            # stream-session reuse: the caller supplies this frame's
            # features — the request skips the encoder outright
            req.features = np.asarray(features) if not hasattr(
                features, "dtype"
            ) else features
            req.bucket = ("heads",) + bucket[1:]
        elif not multi and (self.feature_cache.capacity > 0
                            or self._feature_client is not None):
            feat = (self.feature_cache.get(self._feature_key(digest, size))
                    if self.feature_cache.capacity > 0 else None)
            if feat is not None:
                req.features = feat
                req.bucket = ("heads",) + bucket[1:]
            elif self._feature_client is not None \
                    and self._feature_client.holds(size):
                # disaggregated match tier: a live feature worker holds
                # this size's partition — route heads-only, the fetch
                # happens batch-side (_run_heads)
                req.needs_features = True
                req.bucket = ("heads",) + bucket[1:]
            elif self._feature_client is not None:
                # no holder for the partition: this cold frame stays on
                # the local fused path — counted, never silent (the
                # feature-tier fallback contract)
                self._count("feature_tier.cold_frames")
                if self.feature_cache.capacity > 0:
                    self._seen.put((digest, size), True)
            elif (digest, size) in self._seen:
                req.needs_features = True
                req.bucket = ("heads",) + bucket[1:]
            elif "prefer_heads" in steps:
                # degrade: promote on FIRST sighting — repeats reach the
                # cached heads-only program one round-trip earlier. This
                # is a ROUTING step (the heads-path ULP exception the
                # engine already documents for second sightings), so it
                # stays out of the result key; the stored result's
                # degrade_steps is its provenance either way.
                req.needs_features = True
                req.bucket = ("heads",) + bucket[1:]
                applied.append("prefer_heads")
                if len(applied) == 1:  # not already counted pre-lookup
                    self._count("degraded")
                self._count("degrade.prefer_heads")
            else:
                self._seen.put((digest, size), True)
        if applied:
            req.degrade_steps = tuple(applied)
        # lookup + registration under ONE lock hold: a second identical
        # submit racing this one must either see our registration or win
        # the slot itself — split critical sections would let both execute
        # and silently defeat the dedup (TOCTOU)
        with self._lock:
            live = self._inflight.get(result_key)
            if live is not None:
                live.futures.append(fut)
                # a coalesced group serves its MOST urgent rider: the
                # earliest deadline and the highest class win (the
                # group's single execution must satisfy every rider)
                if req.deadline is not None and (
                    live.deadline is None or req.deadline < live.deadline
                ):
                    live.deadline = req.deadline
                if req.priority > live.priority:
                    live.priority = req.priority
                self._m["submitted"].inc()
                self._m["coalesced"].inc()
                return None
            self._inflight[result_key] = req
        return req

    # ------------------------------------------------------------- threads
    def _shed_expired(self, requests: List[Request],
                      stage: str) -> List[Request]:
        """Drop already-expired requests from a batch before the next
        pipeline stage spends work on them: each sheds with a
        structured deadline rejection, counted per stage
        (``serve.shed.<stage>``). Returns the still-live remainder.
        The common no-deadline path is one generator pass."""
        if all(r.deadline is None for r in requests):
            return requests
        now = time.perf_counter()
        live = []
        for req in requests:
            if not req.expired(now):
                live.append(req)
                continue
            self._drop_inflight(req)
            self._admission.release(req)
            req.fail(RejectedError(
                "deadline",
                f"deadline expired before {stage} "
                f"(waited {(now - req.t_submit) * 1000:.1f} ms)",
                priority=req.priority,
            ))
            n = len(req.futures)
            self._count("shed", n)
            self._count(f"shed.{stage}", n)
        return live

    def _stage_loop(self, group: Optional[str] = None) -> None:
        staged_q = (self._staged_q if group is None
                    else self._group_staged[group])
        target = (None if group is None
                  else self._plan.target_by_group(group))
        while True:
            nb = self._batcher.next_batch(group=group)
            if nb is None:
                staged_q.put(None)
                return
            bucket, reqs = nb
            # deadline shed BEFORE staging: an expired request must
            # never reach device_put, let alone execute
            reqs = self._shed_expired(reqs, "stage")
            if not reqs:
                continue
            try:
                staged = self._stager.stage(
                    bucket, reqs, self._bound_device(bucket),
                    target=target,
                )
                self._m["batches"].inc()
                self._m["padded_slots"].inc(staged.padded_slots)
                with self._lock:
                    dev = str(staged.device)
                    self._per_device[dev] = self._per_device.get(dev, 0) + 1
                staged_q.put(staged)
            except Exception as e:
                self._isolate(reqs, e)

    def _dispatch_loop(self, group: Optional[str] = None) -> None:
        staged_q = (self._staged_q if group is None
                    else self._group_staged[group])
        while True:
            staged = staged_q.get()
            if staged is None:
                self._done_q.put(None)
                return
            # a batch whose EVERY rider expired while staged sheds here
            # and skips the program call entirely; a mixed batch still
            # runs (its rows are already staged — the expired riders
            # shed at postprocess instead of paying host fetch/copy)
            if staged.requests and all(
                r.deadline is not None and r.expired()
                for r in staged.requests
            ):
                self._shed_expired(staged.requests, "dispatch")
                continue
            try:
                t0 = time.perf_counter()
                out, fill_feats = self._run_batch(staged)
                staged.record_stage("serve.execute", t0,
                                    time.perf_counter())
                self._done_q.put((staged, out, fill_feats))
            except Exception as e:
                self._isolate(staged.requests, e, batch_level=True)

    def _complete_loop(self, sentinels: int = 1) -> None:
        """One shared completion thread; ``sentinels`` dispatch loops
        feed it (one per replica-group pipeline under a mesh plan) and
        it exits after seeing every loop's shutdown None."""
        remaining = max(int(sentinels), 1)
        while True:
            item = self._done_q.get()
            if item is None:
                remaining -= 1
                if remaining == 0:
                    return
                continue
            staged, out, fill_feats = item
            try:
                self._finish(staged, out, fill_feats)
            except Exception as e:
                self._isolate(staged.requests, e, batch_level=True)

    # ------------------------------------------------------------ dispatch
    def _program_for(self, bucket: tuple, target):
        """The compiled program one (bucket, target) executes: the
        unsharded fused program off-mesh and on plain (tp == 1) replica
        groups, the mesh-sharded variant on dp / tensor-parallel
        targets — every sharded ``_compiled`` key embeds the target's
        mesh shape + devices, so shape changes recompile instead of
        colliding."""
        kind, _size, cap, k = bucket
        sharded = target is not None and (
            target.mode == "dp" or target.tp > 1
        )
        if kind == "single":
            if sharded:
                return self._pred._get_sharded_fn(cap, target,
                                                  donate=self.donate)
            return self._pred._get_fn(cap, donate=self.donate)
        if kind == "multi":
            if sharded:
                return self._pred._get_sharded_multi_fn(
                    cap, k, target, donate=self.donate
                )
            return self._pred._get_multi_batched_fn(cap, k,
                                                    donate=self.donate)
        raise RuntimeError(f"unknown bucket kind {kind!r}")

    def _run_params(self, target, kind: str):
        """(params, refiner_params) placed for one target: heads
        buckets always run the unsharded tail on the group's primary
        device (tp-sharded params would silently GSPMD a program never
        audited that way); everything else takes the target placement
        the stager committed."""
        if kind == "heads" and target is not None:
            return self._stager.params_for(target.primary)
        return self._stager.params_for(target)

    def _run_batch(self, staged: StagedBatch):
        """Run the bucket's jitted program on the staged arrays. Returns
        (dets, fill_map) — fill_map is the heads path's dict of
        {fill row index: freshly obtained (1, h, w, C) feature row}
        (None elsewhere)."""
        kind, size, cap, k = staged.bucket
        target = staged.target
        params, rparams = (
            self._run_params(target, kind) if target is not None
            else self._stager.params_for(staged.device)
        )
        if kind == "heads":
            return self._run_heads(staged, params, rparams, size, cap)
        fn = self._program_for(staged.bucket, target)
        if kind == "single":
            return fn(params, rparams, staged.images, staged.exemplars), None
        return fn(params, rparams, staged.images, staged.exemplars,
                  staged.k_real), None

    def _run_heads(self, staged: StagedBatch, params, rparams, size, cap):
        import jax.numpy as jnp

        self._m["heads_batches"].inc()
        # fill_map: fill row index -> its freshly obtained (1, h, w, C)
        # feature row (remote fetch or local encode) — _finish caches
        # every entry under the stamped feature key
        fill_map: Dict[int, Any] = {}
        fill_local = list(staged.fill_index)
        if fill_local and self._feature_client is not None:
            # disaggregated match tier: fetch each fill row's features
            # from the remote feature worker; a row whose fetch fails
            # (dead worker, saturated window) drops to the LOCAL encode
            # below — counted, never silent, its future still resolves
            still: List[int] = []
            for i in fill_local:
                req = staged.requests[i]
                try:
                    feat = self._feature_client.fetch(
                        req.image, req.image_digest, size
                    )
                except Exception:
                    feat = None
                if feat is None:
                    still.append(i)
                    self._count("feature_tier.fallback_frames")
                else:
                    fill_map[i] = jnp.asarray(feat)
                    self._count("feature_tier.remote_frames")
            fill_local = still
        if fill_local:
            bb = self._pred._get_backbone_fn()
            fill_feats = bb(params, staged.images)
            self._m["feature_fills"].inc(len(fill_local))
            pos = {i: j for j, i in enumerate(staged.fill_index)}
            for i in fill_local:
                fill_map[i] = fill_feats[pos[i]:pos[i] + 1]
        rows: List[Any] = []
        for i in range(len(staged.requests)):
            row = fill_map.get(i)
            rows.append(staged.features[i] if row is None else row)
        bound = staged.exemplars.shape[0]
        pad = bound - len(rows)
        if pad:
            rows.extend([jnp.zeros_like(rows[0])] * pad)
        feats = jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]
        fn = self._pred._get_heads_fn(cap, size)
        return fn(params, rparams, feats, staged.exemplars), \
            (fill_map or None)

    # ---------------------------------------------------------- completion
    def _finish(self, staged: StagedBatch, out: dict, fill_feats) -> None:
        t_post0 = time.perf_counter()
        host = {name: np.asarray(out[name]) for name in _det_fields(out)}
        # the device fetch above is the batch's postprocess cost; stamp
        # its END here so the per-rider span is the same shared window
        # (like batch_assemble/stage/execute) — anchoring each rider's
        # span at its own resolve time instead would fold every EARLIER
        # rider's unpad+resolve into the later riders' spans
        t_fetch1 = time.perf_counter()
        staged.record_stage("serve.postprocess", t_post0, t_fetch1)
        kind, size = staged.bucket[0], staged.bucket[1]
        traced = obs.tracing_enabled()
        now = time.perf_counter()
        for i, req in enumerate(staged.requests):
            if req.expired(now):
                # postprocess shed: the device seconds are sunk, but the
                # per-request host copies + cache insert are not — and
                # the caller stopped waiting at the deadline anyway
                self._drop_inflight(req)
                self._admission.release(req)
                req.fail(RejectedError(
                    "deadline",
                    "deadline expired before postprocess",
                    priority=req.priority,
                ))
                n = len(req.futures)
                self._count("shed", n)
                self._count("shed.postprocess", n)
                continue
            try:
                # .copy(): a 1-row slice VIEW would pin the whole padded
                # batch's host arrays alive for as long as the result sits
                # in the cache (or with the caller) — a ~batch-size memory
                # retention multiplier at production geometry
                result = {
                    name: host[name][i:i + 1].copy()
                    for name in _det_fields(host)
                }
                if req.degrade_steps:
                    # exactness contract: a degraded result SAYS so —
                    # the cached copy carries the steps too, so a later
                    # cache hit stays accountable
                    result["degrade_steps"] = list(req.degrade_steps)
                if req.result_key is not None:
                    self.result_cache.put(req.result_key, result)
                if kind == "heads" and fill_feats and i in fill_feats:
                    self.feature_cache.put(
                        self._feature_key(req.image_digest, size),
                        fill_feats[i],
                    )
                self._drop_inflight(req)
                self._admission.release(req)
                t_res0 = time.perf_counter()
                req.resolve(result)
                t_res1 = time.perf_counter()
                if traced:
                    obs.add_span("serve.resolve", t_res0, t_res1,
                                 trace_id=req.trace_id or None,
                                 futures=len(req.futures),
                                 batch=req.batch)
                self._lat.observe(t_res1 - req.t_submit)
                if obs.flight_enabled():  # one bool check when off
                    obs.flight_record(
                        "serve.request", bucket=str(staged.bucket),
                        latency_s=round(t_res1 - req.t_submit, 6),
                        batch=len(staged.requests),
                        padded=staged.padded_slots,
                        device=str(staged.device),
                        futures=len(req.futures),
                    )
                # per FUTURE, not per request: coalesced duplicates
                # counted into `submitted` must land in a terminal
                # bucket too, or submitted - (completed+errors+rejected)
                # reads as phantom backlog forever
                self._m["completed"].inc(len(req.futures))
                if self._plan is not None:
                    self._record_drain(req.group)
            except Exception as e:  # isolation: this request alone
                self._drop_inflight(req)
                self._admission.release(req)
                req.fail(e)
                self._m["errors"].inc(len(req.futures))
        tuner = self._tuner
        if tuner is not None:  # live autotune: offer AFTER every future
            # resolved — a sampling decision + non-blocking enqueue, the
            # shadow execution runs on the tuner's thread. Host-side
            # request arrays, never the donated device buffers.
            try:
                tuner.offer(
                    (staged.bucket,
                     [(r.image, r.exemplars, r.k_real)
                      for r in staged.requests]),
                    None, items=len(staged.requests),
                )
            except Exception:
                pass  # tuning must never fail a served batch

    # ------------------------------------------------------ error fallback
    def _isolate(self, requests: List[Request], exc: BaseException,
                 batch_level: bool = False) -> None:
        """Batch-level failure -> per-request fallback: each request
        re-runs alone through the predictor, so one poison request fails
        alone while its batch-mates still get served."""
        if batch_level:
            self._m["batch_fallbacks"].inc()
        for req in requests:
            try:
                result = self._run_single(req)
                self._drop_inflight(req)
                self._admission.release(req)
                req.resolve(result)
                self._lat.observe(time.perf_counter() - req.t_submit)
                self._m["completed"].inc(len(req.futures))
                if self._plan is not None:
                    self._record_drain(req.group)
            except Exception as e:
                self._drop_inflight(req)
                self._admission.release(req)
                req.fail(e)
                self._m["errors"].inc(len(req.futures))

    def _run_single(self, req: Request) -> dict:
        kind = req.bucket[0]
        if kind == "multi":
            dets = self._pred.predict_multi_exemplar(
                req.image[None], req.exemplars, k_real=req.k_real
            )
        else:  # single and heads requests share __call__ semantics
            dets = self._pred(req.image[None], req.exemplars[None])
        out = {name: np.asarray(dets[name]) for name in _det_fields(dets)}
        if req.degrade_steps:
            out["degrade_steps"] = list(req.degrade_steps)
        return out

    def _drop_inflight(self, req: Request) -> None:
        if req.result_key is None:
            return
        with self._lock:
            if self._inflight.get(req.result_key) is req:
                del self._inflight[req.result_key]

    # --------------------------------------------------------------- health
    def health(self) -> dict:
        """One validated ``health_report/v1`` snapshot of the engine:
        queue depths, per-device occupancy, cache stats, compile-event
        tallies, and the anomalies the health watch fired on this pass
        (detector state advances per call — the heartbeat's interval IS
        the detection window). This is the admission-control input
        ROADMAP item 3 consumes; ``start_heartbeat`` appends it to a
        JSONL file on an interval."""
        from tmr_tpu.diagnostics import HEALTH_REPORT_SCHEMA
        from tmr_tpu.obs import devtime

        with self._lock:
            new_events, self._compile_seen = obs.compile_events_since(
                self._compile_seen
            )
            per_device = dict(self._per_device)
            batch_bounds = dict(self._batch_bounds)
            inflight = len(self._inflight)
            closed = self._closed
        pending = self._batcher.pending()
        by_group = self._batcher.depth_by_group()
        anomalies = self._watch.observe(
            self.metrics.snapshot(),
            compile_events=new_events,
            pending=pending,
            pending_by_group=(
                {g: rec["pending"] for g, rec in by_group.items()}
                if by_group else None
            ),
            mfu_totals=(devtime.totals() if obs.flight_enabled()
                        else None),
        )
        # the anomaly pass IS the degrade ladder's control input: each
        # health() call (the heartbeat's interval in production) runs
        # one escalation/cooldown step (serve/degrade.py)
        if self._degrade.enabled:
            self._degrade.observe(anomalies)
        now = time.time()
        # lifetime tallies from the monotone registry counters (exact;
        # the in-process event log is bounded and would undercount) —
        # `recent` is the bounded log's tail, for human eyes
        reg = obs.get_registry()
        recent = obs.compile_events()[-8:]
        doc = {
            "schema": HEALTH_REPORT_SCHEMA,
            "ts": now,
            "uptime_s": round(now - self._t_start, 3),
            "closed": closed,
            "inflight": inflight,
            "queues": {
                "pending": pending,
                "per_bucket": {
                    str(k): v
                    for k, v in self._batcher.depth_snapshot().items()
                },
            },
            "devices": [str(d) for d in self.devices],
            "per_device_batches": per_device,
            "batch_bounds": {str(k): v for k, v in batch_bounds.items()},
            "max_wait_ms": self.max_wait_ms,
            "caches": {
                "result": self.result_cache.stats(),
                "feature": self.feature_cache.stats(),
            },
            "counters": self.counters,
            "compile": {
                "total": int(reg.counter("compile.total").value),
                "cold": int(reg.counter("compile.cold").value),
                "key_change": int(
                    reg.counter("compile.key_change").value
                ),
                "recent": recent,
            },
            "anomalies": anomalies,
        }
        if self._quant_stamp is not None:
            doc["quant"] = dict(self._quant_stamp)
        # the overload-control sections appear only when the features
        # are on: a default-knobs engine's health_report shape stays
        # byte-identical to PR 8 (acceptance-pinned)
        if self._admission.enabled:
            doc["admission"] = self._admission.stats()
        if self._degrade.enabled:
            doc["degrade"] = self._degrade.stats()
        # mesh-serving sections appear only under a plan, so the
        # default-engine health shape stays byte-identical to PR 8
        if self._plan is not None:
            doc["queues"]["per_group"] = {
                str(g): {
                    "pending": rec["pending"],
                    "per_bucket": {
                        str(b): n for b, n in rec["per_bucket"].items()
                    },
                    "occupancy": {
                        str(sz): cnt for sz, cnt in sorted(
                            self._batcher.occupancy_snapshot(
                                group=g
                            ).items()
                        )
                    },
                }
                for g, rec in by_group.items()
            }
            doc["mesh"] = self._plan.describe()
            doc["drain_per_group"] = {
                g: round(r, 3) for g, r in self.drain_snapshot().items()
            }
            if self._warmup_stats is not None:
                doc["warmup"] = dict(self._warmup_stats)
        return doc

    def start_heartbeat(self, path: str,
                        interval_s: Optional[float] = None):
        """Append :meth:`health` to ``path`` as JSONL every
        ``interval_s`` seconds (default ``TMR_HEALTH_INTERVAL_S``).
        Returns the obs.Heartbeat; :meth:`close` stops it."""
        hb = obs.Heartbeat(self.health, path, interval_s=interval_s)
        with self._lock:
            old, self._heartbeat = self._heartbeat, hb
        if old is not None:
            old.stop()
        return hb

    # ------------------------------------------------------------ lifecycle
    def close(self, timeout: Optional[float] = None) -> None:
        """Drain pending requests and stop the pipeline threads — within
        a BOUND. ``timeout`` (None -> ``TMR_SERVE_DRAIN_TIMEOUT_S``,
        default 300) caps the whole drain: past it, every still-
        unresolved request's future fails with a structured shutdown
        :class:`RejectedError` instead of leaving its caller hanging on
        a wedged device (the pipeline threads are daemons, so an
        abandoned drain cannot block process exit). A drain that
        finishes in time is byte-for-byte the PR 3 behavior."""
        timeout = self._drain_timeout_s if timeout is None \
            else float(timeout)
        with self._lock:
            if self._closed:
                return
            self._closed = True
            hb, self._heartbeat = self._heartbeat, None
            tuner, self._tuner = self._tuner, None
        if hb is not None:
            hb.stop()
        if tuner is not None:
            tuner.stop()
        self._batcher.close()
        deadline = time.perf_counter() + max(timeout, 0.0)
        for t in self._threads:
            t.join(timeout=max(deadline - time.perf_counter(), 0.0))
        if not any(t.is_alive() for t in self._threads):
            return
        # bounded drain expired: resolve every leftover future with a
        # shutdown rejection. The inflight registry is the complete set
        # of unresolved requests (queued, staged, or dispatched — each
        # registered at submit, deregistered at its terminal event), and
        # Request.fail only touches not-done futures, so a straggler
        # thread resolving late is a harmless no-op on both sides.
        with self._lock:
            leftovers = list(self._inflight.values())
            self._inflight.clear()
            self._drain_timed_out = True
        for req in leftovers:
            self._admission.release(req)
            req.fail(RejectedError(
                "shutdown",
                f"engine closed; request unserved after the "
                f"{timeout:.1f}s drain bound",
                priority=req.priority,
            ))
            n = len(req.futures)
            self._count("shed", n)
            self._count("shed.shutdown", n)

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- metrics
    @property
    def counters(self) -> Dict[str, int]:
        """The PR 3 ad-hoc counters dict, now a registry read — same keys
        and values, for any consumer that grabbed ``engine.counters``."""
        return {name: c.value for name, c in self._m.items()}

    def metrics_snapshot(self) -> dict:
        """This engine's registry as one ``metrics_report/v1`` document
        (counters + cache counters + the request-latency histogram) — what
        serve_bench attaches under its report's ``metrics`` key."""
        return self.metrics.snapshot()

    def overload_counters(self) -> Dict[str, int]:
        """The admission/shed/degrade tallies as plain ints, zero when
        nothing ever fired — always available (serve_bench and the
        overload probe delta these per workload), but folded into
        ``stats()`` only once an overload feature is in play so the
        default shape stays PR 3."""
        with self._lock:
            live = {name: int(c.value) for name, c in self._mx.items()}
        return {
            "admit_rejected": live.get("admit_rejected", 0),
            "shed": live.get("shed", 0),
            "degraded": live.get("degraded", 0),
            **{k: v for k, v in sorted(live.items())
               if "." in k},  # per-cause / per-stage / per-step splits
        }

    def stats(self) -> dict:
        with self._lock:
            per_device = dict(self._per_device)
            batch_bounds = dict(self._batch_bounds)
        counters = self.counters
        out = {
            **counters,
            "batch_occupancy": {
                str(k): v
                for k, v in sorted(
                    self._batcher.occupancy_snapshot().items()
                )
            },
            "pending": self._batcher.pending(),
            "result_cache": self.result_cache.stats(),
            "feature_cache": self.feature_cache.stats(),
            "devices": [str(d) for d in self.devices],
            "per_device_batches": per_device,
            "max_wait_ms": self.max_wait_ms,
            "batch_bounds": {str(k): v for k, v in batch_bounds.items()},
            "donate": self.donate,
        }
        if self._quant_stamp is not None:
            out["quant"] = dict(self._quant_stamp)
        with self._lock:
            any_fired = bool(self._mx)
            drain_timed_out = self._drain_timed_out
        if self._admission.enabled or self._degrade.enabled or any_fired:
            out["overload"] = {
                "counters": self.overload_counters(),
                "admission": self._admission.stats(),
                "degrade": self._degrade.stats(),
                "drain_timed_out": drain_timed_out,
            }
        if self._plan is not None:
            out["mesh"] = self._plan.describe()
            out["per_group_queues"] = {
                str(g): rec["pending"]
                for g, rec in self._batcher.depth_by_group().items()
            }
            out["per_group_occupancy"] = {
                str(g): {
                    str(sz): cnt for sz, cnt in sorted(
                        self._batcher.occupancy_snapshot(group=g).items()
                    )
                }
                for g in self._batcher.groups
            }
            out["drain_per_group"] = {
                g: round(r, 3) for g, r in self.drain_snapshot().items()
            }
            if self._warmup_stats is not None:
                out["warmup"] = dict(self._warmup_stats)
        return out

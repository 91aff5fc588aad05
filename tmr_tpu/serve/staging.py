"""Host->device staging for the serving pipeline.

One formed batch becomes a :class:`StagedBatch`: host-side padding/stacking
(a ragged tail pads up to its power-of-two sub-bucket — see ``_pad_to`` —
with zero images and a dummy exemplar; padded rows compute garbage that
unpadding drops, real rows are untouched, which is what keeps batched
results bitwise-identical to sequential calls) followed by
``jax.device_put`` onto the next device in a
round-robin over the engine's device list. The engine runs this on a
dedicated staging thread feeding a depth-2 queue, so batch N+1's H2D copy
overlaps batch N's device compute (double buffering), and successive
batches land on different chips for data-parallel multi-device serving —
the eval path is embarrassingly parallel, no collective involved.

Params are replicated lazily: the first batch staged for a device pays one
params transfer; every later batch reuses the committed copy.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Any, List, Sequence

import numpy as np

from tmr_tpu import obs
from tmr_tpu.serve.batcher import Request

#: dummy exemplar box for padded slots — any in-range box works (the rows
#: are dropped at unpad); mid-image keeps select_capacity_bucket happy
_PAD_BOX = (0.45, 0.45, 0.55, 0.55)


@dataclass
class StagedBatch:
    bucket: tuple
    requests: List[Request]
    device: Any  # a jax device (legacy round-robin) or a MeshTarget
    images: Any = None  # device (B, S, S, 3) f32; None for pure-hit heads
    exemplars: Any = None  # device (B, K, 4) f32
    k_real: Any = None  # device (B,) i32 (multi path)
    features: Any = None  # device (B, h, w, C) (heads path, after fill)
    fill_index: List[int] = field(default_factory=list)  # rows needing bb
    padded_slots: int = 0
    t_staged: float = 0.0

    def record_stage(self, name: str, t0: float, t1: float) -> None:
        """One batch stage's window: once for the batch, always
        (``scope="batch"``; ``rows`` over ``slots`` is the occupancy, and
        ``batch`` the batcher's id of it, ``Request.batch``), and under
        ``TMR_TRACE`` once more a rider under the trace id it carried
        from submit, naming the batch."""
        rows = len(self.requests)
        batch = self.requests[0].batch if rows else 0
        obs.add_span(name, t0, t1, scope="batch", batch=batch,
                     bucket=str(self.bucket), rows=rows,
                     slots=rows + self.padded_slots,
                     device=str(self.device))
        if obs.tracing_enabled():
            for r in self.requests:
                obs.add_span(name, t0, t1, trace_id=r.trace_id or None,
                             batch=batch)

    @property
    def target(self):
        """The MeshTarget this batch stages onto (None on the legacy
        per-device path)."""
        from tmr_tpu.serve.meshplan import MeshTarget

        return self.device if isinstance(self.device, MeshTarget) else None


def _pad_to(n: int, bound: int) -> int:
    """Ragged-tail batch shape: the next power of two >= n, capped at the
    bucket's bound. A lone timeout-flushed request must not pay a full
    bound-sized execution (it collapses low-offered-load capacity and the
    p99 bound), so tails run in power-of-two sub-buckets — at most
    log2(bound) extra compiles per bucket, each shape compiled lazily on
    first occurrence, and per-image results stay bitwise-identical (the
    programs are batch-invariant per row; tests/test_serve.py)."""
    p = 1
    while p < n:
        p *= 2
    return min(max(p, 1), max(bound, n))


class DeviceStager:
    """Round-robin device placement + lazy per-device params replication.

    Mesh serving (a ``meshplan.MeshPlan`` on the engine) routes through
    the same stager with :class:`MeshTarget` targets instead of bare
    devices: params commit once per target — sharded over the group's
    ``tp`` axis for tensor-parallel targets
    (``parallel/sharding.serve_param_shardings``), replicated across the
    mesh for the data-parallel target — and batches stage with the
    matching NamedSharding so the program's in_shardings are satisfied
    without a resharding copy at dispatch."""

    def __init__(self, devices: Sequence[Any], params, refiner_params=None):
        if not devices:
            raise ValueError("DeviceStager needs at least one device")
        self.devices = list(devices)
        self._rr = itertools.cycle(self.devices)
        self._host_params = (params, refiner_params)
        self._per_device: dict = {}
        self._lock = threading.Lock()

    def params_for(self, device):
        """(params, refiner_params) committed to ``device`` — a jax
        device or a MeshTarget — cached per placement."""
        from tmr_tpu.serve.meshplan import MeshTarget

        if isinstance(device, MeshTarget):
            return self._params_for_target(device)
        with self._lock:
            if device not in self._per_device:
                import jax

                self._per_device[device] = jax.device_put(
                    self._host_params, device
                )
            return self._per_device[device]

    def _params_for_target(self, target):
        with self._lock:
            placed = self._per_device.get(target.key)
        if placed is not None:
            return placed
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        params, rparams = self._host_params
        if target.tp > 1:
            from tmr_tpu.parallel.sharding import serve_param_shardings

            pshard = serve_param_shardings(params, target.mesh)
            repl = NamedSharding(target.mesh, P())
            placed = (
                jax.device_put(params, pshard),
                None if rparams is None else jax.device_put(rparams, repl),
            )
        elif target.mode == "dp":
            repl = NamedSharding(target.mesh, P())
            placed = (
                jax.device_put(params, repl),
                None if rparams is None else jax.device_put(rparams, repl),
            )
        else:  # tp == 1 replica group: the plain per-device program
            placed = jax.device_put(self._host_params, target.primary)
        with self._lock:
            # a racing double-place commits the same values twice; the
            # second result wins and the first is garbage-collected
            self._per_device[target.key] = placed
        return placed

    def batch_sharding(self, target):
        """How a staged batch array lands on ``target``: sharded over
        ``dp`` for the data-parallel target, replicated across the
        group for tensor-parallel ones, the primary device for plain
        (tp == 1) groups."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if target.mode == "dp":
            return NamedSharding(target.mesh, P("dp"))
        if target.tp > 1:
            return NamedSharding(target.mesh, P())
        return target.primary

    def next_device(self):
        return next(self._rr)

    # ------------------------------------------------------------- staging
    def stage(self, bucket: tuple, requests: List[Request],
              bound: int, target=None) -> StagedBatch:
        """Pad/stack the batch host-side and start its H2D transfers.

        ``bound`` is the PER-DEVICE coalescing bound. With a MeshTarget
        the padded batch additionally respects the target's geometry: a
        data-parallel target pads to ``dp x`` a power-of-two per-shard
        sub-bucket (every shard sees a ladder shape, so dp serving
        compiles the same log2(bound) program set per bucket as the
        unsharded engine — and shards divide evenly by construction)."""
        import time

        import jax

        kind, size, _cap, k = bucket
        n = len(requests)
        if target is not None and target.mode == "dp":
            per_shard = _pad_to((n + target.dp - 1) // target.dp,
                                int(bound))
            bound = per_shard * target.dp
            device = target
            placement = self.batch_sharding(target)
        elif target is not None:
            bound = _pad_to(n, int(bound))
            device = target
            placement = self.batch_sharding(target)
        else:
            bound = _pad_to(n, int(bound))
            device = self.next_device()
            placement = device
        staged = StagedBatch(bucket=bucket, requests=list(requests),
                             device=device,
                             padded_slots=bound - n)

        t_assemble = time.perf_counter()
        if kind == "heads":
            t_put = self._stage_heads(
                staged, bound, size, k,
                target.primary if target is not None else device,
            )
        else:
            images = np.zeros((bound, size, size, 3), np.float32)
            exemplars = np.tile(
                np.asarray(_PAD_BOX, np.float32), (bound, k, 1)
            )
            for i, r in enumerate(requests):
                images[i] = r.image
                exemplars[i] = r.exemplars
            if kind == "multi":
                k_real = np.ones((bound,), np.int32)
                for i, r in enumerate(requests):
                    k_real[i] = r.k_real
            t_put = time.perf_counter()
            staged.images = jax.device_put(images, placement)
            staged.exemplars = jax.device_put(exemplars, placement)
            if kind == "multi":
                staged.k_real = jax.device_put(k_real, placement)
        staged.t_staged = time.perf_counter()
        # host pad/stack (assemble), then the H2D transfers (stage)
        staged.record_stage("serve.batch_assemble", t_assemble, t_put)
        staged.record_stage("serve.stage", t_put, staged.t_staged)
        return staged

    def _stage_heads(self, staged: StagedBatch, bound: int, size: int,
                     k: int, device) -> float:
        """Heads-path staging: requests with cached features move only
        their (tiny) exemplars; promotion fills move their image so the
        dispatch thread can run the encoder for them. Cached features may
        live on a different device (round-robin) — device_put moves them,
        a no-op when already resident. Returns the host-assembly ->
        device-transfer boundary timestamp (the stage-span split)."""
        import jax
        import time

        requests = staged.requests
        exemplars = np.tile(
            np.asarray(_PAD_BOX, np.float32), (bound, k, 1)
        )
        for i, r in enumerate(requests):
            exemplars[i] = r.exemplars
        staged.fill_index = [
            i for i, r in enumerate(requests) if r.features is None
        ]
        images = None
        if staged.fill_index:
            # fills pad to a power-of-two sub-bucket like every other
            # batch shape: the backbone program must compile at log2(bound)
            # shapes, not once per distinct fill count — an encoder
            # retrace at serving time is seconds of injected latency
            n_fill = _pad_to(len(staged.fill_index), bound)
            images = np.zeros((n_fill, size, size, 3), np.float32)
            for j, i in enumerate(staged.fill_index):
                images[j] = requests[i].image
        t_put = time.perf_counter()
        staged.exemplars = jax.device_put(exemplars, device)
        if images is not None:
            staged.images = jax.device_put(images, device)
        # hits: move each (1, h, w, C) feature to this batch's device
        staged.features = [
            None if r.features is None else jax.device_put(r.features,
                                                           device)
            for r in requests
        ]
        return t_put

#!/usr/bin/env python3
"""Which serving program, loaded from the persistent compile cache, halts
a TPU sub-slice (serve/meshplan.refuse_cached_subslice_tp).

Each cell starts a ``ServeEngine`` on some of a four-chip host's devices,
lets its warm-up execute the 1-exemplar program of ViT-B/1024 and answers
two requests:

    one2     no mesh, chip 2 alone
    tp01     mesh tp2 on chips 0 and 1
    tp23     mesh tp2 on chips 2 and 3
    dp2tp2   mesh dp2tp2: both pairs in one process

    python scripts/mesh_cache_probe.py CELL [CELL ...]

A halted chip takes the process with it, so give every cell that may halt
a process of its own, one after the other (one process holds the chips at
a time), all with the same ``JAX_COMPILATION_CACHE_DIR``: the first
process compiles and fills the directory, the later ones load from it.
For every program the script prints the cache key, whether it was a hit,
the loaded executable's fingerprint and its devices, and a line before
each step that can halt. It lifts the engine's guard for itself.
"""

from __future__ import annotations

import collections
import hashlib
import inspect
import logging
import os
import sys
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402  (the smoke script's model and inputs)

#: cell -> (mesh spec, indices into jax.local_devices())
CELLS = {
    "one2": (None, (2,)),
    "tp01": ("tp2", (0, 1)),
    "tp23": ("tp2", (2, 3)),
    "dp2tp2": ("dp2tp2", (0, 1, 2, 3)),
}

_events: collections.Counter = collections.Counter()


class _CacheKeys(logging.Handler):
    """The compiler's own hit/miss lines, for the serving programs only."""

    def emit(self, record):
        msg = record.getMessage()
        if "ersistent" in msg.lower() and (
            "jit_body" in msg or "jit_run" in msg
        ):
            cs.say(f"    jax: {msg}")


def _digest(dets: dict) -> str:
    h = hashlib.sha256()
    for k in cs.FIELDS:
        h.update(np.ascontiguousarray(dets[k]).tobytes())
    return h.hexdigest()[:12]


def _cache_counts():
    return (_events["/jax/compilation_cache/cache_hits"],
            _events["/jax/compilation_cache/cache_misses"])


def run_cell(name: str, pred, size, seed: int) -> None:
    import jax

    from tmr_tpu.serve import ServeEngine, engine

    spec, idx = CELLS[name]
    devices = [jax.local_devices()[i] for i in idx]
    bucket = pred.bucket_key(size.image_size, cs.exemplars(size, 1))
    reqs = cs.serve_requests(size, seed)[:2]
    cs.say(f"cell {name}: mesh {spec} on devices {[d.id for d in devices]}; "
           f"about to start the engine (its warm-up executes the program)")
    h0, m0 = _cache_counts()
    t0 = time.perf_counter()
    with mock.patch.object(engine, "refuse_cached_subslice_tp",
                           lambda plan: None), \
            ServeEngine(pred, batch=1, max_wait_ms=5, feature_cache=0,
                        mesh=spec, devices=devices,
                        warmup_buckets=[bucket]) as eng:
        h1, m1 = _cache_counts()
        cs.say(f"  engine started in {time.perf_counter() - t0:.1f}s: "
               f"warm-up {eng._warmup_stats}; persistent-cache hits "
               f"{h1 - h0} misses {m1 - m0}")
        targets = (eng._plan.group_targets if eng._plan is not None
                   else [None])
        for target in targets:
            if target is None:
                params, rparams = eng._stager.params_for(devices[0])
                placement = devices[0]
            else:
                params, rparams = eng._run_params(target, "single")
                placement = eng._stager.batch_sharding(target)
            fn = inspect.unwrap(eng._program_for(bucket, target),
                                stop=lambda f: hasattr(f, "lower"))
            img, ex, _ = reqs[0]
            exe = fn.lower(
                params, rparams, jax.device_put(img[None], placement),
                jax.device_put(ex[None], placement),
            ).compile().runtime_executable()
            cs.say(f"  {getattr(target, 'name', 'device')}: executable "
                   f"fingerprint {exe.fingerprint.hex()[:16]} on devices "
                   f"{[d.id for d in exe.local_devices()]}")
        for i, (img, ex, multi) in enumerate(reqs):
            cs.say(f"  about to submit request {i}")
            out = eng.submit(img, ex, multi=multi).result(timeout=600)
            cs.say(f"  request {i}: valid "
                   f"{int(np.asarray(out['valid']).sum())}, digest "
                   f"{_digest(cs._np(out))}")
        stats = eng.stats()
        cs.say(f"  batches per device {stats['per_device_batches']}; "
               f"errors {stats['errors']}")
    cs.say(f"cell {name}: survived")


def main(argv) -> int:
    cells = argv or list(CELLS)
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        print(f"unknown cell(s) {unknown}; one of {list(CELLS)}",
              file=sys.stderr)
        return 2
    import jax
    from jax import monitoring

    from tmr_tpu.utils.cache import enable_compilation_cache

    monitoring.register_event_listener(
        lambda name, **kw: _events.update([name]))
    logging.getLogger("jax._src.compiler").setLevel(logging.DEBUG)
    logging.getLogger("jax._src.compiler").addHandler(_CacheKeys())
    logging.getLogger("jax._src.compiler").propagate = False
    cache_dir = enable_compilation_cache()
    dev = jax.devices()[0]
    cs.say(f"mesh_cache_probe {cells}: {dev.device_kind!r} "
           f"x{len(jax.devices())}; compile cache {cache_dir}, "
           f"{cs.cache_entries(cache_dir)} entries")
    size = cs.FULL if dev.platform == "tpu" else cs.Size(
        image_size=256, emb_dim=64, square=32)  # a rehearsal off the chip
    pred = cs.build_predictor(size, 0)
    for name in cells:
        run_cell(name, pred, size, 0)
    h, m = _cache_counts()
    cs.say(f"done {cells}: persistent-cache hits {h} misses {m}; "
           f"{cs.cache_entries(cache_dir)} entries")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

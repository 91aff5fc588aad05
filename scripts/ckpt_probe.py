"""Probe the trained-ckpt bench anomaly (BENCH_CKPT_LIVE.json: 3628 ms vs
394 ms for an identical program).

Times the production fused program (bench shapes) under three param trees:

  init       Predictor.init_params output (the 10.1 img/s headline's args)
  restored   orbax restore with target=init params — these arrays carry
             explicit shardings (the CPU HLO diff shows per-arg
             sdy.sharding annotations, the only trace difference) and are
             the prime suspect for the 9x
  roundtrip  the restored values pulled to host and re-device_put as
             ordinary uncommitted arrays (identical numerics, no committed
             sharding)

If restored is slow and roundtrip is fast, the committed shardings
pessimized XLA's layout/compile and the fix is a host roundtrip (or
device_put-through-identity) in bench.py's restore branch. If both are
slow, the slowdown is value-dependent after all.

Prints one JSON line {variant: ms_per_batch}.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = int(os.environ.get("TMR_BENCH_BATCH", 4))
SIZE = int(os.environ.get("TMR_BENCH_SIZE", 1024))
CKPT = os.environ.get("TMR_BENCH_CKPT", "bench_ckpt/params")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp

    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor
    from tmr_tpu.utils.cache import enable_compilation_cache
    from tmr_tpu.utils.profiling import (
        chained_seconds_per_iter,
        measure_rtt_floor,
    )

    enable_compilation_cache()
    cfg = preset(
        "TMR_FSCD147", backbone="sam_vit_b", image_size=SIZE,
        compute_dtype="bfloat16", batch_size=BATCH,
    )
    pred = Predictor(cfg)
    pred.init_params(seed=0, image_size=SIZE)
    rng = np.random.default_rng(0)
    image = jnp.asarray(
        rng.standard_normal((BATCH, SIZE, SIZE, 3)), jnp.float32
    )
    ex = jnp.tile(
        jnp.asarray([[[0.45, 0.45, 0.53, 0.55]]], jnp.float32), (BATCH, 1, 1)
    )
    fused = pred._get_fn(17, chain_feedback=True)
    rtt = measure_rtt_floor()

    restored = ocp.StandardCheckpointer().restore(
        os.path.abspath(CKPT), target=pred.params
    )
    roundtrip = jax.device_put(jax.device_get(restored))

    out = {"rtt_floor_ms": round(rtt * 1000, 1)}
    for label, params in (
        ("init", pred.params),
        ("restored", restored),
        ("roundtrip", roundtrip),
    ):
        sec = chained_seconds_per_iter(
            lambda im, fb, p=params: fused(p, None, im, ex, fb),
            image, rtt=rtt, iters=5,
        )
        out[label] = round(sec * 1000, 1)
        print(f"[ckpt_probe] {label}: {out[label]} ms/batch",
              file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

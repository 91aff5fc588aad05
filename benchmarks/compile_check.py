#!/usr/bin/env python3
"""Compile each cell's timed program for a *described* TPU v5e and print its
``memory_analysis()``: a rehearsal to run by hand before a chip call
(``JAX_PLATFORMS=cpu python3 benchmarks/compile_check.py [cell ...]``).

Nothing runs, so this says nothing about results or times; it says that the
chip's compiler takes the program at the cell's batch and what the program
would hold on the chip. A compile that passes here is not a chip run.

Code of the program that asks ``jax.default_backend()`` sees the CPU here, so
this steers those branches to their TPU side and lets the Mosaic gates of the
``auto`` path admit (their self-checks execute, which only a chip can), as
``tests/test_chip_compile.py`` does.
"""

from __future__ import annotations

import inspect
import json
import os
import sys
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import traffic
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import mosaic_gate
    from tmr_tpu.inference import Predictor
    from tmr_tpu.ops import flash_attn, pallas_nms

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = argv or [w["name"] for w in manifest["workloads"]]

    def admits(name):
        def gate(*a, **k):
            return True
        gate.__name__ = name
        return mosaic_gate(gate)

    patches = [mock.patch.object(jax, "default_backend", lambda: "tpu")] + [
        mock.patch.object(mod, name, admits(name)) for mod, name in (
            (flash_attn, "flash_attention_ok"),
            (flash_attn, "flash_window_ok"),
            (pallas_nms, "pallas_nms_compiled_ok"))]
    for p in patches:
        p.start()
    try:
        for name in cells:
            with open(os.path.join(HERE, "workloads", name + ".json")) as f:
                workload = json.load(f)
            with open(os.path.join(HERE, "configs",
                                   workload["config"] + ".json")) as f:
                config = json.load(f)
            pred = Predictor(preset(config["preset"], **config["overrides"]))
            size, batch = pred.cfg.image_size, workload["traffic"]["batch"]
            lo, hi = workload["traffic"]["exemplar_side_px"]
            sides = traffic.exemplar_sides(
                lo, hi, batch * workload["traffic"]["pool_batches"])
            image = jnp.zeros((1, size, size, 3), jnp.float32)
            ex = jnp.asarray([[[0.0, 0.0, sides[-1] / size, sides[-1] / size]]])
            params = jax.tree.map(
                lambda x: sds(x.shape, x.dtype),
                jax.eval_shape(pred.model.init, jax.random.key(0), image,
                               ex)["params"])
            cap = pred.pick_capacity(ex, size)
            fn = inspect.unwrap(pred._get_fn(cap),
                                stop=lambda f: hasattr(f, "lower"))
            t0 = time.perf_counter()
            compiled = fn.lower(
                params, None, sds((batch, size, size, 3), jnp.float32),
                sds((batch, 1, 4), jnp.float32)).compile()
            m = compiled.memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes)
            print(f"{name}: batch {batch}, capacity {cap}, compiled for a "
                  f"described v5e in {time.perf_counter() - t0:.0f}s (a "
                  f"compile, not a run): arguments "
                  f"{m.argument_size_in_bytes} outputs "
                  f"{m.output_size_in_bytes} temporaries "
                  f"{m.temp_size_in_bytes} bytes, together {total} "
                  f"({total / 2**30:.2f} GiB); "
                  f"{compiled.as_text().count('tpu_custom_call')} "
                  f"tpu_custom_call", flush=True)
    finally:
        for p in patches:
            p.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

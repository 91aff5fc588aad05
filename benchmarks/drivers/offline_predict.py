"""Offline evaluation: batches through ``Predictor.__call__``, fed as
``Trainer.eval_epoch`` feeds it.

The one-batch software pipeline of the trainer's eval loop: dispatch batch
k + 1 (host images in, so the upload is inside the window, as an eval job
pays it), then ``detections_to_numpy`` of batch k. The window cycles through
the pool that set-up made from ``--seed``; every image whose detections
reached the host counts, over the whole window from the first dispatch to
the last collection.

From the program this takes ``preset``, ``Predictor`` and
``detections_to_numpy`` and nothing else; weights, inputs, the reference and
the arithmetic are the benchmark's.
"""

from __future__ import annotations

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check_detections, reference, traffic, weights, work


def _build_predictor(ctx):
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset(ctx.config["preset"], **ctx.overrides)
    return Predictor(cfg)


def _template_cells(exemplars: np.ndarray, hw: int) -> float:
    cells = [np.prod(reference.template_size(e, hw, hw)[1])
             for e in exemplars.reshape(-1, 4)]
    return float(np.mean(cells))


def _say_gates(ctx, size: int) -> None:
    """The program's own verdicts on the kernels its ``auto`` path asks
    for at this configuration's head size, and every refusal on record."""
    from tmr_tpu.diagnostics import drain_gate_refusals
    from tmr_tpu.ops.flash_attn import flash_attention_ok, flash_window_ok
    from tmr_tpu.ops.pallas_nms import pallas_nms_compiled_ok

    model = ctx.config["model"]
    grid, win = size // model["patch_size"], model["window_size"]
    hd = model["embed_dim"] // model["num_heads"]
    ctx.say(f"gates: flash_attention_ok({grid}, {grid}, {hd}) "
            f"{flash_attention_ok(grid, grid, hd)}; flash_window_ok({win}, "
            f"{win}, {hd}) {flash_window_ok(win, win, hd)}; "
            f"pallas_nms_compiled_ok() {pallas_nms_compiled_ok()}")
    for r in drain_gate_refusals():
        ctx.say(f"gate refusal: {r['gate']} cause {r['cause']} config "
                f"{r['config']} message {r['message'][:200]!r}")


def setup(ctx) -> dict:
    pred = _build_predictor(ctx)
    size = int(pred.cfg.image_size)
    _say_gates(ctx, size)
    images, exemplars = traffic.generate(
        ctx.workload["traffic"], size, ctx.seed, ctx.workload["config"])
    ctx.say(f"traffic: pool {images.shape[:2]} images of {size} px, "
            f"{images.nbytes / 2**20:.0f} MiB on the host")

    shapes = jax.eval_shape(
        pred.model.init, jax.random.key(0), jnp.zeros((1, size, size, 3)),
        jnp.asarray(exemplars[0, :1]))["params"]
    t0 = time.perf_counter()
    flat = jax.block_until_ready(weights.make_weights(
        weights.flatten(shapes), ctx.config["weights"], ctx.seed))
    pred.params = weights.unflatten(flat)
    n_par = sum(int(np.prod(v.shape)) for v in flat.values())
    ctx.say(f"weights: {n_par / 1e6:.1f} M float32 parameters made on the "
            f"device in {time.perf_counter() - t0:.1f}s")

    caps = [pred.pick_capacity(ex, size) for ex in exemplars]
    ctx.say(f"template capacities of the pool's batches: {caps}")
    from tmr_tpu.inference import detections_to_numpy

    for cap in sorted(set(caps)):
        p = caps.index(cap)
        for label in ("first", "second"):
            t0 = time.perf_counter()
            detections_to_numpy(pred(images[p], exemplars[p]))
            ctx.say(f"warm-up, capacity {cap}, {label} call: "
                    f"{time.perf_counter() - t0:.2f}s")
    return {"pred": pred, "flat": flat, "images": images,
            "exemplars": exemplars, "caps": caps,
            "collect": detections_to_numpy, "size": size,
            "feature_hw": pred.feature_hw(size),
            "rules": {"cls_threshold": float(pred.cfg.NMS_cls_threshold),
                      "iou_threshold": float(pred.cfg.NMS_iou_threshold),
                      "max_detections": int(pred.cfg.max_detections)}}


def window(ctx, state: dict, seconds: float, max_batches=None) -> dict:
    """The measured window. ``max_batches`` bounds a traced window."""
    pred, images, exemplars = state["pred"], state["images"], state["exemplars"]
    collect, span = state["collect"], ctx.span
    n_pool = len(images)
    served = [None] * n_pool
    n_img = dispatched = 0
    pending = None
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds or (max_batches and dispatched >= max_batches):
                break
            p = dispatched % n_pool
            with span("bench.dispatch"):
                dets = pred(images[p], exemplars[p])
            dispatched += 1
            if pending is not None:
                with span("bench.collect"):
                    served[pending[0]] = collect(pending[1])
                n_img += len(served[pending[0]])
            pending = (p, dets)
        if pending is not None:
            with span("bench.collect"):
                served[pending[0]] = collect(pending[1])
            n_img += len(served[pending[0]])
        t1 = time.perf_counter()
    state["served"] = served
    return {"images": n_img, "batches": dispatched, "seconds": t1 - t0,
            "order": [i % n_pool for i in range(dispatched)],
            "run_prefix": "jit_run",  # Predictor._get_fn jits ``run``
            "metrics": {"img_per_s": n_img / (t1 - t0)},
            "attempted": n_img, "failed": 0}


def hlo_texts(state: dict) -> dict:
    """capacity -> the compiled text of the very program the window ran
    (after ``chip_smoke.py:phase_predict``); served from the compile cache."""
    pred = state["pred"]
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    out = {}
    for cap in sorted(set(state["caps"])):
        p = state["caps"].index(cap)
        jitted = inspect.unwrap(pred._get_fn(cap),
                                stop=lambda f: hasattr(f, "lower"))
        compiled = jitted.lower(
            jax.tree.map(sds, pred.exec_params()), pred.refiner_params,
            sds(state["images"][p]), sds(state["exemplars"][p])).compile()
        out[cap] = {"text": compiled.as_text(),
                    "memory": compiled.memory_analysis()}
    return out


def work_per_image(ctx, state: dict) -> dict:
    """What the reducers divide by: FLOPs and bytes from the shapes."""
    model = ctx.config["model"]
    cells = _template_cells(state["exemplars"], state["feature_hw"])
    return {
        "forward_flops": work.forward_flops_per_image(
            model, state["size"], cells),
        "global_attn": work.global_attn_per_image(model, state["size"]),
    }


def release(state: dict) -> None:
    """Free the program's state on the device; the weights stay for the
    reference, which shares their buffers."""
    state["pred"].invalidate_compiled()
    state["pred"].params = None
    state.pop("pred")
    jax.clear_caches()


def check(ctx, state: dict, quant=None, alter=None) -> dict:
    """Compare a sample of the window's answers, drawn from the seed, with
    the plain reference. ``quant`` puts the reference at a lower precision
    in the program's place (the control); ``alter`` is a test's hook on the
    served answers."""
    rules = dict(state["rules"], **ctx.workload["correct"]["margins"])
    model = ctx.config["model"]
    rng = traffic.rng_for(ctx.seed, "check")
    done = [p for p, s in enumerate(state["served"]) if s is not None]
    if not done:
        return check_detections.merge([])
    batch = state["images"].shape[1]
    biggest = max(done, key=lambda p: state["caps"][p])
    picks = [(biggest, int(rng.integers(batch)))]
    want = int(ctx.workload["correct"]["images"])
    for _ in range(20 * want):  # distinct images, at most `want` of them
        if len(picks) >= min(want, len(done) * batch):
            break
        pick = (done[int(rng.integers(len(done)))], int(rng.integers(batch)))
        if pick not in picks:
            picks.append(pick)
    flat = state["flat"]
    per_image = []
    for p, b in picks:
        t0 = time.perf_counter()
        image, exemplar = state["images"][p, b], state["exemplars"][p, b, 0]
        obj, reg = reference.forward_dense(flat, image, exemplar, model)
        if quant is None:
            served = state["served"][p][b]
        else:
            q_obj, q_reg = reference.forward_dense(flat, image, exemplar,
                                                   model, quant=quant)
            got = reference.detect(q_obj, q_reg, exemplar,
                                   rules["cls_threshold"],
                                   rules["iou_threshold"],
                                   rules["max_detections"])
            hw = obj.shape[0]
            served = {"boxes": got["boxes"], "scores": got["scores"],
                      "refs": np.stack([(got["cells"] % hw) / hw,
                                        (got["cells"] // hw) / hw], -1)}
        if alter is not None:
            served = alter(served)
        numbers = check_detections.compare_image(served, obj, reg, exemplar,
                                                 rules)
        ctx.say(f"check pool batch {p} row {b} (capacity "
                f"{state['caps'][p]}): {numbers} "
                f"[{time.perf_counter() - t0:.1f}s]")
        per_image.append(numbers)
    return check_detections.merge(per_image)

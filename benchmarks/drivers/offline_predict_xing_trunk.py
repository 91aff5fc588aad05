"""Offline evaluation through ``Predictor.__call__`` with the Xing4.0 trunk
as the backbone (``tmr_tpu/models/lm_trunk.py``, ``xing4_a4b_stage6``).

The window, the compiled texts and the release are ``offline_predict``'s and
the weights' draw a layer at a time, the served batch with its routing
table, the balancing rule and the count of moved pairs are
``offline_predict_lm_trunk``'s, all by import: the cell is fed and judged as
``kimilinear_fscd147.eval`` is. What this file binds anew:

- the reference is ``reference_xing_trunk`` and the work ``work_xing_trunk``
  (the two functions of ``offline_predict_lm_trunk`` that name their
  reference, ``balance_routers`` and ``check``, are written out here against
  this one: a ``benchmark`` PR that hands them the reference as an argument
  makes these copies go);
- ``b_res`` gets twice the identity added to what ``weights.py`` drew (it
  draws ``mean + std x noise`` only), so that ``H_res`` is neither the
  identity nor uniform (the configuration's ``assumed.hc_weights``);
- the configuration's sizes under the program's names include the low-rank
  query, the rotary group, the streams and the norm's eps.
"""

from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import (check_detections, reference, reference_xing_trunk,
                        traffic, weights, work_xing_trunk)
from benchmarks.drivers.offline_predict import (_build_predictor,
                                                _template_cells, hlo_texts,
                                                release, window)
from benchmarks.drivers.offline_predict_lm_trunk import (_NEED_STEPS,
                                                         _collect, _even_bias,
                                                         _groups, _moved,
                                                         make_weights)
from benchmarks.reference import _sub

__all__ = ["setup", "window", "hlo_texts", "work_per_image", "release",
           "check"]


def _trunk_sizes(model: dict) -> dict:
    """The configuration file's sizes under the program's own names
    (``lm_trunk.TRUNK_CONFIGS``)."""
    rope = {k: v for k, v in model["rope_scaling"].items() if k != "type"}
    return dict(
        hidden=model["hidden_size"],
        layers=tuple(tuple(layer) for layer in model["layers"]),
        num_heads=model["num_heads"], qk_nope_dim=model["qk_nope_head_dim"],
        qk_pe_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        kv_rank=model["kv_lora_rank"], q_rank=model["q_lora_rank"],
        dense_width=model["intermediate_size"],
        expert_width=model["moe_intermediate_size"],
        num_experts=model["router_experts"],
        experts_held=model["experts_held"],
        top_k=model["num_experts_per_token"],
        routed_scale=float(model["routed_scaling_factor"]),
        norm_eps=model["rms_norm_eps"],
        rope=dict(rope, theta=model["rope_theta"]),
        hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"], hc_eps=model["hc_eps"],
        hc_clamp=(float(model["mhc_h_res_clamp_min"]),
                  float(model["mhc_h_res_clamp_max"])))


def _agree_on_sizes(ctx) -> None:
    """The backbone the cell names is the one its file describes; the
    rehearsal's tiny one enters the program's registry here, under the
    rehearsal's own name. A program that lacks the backbone is left to say
    so itself (``build_backbone`` raises ``KeyError``)."""
    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS

    name, sizes = ctx.overrides["backbone"], _trunk_sizes(ctx.config["model"])
    if ctx.rehearsal:
        TRUNK_CONFIGS[name] = sizes
    if name in TRUNK_CONFIGS and dict(TRUNK_CONFIGS[name]) != sizes:
        raise ValueError(f"configuration and program disagree on {name!r}: "
                         f"{sizes} against {TRUNK_CONFIGS[name]}")


def _say_gates(ctx, pred) -> None:
    from tmr_tpu.diagnostics import drain_gate_refusals
    from tmr_tpu.ops import moe
    from tmr_tpu.ops.pallas_nms import pallas_nms_compiled_ok

    bb, batch = pred.model.backbone, int(ctx.workload["traffic"]["batch"])
    rows = batch * (int(pred.cfg.image_size) // bb.patch_size) ** 2 * bb.top_k
    ctx.say(f"formulations: moe "
            f"{moe.grouped_formulation(rows, bb.hidden, bb.expert_width, bb.dtype)}"
            f" at {rows} pairs (the others are on the compile span); "
            f"pallas_nms_compiled_ok() {pallas_nms_compiled_ok()}")
    for r in drain_gate_refusals():
        ctx.say(f"gate refusal: {r['gate']} cause {r['cause']} config "
                f"{r['config']} message {r['message'][:200]!r}")


def _identity_into_b_res(flat: dict) -> None:
    for path in flat:
        if re.search(r"hc_(attn|ffn)/b_res$", path):
            leaf = flat[path]
            flat[path] = (leaf.astype(jnp.float32)
                          + 2.0 * jnp.eye(leaf.shape[0])).astype(leaf.dtype)


def balance_routers(ctx, flat: dict, image) -> None:
    """As ``offline_predict_lm_trunk.balance_routers``: every router's
    selection bias moved until the experts' load on one image of the pool is
    even, by this cell's plain reference's own scores there, a layer at a
    time (``_even_bias``), with no part of the program under test."""
    model, worst = ctx.config["model"], []

    def rebias(path, scores):
        leaf = "backbone/" + path
        bias, before, after = _even_bias(
            np.asarray(scores), np.asarray(flat[leaf].astype(jnp.float32)),
            int(model["num_experts_per_token"]), flat[leaf].dtype)
        worst.append((round(float(before), 2), round(float(after), 2)))
        flat[leaf] = jnp.asarray(bias).astype(flat[leaf].dtype)
        return flat[leaf]

    with jax.default_matmul_precision("highest"):
        bb = _sub(flat, "backbone/")
        x = reference_xing_trunk.embed_tokens(bb, image, model)
        jax.block_until_ready(reference_xing_trunk.trunk(
            bb, x.reshape(-1, x.shape[-1]), model, rebias=rebias))
    ctx.say(f"router balance on one image: busiest expert over the mean (all"
            f" {model['router_experts']} experts), before and after, a "
            f"layer: {worst}")


def setup(ctx) -> dict:
    _agree_on_sizes(ctx)
    pred = _build_predictor(ctx)
    size = int(pred.cfg.image_size)
    _say_gates(ctx, pred)
    images, exemplars = traffic.generate(
        ctx.workload["traffic"], size, ctx.seed, ctx.workload["config"])
    ctx.say(f"traffic: pool {images.shape[:2]} images of {size} px, "
            f"{images.nbytes / 2**20:.0f} MiB on the host")

    shapes = weights.flatten(jax.eval_shape(
        pred.model.init, jax.random.key(0), jnp.zeros((1, size, size, 3)),
        jnp.asarray(exemplars[0, :1]))["params"])
    t0 = time.perf_counter()
    flat = make_weights(ctx, shapes)
    _identity_into_b_res(flat)
    t_made = time.perf_counter()
    balance_routers(ctx, flat, images[0, 0])
    ctx.say(f"router balance: {time.perf_counter() - t_made:.1f}s")
    pred.params = weights.unflatten(flat)
    n_par = sum(int(np.prod(v.shape)) for v in flat.values())
    n_bytes = sum(v.nbytes for v in flat.values())
    ctx.say(f"weights: {n_par / 1e6:.1f} M parameters, {n_bytes / 2**30:.2f} "
            f"GiB on the device, made in {len(_groups(shapes))} draws in "
            f"{t_made - t0:.1f}s")

    caps = [pred.pick_capacity(ex, size) for ex in exemplars]
    ctx.say(f"template capacities of the pool's batches: {caps}")
    for cap in sorted(set(caps)):
        p = caps.index(cap)
        for label in ("first", "second"):
            t0 = time.perf_counter()
            _collect(pred(images[p], exemplars[p]))
            ctx.say(f"warm-up, capacity {cap}, {label} call: "
                    f"{time.perf_counter() - t0:.2f}s")
    return {"pred": pred, "flat": flat, "images": images,
            "exemplars": exemplars, "caps": caps,
            "collect": _collect, "size": size,
            "feature_hw": pred.feature_hw(size),
            "rules": {"cls_threshold": float(pred.cfg.NMS_cls_threshold),
                      "iou_threshold": float(pred.cfg.NMS_iou_threshold),
                      "max_detections": int(pred.cfg.max_detections)}}


def work_per_image(ctx, state: dict) -> dict:
    """The experts' products at the pairs the program's own run-time
    counters counted (every call of this process), the forward as a whole at
    the expected ones; every expert is held, so the two agree."""
    from tmr_tpu.obs import get_registry

    model = ctx.config["model"]
    cells = _template_cells(state["exemplars"], state["feature_hw"])
    batch = int(ctx.workload["traffic"]["batch"])
    counted = get_registry().counters("trunk.moe.")
    tokens = (state["size"] // model["patch_size"]) ** 2
    pairs = tokens * counted["pairs_here"] / counted["tokens"]
    ctx.say(f"pairs an image brings to a layer's held experts: counted "
            f"{pairs:.1f}")
    return {
        "forward_flops": work_xing_trunk.forward_flops_per_image(
            model, state["size"], cells),
        "hc_mix": work_xing_trunk.hc_mix_per_image(model, state["size"],
                                                   batch),
        "mla_attn": work_xing_trunk.mla_attn_per_image(model, state["size"]),
        "moe_experts": work_xing_trunk.moe_experts_per_image(
            model, state["size"], batch, pairs),
    }


def check(ctx, state: dict, quant=None, alter=None) -> dict:
    """As ``offline_predict_lm_trunk.check``, against
    ``reference_xing_trunk``: the six detection numbers on the sampled
    images, the reference handed the experts the answer chose, and
    ``route_refused`` / ``route_moved``."""
    margins = ctx.workload["correct"]["margins"]
    rules = dict(state["rules"], **margins)
    margin = float(margins["route_margin"])
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
    per_image, moved, refused, pairs, need = [], 0, 0, 0, []
    for p, b in sorted(picks):
        t0 = time.perf_counter()
        image, exemplar = state["images"][p, b], state["exemplars"][p, b, 0]
        if quant is None:
            served = state["served"][p][b]
            # the timed program's own table, of the very batch served
            table = np.asarray(state["served"][p].routing)
            rows = table.shape[1] // batch
            theirs = list(table[:, b * rows:(b + 1) * rows])
        else:
            q_routing: list = []
            q_obj, q_reg = reference_xing_trunk.forward_dense(
                flat, image, exemplar, model, quant=quant, routing=q_routing)
            theirs = [r["experts"] for r in q_routing]
            got = reference.detect(q_obj, q_reg, exemplar,
                                   rules["cls_threshold"],
                                   rules["iou_threshold"],
                                   rules["max_detections"])
            hw = q_obj.shape[0]
            served = {"boxes": got["boxes"], "scores": got["scores"],
                      "refs": np.stack([(got["cells"] % hw) / hw,
                                        (got["cells"] // hw) / hw], -1)}
        routing: list = []
        obj, reg = reference_xing_trunk.forward_dense(
            flat, image, exemplar, model, routing=routing, follow=theirs,
            margin=margin)
        for mine, ref in zip(theirs, routing):
            refused += _moved(mine, ref["experts"])
            moved += _moved(mine, ref["own"])
            pairs += mine.size
            need.append(ref["need"])
        if alter is not None:
            served = alter(served)
        numbers = check_detections.compare_image(served, obj, reg, exemplar,
                                                 rules)
        ctx.say(f"check pool batch {p} row {b} (capacity "
                f"{state['caps'][p]}): {numbers} "
                f"[{time.perf_counter() - t0:.1f}s]")
        per_image.append(numbers)
    need = np.concatenate(need)
    steps = [m for m in _NEED_STEPS if m < 2 * margin] + [margin]
    ctx.say(f"routing: of {pairs} token-expert pairs of the sampled images "
            f"the reference, left to itself, would choose {moved} otherwise;"
            f" {refused} lie further than {margin} from its own choice and "
            f"were not taken over. Tokens that need a margin of at least m, "
            f"of {need.size} (sound only while none is refused): "
            + ", ".join(f"{m:g}: {int((need >= m).sum())}"
                        for m in sorted(set(steps)))
            + f"; the largest need {float(need.max()):.5f}")
    return dict(check_detections.merge(per_image), route_moved=moved,
                route_refused=refused)

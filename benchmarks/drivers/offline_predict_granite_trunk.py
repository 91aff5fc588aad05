"""Offline evaluation through ``Predictor.__call__`` with the Granite 4.0-H
trunk as the backbone (``tmr_tpu/models/lm_trunk.py``,
``granite4_h_small_share2``).

The window, the compiled texts and the release are ``offline_predict``'s;
the weights' draw a layer at a time, the served batch with its routing table
and the count of moved pairs are ``offline_predict_lm_trunk``'s and the gate
report ``offline_predict_xing_trunk``'s, all by import: the cell is fed and
judged as the other trunk cells are. What this file binds anew:

- the reference is ``reference_granite_trunk`` and the work
  ``work_granite_trunk`` (``check`` is ``offline_predict_lm_trunk``'s,
  written out against a reference handed to it: PERF.md section 7, "Left
  after PR 33" (f));
- the state-space leaves ``A_log`` and ``dt_bias`` are drawn by the family's
  own rule (``weights.py`` draws ``mean + std x noise`` only): ``A`` uniform
  in [1, 16], ``Delta`` log-uniform in [0.001, 0.1] through the inverse of
  the softplus, so that a head's memory spans one to a thousand patches;
- **the routers are balanced with no selection bias to move**: this router
  has none and the program gets none. The balance is made in the weights
  the benchmark draws (``balance_routers``), by the plain reference's own
  ``norm2`` outputs on the pool's first image, a layer at a time.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import (check_detections, reference, reference_granite_trunk,
                        traffic, weights, work_granite_trunk)
from benchmarks.drivers.offline_predict import (_build_predictor,
                                                _template_cells, hlo_texts,
                                                release, window)
from benchmarks.drivers.offline_predict_lm_trunk import (_NEED_STEPS,
                                                         _collect, _groups,
                                                         _moved, make_weights)
from benchmarks.drivers.offline_predict_xing_trunk import _say_gates
from benchmarks.reference import _sub

__all__ = ["setup", "window", "hlo_texts", "work_per_image", "release",
           "check"]


def _trunk_sizes(model: dict) -> dict:
    """The configuration file's sizes under the program's own names
    (``lm_trunk.TRUNK_CONFIGS``)."""
    return dict(
        hidden=model["hidden_size"],
        layers=tuple(tuple(layer) for layer in model["layers"]),
        ssm_heads=model["mamba_n_heads"], ssm_head_dim=model["mamba_d_head"],
        ssm_state=model["mamba_d_state"], ssm_groups=model["mamba_n_groups"],
        conv_size=model["mamba_d_conv"], ssm_chunk=model["ssm_chunk"],
        num_heads=model["num_heads"], kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        attn_scale=float(model["attention_multiplier"]),
        expert_width=model["intermediate_size"],
        shared_width=model["shared_intermediate_size"],
        num_experts=model["router_experts"],
        experts_held=model["experts_held"],
        top_k=model["num_experts_per_token"], router=model["router"],
        residual_multiplier=float(model["residual_multiplier"]),
        embedding_multiplier=float(model["embedding_multiplier"]))


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


def _family_rule_into_ssm(ctx, flat: dict) -> None:
    """``A_log`` and ``dt_bias`` of every state-space layer by the family's
    rule (the configuration's ``ssm_init``), from the seed and the leaf's
    name."""
    rule = ctx.config["ssm_init"]
    for path in sorted(flat):
        if not path.endswith(("attn/A_log", "attn/dt_bias")):
            continue
        leaf = flat[path]
        unit = traffic.rng_for(ctx.seed, path).random(leaf.shape)
        if path.endswith("A_log"):
            lo, hi = rule["A_uniform"]
            drawn = np.log(lo + (hi - lo) * unit)
        else:
            lo, hi = rule["delta_log_uniform"]
            delta = lo * (hi / lo) ** unit
            drawn = delta + np.log(-np.expm1(-delta))  # softplus^-1
        flat[path] = jnp.asarray(drawn, jnp.float32).astype(leaf.dtype)


#: the last step's own numbers: sweeps over the image's logits, the first
#: step in units of a column's scale, and what a sweep leaves of the step
_SWEEPS, _STEP, _KEEP = 40, 0.2, 0.93


def _loads(logits: np.ndarray, top_k: int) -> np.ndarray:
    """Pairs an expert gets, by the ``top_k`` largest logits a token."""
    idx = np.argpartition(-logits, top_k - 1, axis=1)[:, :top_k]
    return np.bincount(idx.reshape(-1),
                       minlength=logits.shape[1]).astype(np.float64)


def _even_kernel(h: np.ndarray, kernel: np.ndarray, top_k: int, dtype):
    """A router's kernel (D, E) evened on the tokens ``h`` (S, D), in three
    steps on the weights alone: the columns' component along the mean token
    is taken out, so that no expert's logit has a part common to all the
    image's tokens; each column is scaled to the mean spread (standard
    deviation over the tokens) of the logits, so that no expert's logits
    reach further than another's; and the columns' scales are moved against
    what is left of the load's unevenness, ``scale *= (load / mean)^-step``
    a sweep (a logit about 0 reaches the top more often the larger its
    scale). Rounded to the leaf's type. Also busiest / mean of the load on
    ``h`` as drawn and after each step."""
    rounded = lambda w: np.asarray(jnp.asarray(w, dtype).astype(jnp.float32))
    worst = lambda w: _loads(h @ rounded(w), top_k)
    mean = h.mean(0)
    mean = mean / np.linalg.norm(mean)
    no_common = kernel - np.outer(mean, mean @ kernel)
    spread = (h @ no_common).std(0)
    evened = no_common * (spread.mean() / spread)
    logits, scale = h @ evened, np.ones(kernel.shape[1])
    for i in range(_SWEEPS):
        now = _loads(logits * scale, top_k)
        scale = scale * ((now + 1.0) / (now.mean() + 1.0)) ** (
            -_STEP * _KEEP ** i)
    swept = evened * scale
    reached = tuple(float(ld.max() / ld.mean()) for ld in map(
        worst, (kernel, no_common, evened, swept)))
    return rounded(swept), reached


def balance_routers(ctx, flat: dict, image) -> None:
    """Even every router's load on one image of the pool, with no selection
    bias to move (this router has none, and the program gets none): the
    plain reference's trunk runs once over the image, and at each expert
    layer its own ``norm2`` outputs there reshape that layer's router kernel
    (``_even_kernel``) before the layer chooses, so the layers after it see
    tokens routed by the balanced layer. With seeded random weights the part
    of a recurrent mixer's output common to all tokens of an image sends
    every token to the same few experts, which no deployment sees: this
    family trains with a balancing loss. No part of the program under test
    takes part; program and reference are then given the same leaves."""
    model, loads = ctx.config["model"], []

    def rekernel(path, h):
        leaf = "backbone/" + path
        kernel, reached = _even_kernel(
            np.asarray(h, np.float64),
            np.asarray(flat[leaf].astype(jnp.float32), np.float64),
            int(model["num_experts_per_token"]), flat[leaf].dtype)
        loads.append(tuple(round(x, 2) for x in reached))
        flat[leaf] = jnp.asarray(kernel).astype(flat[leaf].dtype)
        return flat[leaf]

    with jax.default_matmul_precision("highest"):
        bb = _sub(flat, "backbone/")
        x = reference_granite_trunk.embed_tokens(bb, image, model)
        jax.block_until_ready(reference_granite_trunk.trunk(
            bb, x.reshape(-1, x.shape[-1]), model, rekernel=rekernel))
    ctx.say(f"router balance on one image: busiest expert over the mean (all"
            f" {model['router_experts']} experts) as drawn, with the mean "
            f"token's component out, with the spreads evened, and after the "
            f"sweeps of the scales, a layer: {loads}")


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
    _family_rule_into_ssm(ctx, flat)
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
    the expected ones."""
    from tmr_tpu.obs import get_registry

    model = ctx.config["model"]
    cells = _template_cells(state["exemplars"], state["feature_hw"])
    batch = int(ctx.workload["traffic"]["batch"])
    counted = get_registry().counters("trunk.moe.")
    tokens = (state["size"] // model["patch_size"]) ** 2
    pairs = tokens * counted["pairs_here"] / counted["tokens"]
    even = (tokens * model["num_experts_per_token"] * model["experts_held"]
            / model["router_experts"])
    ctx.say(f"pairs an image brings to a layer's held experts: counted "
            f"{pairs:.1f}, an even router {even:.1f}")
    return {
        "forward_flops": work_granite_trunk.forward_flops_per_image(
            model, state["size"], cells),
        "ssd_scan": work_granite_trunk.ssd_scan_per_image(model,
                                                          state["size"]),
        "moe_experts": work_granite_trunk.moe_experts_per_image(
            model, state["size"], batch, pairs),
    }


def check_against(ref, ctx, state: dict, quant=None, alter=None) -> dict:
    """``offline_predict_lm_trunk.check`` against the reference module
    ``ref`` (``forward_dense`` with ``routing``, ``follow``, ``margin``):
    the six detection numbers on the sampled images, the reference handed
    the experts the answer chose, and ``route_refused`` / ``route_moved``."""
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
            q_obj, q_reg = ref.forward_dense(
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
        obj, reg = ref.forward_dense(
            flat, image, exemplar, model, routing=routing, follow=theirs,
            margin=margin)
        for mine, own in zip(theirs, routing):
            refused += _moved(mine, own["experts"])
            moved += _moved(mine, own["own"])
            pairs += mine.size
            need.append(own["need"])
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
    steps += [2 * margin, 4 * margin]
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


check = functools.partial(check_against, reference_granite_trunk)

"""Optimizer + train step (reference trainer.py:208-236 + Lightning wiring).

Reference recipe: AdamW with two LR groups — backbone params at
``lr_backbone`` (0 in every published script => frozen), everything else at
``lr`` — weight decay 1e-4, global-norm grad clip 0.1 (main.py:116), and
MultiStepLR x0.1 at 60% of training when ``lr_drop`` (trainer.py:227-234).

TPU-native expression: one optax chain — clip_by_global_norm ->
multi_transform{head: adamw(sched), backbone: adamw(sched)|set_to_zero}.
``set_to_zero`` for frozen groups means frozen params carry no optimizer
state (no m/v buffers), saving HBM for the 632M-param ViT-H. FrozenBatchNorm
statistics are always in the frozen group regardless of backbone LR.

The train step is a pure jittable function; data parallelism comes from
sharding its inputs over a mesh (see tmr_tpu/parallel), not from a wrapper.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import traverse_util
from flax.training import train_state

from tmr_tpu.train.criterion import criterion
from tmr_tpu.train.targets import assign_targets


class TrainState(train_state.TrainState):
    pass


def param_labels(params: Any, frozen_backbone: bool) -> Any:
    """Label tree for multi_transform: 'head' | 'backbone' | 'frozen'.

    - everything under the top-level 'backbone' module is the backbone group
      (the reference matches parameter names on the substring 'backbone',
      trainer.py:210-225);
    - FrozenBatchNorm running statistics are always 'frozen';
    - frozen_backbone switches the whole backbone group to 'frozen'.
    """
    flat = traverse_util.flatten_dict(params)
    labels = {}
    for path in flat:
        if any(k in ("running_mean", "running_var") for k in path):
            labels[path] = "frozen"
        elif path[0] == "backbone":
            labels[path] = "frozen" if frozen_backbone else "backbone"
        else:
            labels[path] = "head"
    return traverse_util.unflatten_dict(labels)


def make_optimizer(cfg, steps_per_epoch: int) -> optax.GradientTransformation:
    accum = cfg.grad_accum_steps
    # the piecewise schedule advances once per OPTIMIZER UPDATE — under
    # MultiSteps that is once per k micro-steps, so the 60% milestone must
    # be expressed in updates, not in data steps
    updates_per_epoch = max(steps_per_epoch // max(accum, 1), 1)
    if cfg.lr_drop:
        milestone = int(cfg.max_epochs * 0.6) * updates_per_epoch
    else:
        milestone = (cfg.max_epochs + 1) * updates_per_epoch

    def sched(base):
        return optax.piecewise_constant_schedule(base, {milestone: 0.1})

    frozen_backbone = cfg.lr_backbone == 0 or cfg.backbone.endswith("_FRZ")
    transforms = {
        "head": optax.adamw(sched(cfg.lr), weight_decay=cfg.weight_decay),
        "backbone": optax.adamw(sched(cfg.lr_backbone),
                                weight_decay=cfg.weight_decay),
        "frozen": optax.set_to_zero(),
    }
    tx = optax.chain(
        optax.clip_by_global_norm(cfg.clip_max_norm),
        optax.multi_transform(
            transforms, lambda p: param_labels(p, frozen_backbone)
        ),
    )
    if accum > 1:
        # mean-accumulate k micro-step gradients, apply ONE update every k
        # steps (params are bit-identical in between) — one chip reaches the
        # reference's DDP effective batch without the memory of a big batch
        tx = optax.MultiSteps(tx, every_k_schedule=accum)
    return tx


def create_train_state(
    model, cfg, rng, sample_image, sample_exemplars, steps_per_epoch: int = 1000
) -> TrainState:
    # jitted init — eager init is op-by-op
    params = jax.jit(model.init)(rng, sample_image, sample_exemplars)["params"]
    tx = make_optimizer(cfg, steps_per_epoch)
    return TrainState.create(
        apply_fn=model.apply, params=params, tx=tx
    )


def compute_losses(
    model_out: dict,
    batch: dict,
    positive_threshold: float,
    negative_threshold: float,
    use_focal_loss: bool = False,
    scale_imgsize: bool = False,
    scale_wh_only: bool = False,
) -> dict:
    """Forward outputs + batch -> loss dict (the body of trainer.py:132-137).

    batch: image (B,S,S,3), exemplars (B,K,4), gt_boxes (B,M,4) normalized
    xyxy padded, gt_valid (B,M) bool.
    """
    ex0 = batch["exemplars"][:, 0, :]
    num_levels = len(model_out["objectness"])
    targets = []
    for lvl, obj in enumerate(model_out["objectness"]):
        h, w = obj.shape[1], obj.shape[2]
        targets.append(
            assign_targets(
                batch["gt_boxes"],
                batch["gt_valid"],
                ex0,
                h,
                w,
                positive_threshold,
                negative_threshold,
                is_last_level=(lvl == num_levels - 1),
            )
        )
    return criterion(
        model_out["objectness"],
        model_out["regressions"],
        targets,
        ex0,
        use_focal_loss=use_focal_loss,
        scale_imgsize=scale_imgsize,
        scale_wh_only=scale_wh_only,
    )


def make_train_step(model, cfg, forward_fn: Callable = None) -> Callable:
    """Build the jittable train step. Static config is closed over; the
    returned fn is (state, batch) -> (state, metrics) and is safe to wrap in
    jax.jit with sharded inputs.

    ``forward_fn(params, image, exemplars) -> model_out`` overrides the
    default ``model.apply`` forward — the pipeline-parallel step
    (parallel/pipeline.make_pp_train_step) routes the encoder through its
    GPipe island this way while sharing all the loss/containment logic."""

    if forward_fn is None:
        def forward_fn(params, image, exemplars):
            return model.apply({"params": params}, image, exemplars)

    def train_step(state: TrainState, batch: dict):
        def loss_fn(params):
            out = forward_fn(params, batch["image"], batch["exemplars"])
            losses = compute_losses(
                out,
                batch,
                cfg.positive_threshold,
                cfg.negative_threshold,
                use_focal_loss=cfg.focal_loss,
                scale_imgsize=cfg.regression_scaling_imgsize,
                scale_wh_only=cfg.regression_scaling_WH_only,
            )
            return losses["loss"], losses

        (loss, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        # failure containment the reference lacks (SURVEY §5.3: "training
        # side: none"): a non-finite loss OR any non-finite gradient leaf
        # (backward-only overflow) discards the whole step — params,
        # optimizer moments, and the schedule step all keep their previous
        # values — while the loss dict still reports the event.
        finite_leaves = [
            jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)
        ]
        ok = jnp.isfinite(loss) & jnp.all(jnp.stack(finite_leaves))
        new_state = state.apply_gradients(grads=grads)
        state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old), new_state, state
        )
        losses["skipped_nonfinite"] = (~ok).astype(jnp.float32)
        return state, losses

    return train_step


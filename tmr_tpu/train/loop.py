"""Training/eval driver (reference trainer.py Matching_Trainer + main.py run
orchestration, re-expressed as an explicit loop over jitted steps).

Covers: per-epoch training, validation every ``AP_term`` epochs
(trainer.py:68-73), the eval step chain forward -> loss -> decode -> NMS ->
per-image JSON logging (:123-153), the epoch-end metrics rendezvous
(:172-206 — process 0 merges, all processes compute, barriers around it),
multi-exemplar eval (:75-121), checkpoint best/last/resume (callbacks.py),
and CSV metric logging (the --nowandb path of main.py:113).
"""

from __future__ import annotations

import csv
import dataclasses
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tmr_tpu.data import DataLoader, build_dataset
from tmr_tpu.inference import Predictor, detections_to_numpy
from tmr_tpu.models import build_model
from tmr_tpu.train.state import (
    compute_losses,
    create_train_state,
    make_train_step,
)
from tmr_tpu.utils.checkpoint import CheckpointManager
from tmr_tpu.obs import get_registry, span
from tmr_tpu.utils.profiling import (
    PhaseTimer,
    log_info,
    log_warning,
    step_annotation,
    trace,
)
from tmr_tpu.utils.metrics import (
    coco_style_annotation_generator,
    del_img_log_path,
    get_ap_scores,
    get_mae_rmse,
    image_info_collector,
)


class CSVLogger:
    """Epoch metrics CSV. Rows have varying key sets (val metrics only on
    AP_term epochs), so the file is rewritten with the union of keys —
    never truncating earlier epochs."""

    def __init__(self, logpath: str):
        os.makedirs(logpath, exist_ok=True)
        self.path = os.path.join(logpath, "metrics.csv")
        self._rows: list = []
        if os.path.exists(self.path):  # resume: keep existing history
            with open(self.path, newline="") as f:
                self._rows = list(csv.DictReader(f))

    def log(self, row: Dict[str, float]) -> None:
        self._rows.append({k: str(v) for k, v in row.items()})
        keys = sorted({k for r in self._rows for k in r})
        with open(self.path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            for r in self._rows:
                w.writerow(r)


class Trainer:
    """Explicit train/eval driver. Single-process by default; on a mesh the
    jitted steps run sharded (see tmr_tpu.parallel) and the metrics
    rendezvous is gated on jax.process_index() == 0 like the reference's
    rank-0 gating."""

    def __init__(self, cfg, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.model = build_model(cfg, mesh=mesh)
        # --refine_box: build the SAM refiner once and hand it to the
        # Predictor, which runs decode -> refine -> NMS inside the fused
        # program (reference test-step order, trainer.py:143-150)
        refiner = refiner_params = None
        if cfg.refine_box:
            from tmr_tpu.refine import build_refiner

            refiner, refiner_params = build_refiner(cfg, seed=cfg.seed)
        self.predictor = Predictor(
            cfg, model=self.model, refiner=refiner,
            refiner_params=refiner_params,
        )
        self.logger = CSVLogger(cfg.logpath)
        self.wandb = None
        # process-0 gated like every other host-side sink (the reference's
        # WandbLogger is rank-0 only under Lightning DDP)
        if not cfg.nowandb and not cfg.eval and jax.process_index() == 0:
            from tmr_tpu.utils.wandb_logger import WandbLogger

            self.wandb = WandbLogger(
                cfg.project_name, name=os.path.basename(cfg.logpath),
                config=dataclasses.asdict(cfg),
            )
        self.ckpt = CheckpointManager(
            os.path.join(cfg.logpath, "checkpoints"),
            monitor="val/MAE" if cfg.best_model_count else "val/AP",
            mode="min" if cfg.best_model_count else "max",
            every_n_epochs=cfg.AP_term,
            # reference callbacks.py:12-13: a fresh (non-resume, non-eval,
            # single-process) training refuses to clobber an existing logpath
            fresh_guard=not cfg.resume and not cfg.eval
            and jax.process_count() == 1,
        )
        self.state = None
        self._train_step = None
        self._shared_loss_fn = None  # one closure -> one compiled program
        # device-side loss accumulator: one tiny jitted add per step instead
        # of a host float() sync (which would stall the prefetch pipeline)
        self._acc_fn = jax.jit(lambda s, l: jax.tree.map(jnp.add, s, l))
        # weighted variant for eval: batches of different sizes (ragged tail
        # split to B=1 next to full eval_batch_size batches) must contribute
        # per-image, not per-batch, to the epoch mean
        self._scale_fn = jax.jit(
            lambda l, w: jax.tree.map(lambda x: x * w, l)
        )

    # ------------------------------------------------------------ plumbing
    def _loaders(self):
        cfg = self.cfg
        train = DataLoader(
            build_dataset(cfg, "train", eval_mode=False),
            batch_size=cfg.batch_size, shuffle=True, seed=cfg.seed,
            max_gt=cfg.max_gt_boxes, max_exemplars=cfg.num_exemplars,
            num_workers=cfg.num_workers, drop_last=True,
        )
        # reference forces batch_size=1 for val/test (datamodules.py:27,47,50);
        # --eval_batch_size > 1 is the opt-in TPU throughput mode — the
        # loader already groups images by size bucket and the eval step /
        # per-image JSON collector unbatch natively. Multi-exemplar eval
        # stays at 1 (its meta plumbing is per-image).
        eval_bs = cfg.eval_batch_size if cfg.num_exemplars == 1 else 1
        if eval_bs != cfg.eval_batch_size:
            log_warning(
                f"--eval_batch_size {cfg.eval_batch_size} forced to 1: "
                "multi-exemplar eval is per-image (num_exemplars="
                f"{cfg.num_exemplars})"
            )
        val_split = "val" if cfg.dataset == "FSCD147" else "test"
        val = DataLoader(
            build_dataset(cfg, val_split),
            batch_size=eval_bs, shuffle=False, seed=cfg.seed,
            max_gt=cfg.max_gt_boxes, max_exemplars=cfg.num_exemplars,
            num_workers=cfg.num_workers,
        )
        test = DataLoader(
            build_dataset(cfg, "test"),
            batch_size=eval_bs, shuffle=False, seed=cfg.seed,
            max_gt=cfg.max_gt_boxes, max_exemplars=cfg.num_exemplars,
            num_workers=cfg.num_workers,
        )
        return train, val, test

    def _init_state(self, sample_batch, steps_per_epoch: int):
        if self.mesh is not None and "pipe" in self.mesh.shape:
            return self._init_state_pp(sample_batch, steps_per_epoch)
        self.state = create_train_state(
            self.model, self.cfg, jax.random.key(self.cfg.seed),
            jnp.asarray(sample_batch["image"]),
            jnp.asarray(sample_batch["exemplars"]),
            steps_per_epoch=steps_per_epoch,
        )
        step = make_train_step(self.model, self.cfg)
        if self.mesh is not None:
            # DDP replacement: params sharded per the TP rules (replicated on
            # a pure-data mesh), batches split over 'data'; XLA derives the
            # gradient psum from these annotations.
            from tmr_tpu.parallel import shard_params
            from tmr_tpu.parallel.sharding import state_sharding

            self.state = self.state.replace(
                params=shard_params(self.state.params, self.mesh)
            )
            sharding = state_sharding(self.state, self.mesh)
            # place the whole state as the step returns it: optimizer
            # moments left on their init placement make the second call's
            # input shardings differ from the first's, and the step
            # compiles twice (seen on the chip: 55 s + 60 s)
            self.state = jax.device_put(self.state, sharding)
            self._train_step = self._jit_step_under_mesh(step, sharding)
        else:
            self._train_step = jax.jit(step, donate_argnums=0)

    def _restage_state(self):
        """Re-place a restored state on device exactly as _init_state did.

        CheckpointManager.restore returns HOST numpy leaves by contract
        (the orbax device arrays' sharding annotations pessimize compiled
        programs — the measured 9.2x eval anomaly, utils/checkpoint.py).
        The flip side is that a restore drops the placement _init_state
        established, so resume/test must re-stage: pp stage-major sharding
        on a 'pipe' mesh, the TP/DP state sharding on any other mesh, and
        a plain one-time device_put otherwise (leaving numpy params in
        self.state would instead re-upload the whole tree on every jit
        call)."""
        if self.mesh is not None and "pipe" in self.mesh.shape:
            from tmr_tpu.parallel.pipeline import pp_state_sharding

            self.state = jax.device_put(
                self.state, pp_state_sharding(self.state, self.mesh)
            )
        elif self.mesh is not None:
            from tmr_tpu.parallel.sharding import state_sharding

            self.state = jax.device_put(
                self.state, state_sharding(self.state, self.mesh)
            )
        else:
            self.state = jax.device_put(self.state)

    def _jit_step_under_mesh(self, step, sharding):
        """jit with sharded output state + tracing under set_mesh — NOT a
        bare ``with mesh:``, which mesh-aware ops can't see: the matcher's
        data-axis shard_map island (ops/xcorr.py) discovers the mesh through
        get_abstract_mesh at trace time. The step is XLA's to partition
        (``partitioned``): over more than one device it holds no Mosaic
        kernel."""
        from tmr_tpu.parallel.compat import partitioned

        jitted = jax.jit(partitioned(step, self.mesh),
                         out_shardings=(sharding, None), donate_argnums=0)

        def step_under_mesh(state, batch, _jit=jitted, _mesh=self.mesh):
            with jax.sharding.set_mesh(_mesh):
                return _jit(state, batch)

        step_under_mesh.__wrapped__ = jitted  # for .lower()
        return step_under_mesh

    def _init_state_pp(self, sample_batch, steps_per_epoch: int):
        """Pipeline-parallel training (--mesh_pipe): stage-sharded params AND
        optimizer moments over 'pipe', GPipe encoder island in the step (the
        reference has nothing comparable — its only training parallelism is
        DDP). Eval/checkpoint interop converts to the dense layout via
        unstack_backbone_params (see eval_epoch)."""
        from tmr_tpu.parallel.pipeline import (
            create_pp_train_state,
            make_pp_train_step,
            pp_state_sharding,
        )

        self.state = create_pp_train_state(
            self.model, self.cfg, jax.random.key(self.cfg.seed),
            jnp.asarray(sample_batch["image"]),
            jnp.asarray(sample_batch["exemplars"]),
            steps_per_epoch=steps_per_epoch,
        )
        sharding = pp_state_sharding(self.state, self.mesh)
        self.state = jax.device_put(self.state, sharding)
        data_axis = "data" if self.mesh.shape.get("data", 1) > 1 else None
        step = make_pp_train_step(
            self.model, self.cfg, self.mesh,
            microbatches=self.cfg.pp_microbatches, data_axis=data_axis,
        )
        self._train_step = self._jit_step_under_mesh(step, sharding)

    def _eval_params(self, params):
        """Params as the dense layout every eval consumer expects — a no-op
        unless training runs pipeline-parallel (stacked 'stages' layout)."""
        if self.mesh is not None and "pipe" in self.mesh.shape:
            from tmr_tpu.parallel.pipeline import unstack_backbone_params

            return unstack_backbone_params(params, self.model.backbone)
        return params

    def _to_device(self, batch: dict) -> dict:
        arrays = {k: v for k, v in batch.items() if k != "meta"}
        if self.mesh is not None:
            from tmr_tpu.parallel.sharding import shard_batch

            return shard_batch(arrays, self.mesh)
        return {k: jnp.asarray(v) for k, v in arrays.items()}

    def _loss_fn(self):
        """Loss closure shared by the fused eval programs:
        (model_out, exemplars (B,K,4), gt_boxes, gt_valid) -> loss dict.
        Built once — the predictor's compile cache is keyed on the closure
        object, so a fresh closure per call would recompile."""
        if self._shared_loss_fn is not None:
            return self._shared_loss_fn
        cfg = self.cfg

        def loss_fn(out, exemplars, gt_boxes, gt_valid):
            return compute_losses(
                out,
                {"exemplars": exemplars, "gt_boxes": gt_boxes,
                 "gt_valid": gt_valid},
                cfg.positive_threshold, cfg.negative_threshold,
                use_focal_loss=cfg.focal_loss,
                scale_imgsize=cfg.regression_scaling_imgsize,
                scale_wh_only=cfg.regression_scaling_WH_only,
            )

        self._shared_loss_fn = loss_fn
        return loss_fn

    def _get_eval_step(self, capacity: int):
        """ONE forward per eval image: losses + decoded/NMS'd detections
        from the same model outputs — the reference's each_step test branch
        (trainer.py:123-153 computes loss and Get_pred_boxes from a single
        forward; running the predictor separately would double the encoder
        cost of every eval epoch). The pipeline itself lives in
        Predictor._get_fn — this only supplies the loss closure."""
        return self.predictor._get_fn(capacity, loss_fn=self._loss_fn())

    # ---------------------------------------------------------------- train
    def fit(self, max_steps_per_epoch: Optional[int] = None) -> None:
        cfg = self.cfg
        train, val, _ = self._loaders()
        steps = len(train) if max_steps_per_epoch is None else min(
            len(train), max_steps_per_epoch
        )

        start_epoch = 0
        it0 = iter(train)
        try:
            first = next(it0)
        finally:
            it0.close()  # don't leave the prefetch pool suspended
        self._init_state(first, steps)
        if cfg.resume and self.ckpt.last_path():
            self.state = self.ckpt.restore(self.ckpt.last_path(), self.state)
            self._restage_state()
            start_epoch = self.ckpt.meta["last_epoch"] + 1
            log_info(f"resumed from epoch {start_epoch}")

        for epoch in range(start_epoch, cfg.max_epochs):
            train.set_epoch(epoch)
            t0 = time.time()
            sums = None  # device-scalar pytree, fetched once per epoch
            n = 0
            # per-epoch timer; phases also open obs spans ("train.data" /
            # "train.step" / "train.metrics") when TMR_TRACE=1 so the step
            # loop lands on the same trace as serve/map
            timers = PhaseTimer(span_prefix="train.")
            # capture an xprof trace of the first post-resume epoch
            profile = cfg.profile_dir if epoch == start_epoch else None
            with trace(profile):
                it = iter(train)
                try:
                    # one-batch device prefetch: the NEXT batch's host decode
                    # + H2D transfer run while the CURRENT step computes on
                    # device (jit dispatch is async; the loss float() below
                    # is the only sync point)
                    with timers.phase("data"):
                        nxt = next(it, None)
                        nxt = self._to_device(nxt) if nxt is not None else None
                    for i in range(steps):
                        if nxt is None:
                            break
                        batch = nxt
                        with timers.phase("step"), step_annotation("train", i):
                            self.state, losses = self._train_step(
                                self.state, batch
                            )
                        with timers.phase("data"):
                            # no dead fetch past the epoch's last step
                            nxt = next(it, None) if i + 1 < steps else None
                            nxt = (
                                self._to_device(nxt)
                                if nxt is not None else None
                            )
                        with timers.phase("metrics"):
                            # accumulate ON DEVICE: the step loop has no host
                            # sync point, so compute overlaps the next batch's
                            # decode + H2D end to end (VERDICT r2 #7)
                            sums = (
                                losses if sums is None
                                else self._acc_fn(sums, losses)
                            )
                        n += 1
                finally:
                    # release the loader's worker pool + prefetch window now,
                    # not whenever the suspended generator gets GC'd
                    it.close()
            # single per-epoch device fetch of the loss sums
            sums_host = (
                {} if sums is None
                else {k: float(v) for k, v in jax.device_get(sums).items()}
            )
            row = {f"train/{k}": v / max(n, 1) for k, v in sums_host.items()}
            row["epoch"] = epoch
            row["train/sec"] = time.time() - t0
            row.update(timers.as_dict())
            # fold the epoch's phase distributions into the process-wide
            # registry (train/time/<phase> histograms) — once per timer,
            # so epochs accumulate without double-counting
            timers.to_registry(get_registry(), prefix="train/time/")

            ap_epoch = epoch == 0 or (epoch % cfg.AP_term == cfg.AP_term - 1)
            if ap_epoch:
                row.update(self.eval_epoch(val, "val", self.state.params))
            self.logger.log(row)
            if self.wandb is not None:
                self.wandb.log(row, step=epoch)
            line = f"Epoch {epoch}: | " + " | ".join(
                f"{k}: {v:.4f}" for k, v in sorted(row.items()) if k != "epoch"
            )
            # stderr protocol line: stdout stays reserved for machine-
            # readable report output (the stdout-hygiene tier-1 lint)
            log_info(line)
            self.ckpt.save_epoch(self.state, epoch, row)
        self.ckpt.wait()
        if self.wandb is not None:
            self.wandb.finish()

    # ----------------------------------------------------------------- eval
    @staticmethod
    def _split_per_image(batch: dict):
        """Ragged tail batch -> B=1 sub-batches. Each size bucket's leftover
        has its own batch dim; compiling the whole eval program once per
        leftover shape would cost a full XLA compile for a batch used once
        per epoch — B=1 is one stable extra shape instead."""
        b = batch["image"].shape[0]
        for i in range(b):
            yield {
                k: (v[i : i + 1] if k != "meta" else [v[i]])
                for k, v in batch.items()
            }

    def eval_epoch(self, loader, stage: str, params) -> Dict[str, float]:
        cfg = self.cfg
        self.predictor.params = self._eval_params(params)
        # the params live across the mesh, so every eval program is XLA's
        # to partition over it, like the train step
        from tmr_tpu.parallel.compat import partitioned

        eval_batch = partitioned(self._eval_batch, self.mesh)
        sums = None  # device-scalar pytree, fetched once per epoch
        n = 0
        # one-batch software pipeline: batch k's detections are fetched only
        # AFTER batch k+1's H2D upload and compute have been dispatched
        # (both async), so the host->device transfer — the dominant cost on
        # slow links — overlaps the previous batch's compute instead of
        # serializing with its result fetch
        # (bsz, meta, losses, dets) awaiting collection — only size + meta
        # from the host batch, so `pending` itself doesn't pin batch k's
        # image/gt arrays across the overlap (loop locals still hold the
        # current batch, so peak residency is the loader's usual window)
        pending = None

        def collect(p):
            nonlocal sums, n
            bsz, meta, losses, dets = p
            # weight each batch's losses by its size so a ragged-tail B=1
            # image doesn't weigh as much as a full batch. NB this is
            # batch-size weighting, not exact per-image parity: the
            # criterion normalizes by the batch's TOTAL positive count
            # (criterion.py), so batched losses still differ from the
            # eval_batch_size=1 aggregation — the documented caveat on
            # --eval_batch_size. Still device-side, no host sync.
            scaled = self._scale_fn(losses, jnp.float32(bsz))
            sums = scaled if sums is None else self._acc_fn(sums, scaled)
            n += bsz
            image_info_collector(
                cfg.logpath, stage, meta, detections_to_numpy(dets)
            )

        for full_batch in loader:
            b = full_batch["image"].shape[0]
            if cfg.num_exemplars == 1 and b not in (1, cfg.eval_batch_size):
                sub_batches = self._split_per_image(full_batch)
            else:
                sub_batches = [full_batch]
            for batch in sub_batches:
                with span("eval.batch", stage=stage):
                    losses, dets = eval_batch(batch)  # async dispatch
                if pending is not None:
                    collect(pending)
                pending = (
                    int(batch["image"].shape[0]), batch["meta"], losses, dets
                )
        if pending is not None:
            collect(pending)
        return self._finish_eval(stage, sums, n)

    def _eval_batch(self, batch: dict):
        cfg = self.cfg
        params = self.predictor.params
        if cfg.num_exemplars > 1:
            # one fused program: per-exemplar losses SUMMED (reference
            # trainer.py:102-104,121) + union detections
            losses, dets = self.predictor.predict_multi_exemplar(
                batch["image"], batch["meta"][0]["orig_exemplars"]
                / np.array(batch["meta"][0]["img_size"].tolist() * 2,
                           np.float32),
                loss_fn=self._loss_fn(),
                loss_args=(jnp.asarray(batch["gt_boxes"]),
                           jnp.asarray(batch["gt_valid"])),
            )
        else:
            # fused: losses + detections from one forward
            cap = self.predictor.pick_capacity(
                batch["exemplars"], int(batch["image"].shape[1])
            )
            fn = self._get_eval_step(cap)
            keys = ("image", "exemplars", "gt_boxes", "gt_valid")
            mesh = self.mesh
            if (
                mesh is not None
                and mesh.shape.get("data", 1) > 1
                and batch["image"].shape[0] % mesh.shape["data"] == 0
            ):
                # data-sharded eval: with --eval_batch_size a multiple of
                # the 'data' axis, the fused eval program runs each image
                # shard on its own devices (the reference's DDP eval
                # spreads ranks the same way; per-image JSON collection
                # and the rank-0 merge are already shard-order agnostic).
                # shard_batch device_puts host arrays straight to their
                # sharding — one transfer, same helper _to_device uses.
                from tmr_tpu.parallel.sharding import shard_batch

                sharded = shard_batch({k: batch[k] for k in keys}, mesh)
                with jax.sharding.set_mesh(mesh):
                    losses, dets = fn(
                        params, self.predictor.refiner_params,
                        *(sharded[k] for k in keys),
                    )
            else:
                losses, dets = fn(
                    params, self.predictor.refiner_params,
                    *(jnp.asarray(batch[k]) for k in keys),
                )
        return losses, dets

    def _finish_eval(self, stage: str, sums, n: int) -> Dict[str, float]:
        cfg = self.cfg
        sums_host = (
            {} if sums is None
            else {k: float(v) for k, v in jax.device_get(sums).items()}
        )
        metrics = {f"{stage}/{k}": v / max(n, 1) for k, v in sums_host.items()}

        # epoch-end rendezvous (trainer.py:181-199): process 0 merges the
        # per-image JSONs; every process computes the metrics from the files.
        if jax.process_count() > 1:  # pragma: no cover - multihost only
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("tmr_eval_pre_merge")
        if jax.process_index() == 0:
            coco_style_annotation_generator(cfg.logpath, stage)
        if jax.process_count() > 1:  # pragma: no cover
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("tmr_eval_post_merge")

        mae, rmse = get_mae_rmse(cfg.logpath, stage)
        ap, ap50, ap75 = get_ap_scores(cfg.logpath, stage)
        metrics.update(
            {f"{stage}/AP": ap, f"{stage}/AP50": ap50, f"{stage}/AP75": ap75,
             f"{stage}/MAE": mae, f"{stage}/RMSE": rmse}
        )
        if jax.process_index() == 0:
            log_info(
                f"{stage}/AP: {ap:.2f} | {stage}/AP50: {ap50:.2f} | "
                f"{stage}/AP75: {ap75:.2f} | {stage}/MAE: {mae:.2f} | "
                f"{stage}/RMSE: {rmse:.2f}"
            )
            if cfg.visualize:
                # triptychs + PR curves (log_utils.py:311-377, 447-491);
                # best-effort: visualization must never fail an eval run
                from tmr_tpu.utils.profiling import log_warning
                from tmr_tpu.utils.visualize import (
                    plot_pr_curves,
                    save_triptychs,
                )

                try:
                    save_triptychs(cfg.logpath, stage)
                    plot_pr_curves(cfg.logpath, stage)
                except Exception as e:  # pragma: no cover
                    log_warning(f"visualization failed: {e}")
            del_img_log_path(cfg.logpath, stage)
        return metrics

    def test(self, params=None) -> Dict[str, float]:
        """Eval-mode entry (reference main.py:122-130): load the best
        checkpoint unless params are given, run the test loop."""
        _, _, test = self._loaders()
        if params is None:
            if self.state is None:
                first = next(iter(test))
                self._init_state(first, steps_per_epoch=1)
            best = self.ckpt.best_path()
            if best is None:
                # mirror the reference, which fails when no checkpoint
                # resolves for --eval (callbacks.py:40-45 / main.py:124-129)
                raise FileNotFoundError(
                    f"--eval: no best_model checkpoint under "
                    f"{self.ckpt.directory}; train first or pass params"
                )
            self.state = self.ckpt.restore(best, self.state)
            self._restage_state()
            params = self.state.params
        return self.eval_epoch(test, "test", params)

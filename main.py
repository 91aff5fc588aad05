"""CLI entry point — flag-for-flag surface of the reference main.py:14-83,
plus the TPU-native knobs (--device, mesh shape, dtype).

Train:  python main.py --dataset FSCD147 --datapath ... --backbone sam \
            --emb_dim 512 --fusion --feature_upsample --lr_drop ...
Eval:   add --eval (loads the best checkpoint like reference main.py:122-130).
"""

from __future__ import annotations

import argparse
import dataclasses
import random

import numpy as np


def config_parser(argv=None):
    p = argparse.ArgumentParser(description="Matching Network (TPU-native)")

    p.add_argument("--seed", default=42, type=int)

    # logging
    p.add_argument("--project_name", type=str, default="Few-Shot Pattern Detection")
    p.add_argument("--logpath", type=str, default="./outputs/default")
    p.add_argument("--nowandb", action="store_true",
                   help="kept for parity; logging is CSV either way")
    p.add_argument("--AP_term", default=5, type=int)
    p.add_argument("--best_model_count", action="store_true")

    # dataset
    p.add_argument("--datapath", type=str, default="/home/")
    p.add_argument("--dataset", type=str, default="RPINE")
    p.add_argument("--batch_size", default=1, type=int)
    p.add_argument(
        "--eval_batch_size", default=1, type=int,
        help="batch size for val/test (reference pins 1; >1 is the TPU "
        "throughput mode, per-image outputs unchanged; forced to 1 when "
        "--num_exemplars > 1)",
    )
    p.add_argument("--num_workers", default=8, type=int)
    p.add_argument("--num_exemplars", default=1, type=int)
    p.add_argument("--image_size", default=1024, type=int)

    # training
    p.add_argument("--resume", action="store_true")
    p.add_argument("--max_epochs", default=30, type=int)
    p.add_argument("--multi_gpu", action="store_true",
                   help="parity alias for data parallelism over all devices")

    # optimizer
    p.add_argument("--weight_decay", default=1e-4, type=float)
    p.add_argument("--clip_max_norm", default=0.1, type=float)
    p.add_argument("--lr_drop", action="store_true")
    p.add_argument("--lr", default=1e-4, type=float)
    p.add_argument("--lr_backbone", default=1e-5, type=float)
    p.add_argument(
        "--grad_accum_steps", default=1, type=int,
        help="accumulate gradients over k micro-steps before one optimizer "
        "update (one chip reaches the reference's 4-GPU effective batch)",
    )

    # eval / vis
    p.add_argument("--eval", action="store_true")
    p.add_argument("--visualize", action="store_true")

    # model
    p.add_argument("--modeltype", type=str, default="matching_net")
    p.add_argument("--emb_dim", default=512, type=int)
    p.add_argument("--no_matcher", action="store_true")
    p.add_argument("--squeeze", action="store_true")
    p.add_argument("--fusion", action="store_true")
    p.add_argument("--positive_threshold", default=0.7, type=float)
    p.add_argument("--negative_threshold", default=0.7, type=float)
    p.add_argument("--NMS_cls_threshold", default=0.1, type=float)
    p.add_argument("--NMS_iou_threshold", default=0.15, type=float)
    p.add_argument("--refine_box", action="store_true")
    p.add_argument("--refiner_checkpoint", default=None, type=str,
                   help="SAM .pth for the --refine_box mask decoder "
                        "(random init with a warning when omitted)")
    p.add_argument("--ablation_no_box_regression", action="store_true")
    p.add_argument("--template_type", type=str, default="roi_align")
    p.add_argument("--feature_upsample", action="store_true")
    p.add_argument("--eval_multi_scale", action="store_true")  # parity (dead)
    p.add_argument("--regression_scaling_imgsize", action="store_true")
    p.add_argument("--regression_scaling_WH_only", action="store_true")
    p.add_argument("--focal_loss", action="store_true")

    # backbone / heads
    p.add_argument("--backbone", default="resnet50", type=str)
    p.add_argument("--encoder", default="original", type=str)
    p.add_argument("--dilation", default=True)
    p.add_argument("--decoder_num_layer", default=1, type=int)
    p.add_argument("--decoder_kernel_size", default=3, type=int)

    # TPU-native additions
    p.add_argument("--device", default="tpu", type=str,
                   help="'tpu' (default) or 'cpu'")
    p.add_argument("--mesh_data", default=-1, type=int,
                   help="data-parallel mesh size (-1: all devices)")
    p.add_argument("--mesh_model", default=1, type=int,
                   help="tensor-parallel mesh size for the ViT")
    p.add_argument("--mesh_seq", default=1, type=int,
                   help="sequence/context-parallel mesh size: global "
                        "attention blocks run ring attention over this axis")
    p.add_argument("--mesh_pipe", default=1, type=int,
                   help="pipeline-parallel stages (GPipe over a 'pipe' "
                        "axis); must equal the backbone's global-attention "
                        "block count (4 for vit_b/vit_h). Composes with "
                        "--mesh_data only; use the same value for --resume/"
                        "--eval of a pp-trained run (checkpoints store the "
                        "stage-major layout)")
    p.add_argument("--pp_microbatches", default=0, type=int,
                   help="GPipe microbatches (0: one per stage)")
    p.add_argument("--compute_dtype", default="bfloat16", type=str)
    p.add_argument("--max_detections", default=2000, type=int,
                   help="fixed detection-slot capacity of the fused decode/"
                        "refine/NMS program (AP maxDets tops out at 1100)")
    p.add_argument("--profile_dir", default=None, type=str,
                   help="capture an XLA profiler trace of the first epoch "
                        "into this directory (TensorBoard/xprof)")
    p.add_argument("--remat_backbone", action="store_true",
                   help="gradient-checkpoint the ViT blocks (activation "
                        "memory ~1/depth for one extra forward)")
    p.add_argument("--autotune", action="store_true",
                   help="microbenchmark kernel formulations (x-corr "
                        "lowering, decoder tail) on this device at "
                        "the run's shapes and use the winners (TPU only)")

    args = p.parse_args(argv)
    return args


def to_config(args):
    from tmr_tpu.config import Config

    fields = {f.name for f in dataclasses.fields(Config)}
    kw = {k: v for k, v in vars(args).items() if k in fields}
    kw["dilation"] = bool(args.dilation)
    return Config(**kw)


#: inference-only quantization knobs a TRAINING run must never inherit:
#: fake_quant's rounding has (near-)zero gradient, and a stored-int8
#: param tree (TMR_QUANT_STORAGE) must never exist on the training side
#: at all — optimizer updates on an int8 leaf are meaningless. One
#: list so the scrub and its test can never drift.
_TRAINING_SCRUB_KNOBS = ("TMR_QUANT", "TMR_QUANT_STORAGE")


def scrub_training_env(environ=None) -> list:
    """Strip the inference-only quantization knobs from ``environ``
    (default ``os.environ``) before a training run traces anything —
    the invariant enforced at the consumption point, not just at
    autotune election (a sourced TMR_AUTOTUNE_EXPORT file can set them).
    Returns the knobs that were scrubbed, for logging/tests."""
    import os

    env = os.environ if environ is None else environ
    scrubbed = []
    for knob in _TRAINING_SCRUB_KNOBS:
        if env.get(knob, "off") not in ("", "off"):
            env[knob] = "off"
            scrubbed.append(knob)
    return scrubbed


def main(argv=None):
    args = config_parser(argv)

    from tmr_tpu.utils.cache import enable_compilation_cache, select_device

    select_device(args.device)
    enable_compilation_cache()

    # seed_everything (reference main.py:86)
    random.seed(args.seed)
    np.random.seed(args.seed)

    cfg = to_config(args)

    from tmr_tpu.parallel import make_mesh
    from tmr_tpu.train.loop import Trainer

    mesh = None
    if args.mesh_pipe > 1:
        if args.mesh_model > 1 or args.mesh_seq > 1:
            raise SystemExit(
                "--mesh_pipe composes with --mesh_data only (tp/sp inside a "
                "pipeline mesh is not supported)"
            )
        mesh = make_mesh(
            (args.mesh_data, args.mesh_pipe), axis_names=("data", "pipe")
        )
    elif args.multi_gpu or args.mesh_model > 1 or args.mesh_seq > 1:
        if args.mesh_seq > 1:
            mesh = make_mesh((args.mesh_data, args.mesh_model, args.mesh_seq))
        else:
            mesh = make_mesh((args.mesh_data, args.mesh_model))

    if args.autotune:
        from tmr_tpu.utils.autotune import autotune
        from tmr_tpu.utils.profiling import log_info

        # tune at the PER-DEVICE shape the run will actually compile: the
        # eval batch under --eval (mirrors the loop's num_exemplars forcing
        # AND its data-sharded eval split when the 'data' axis divides it),
        # else the per-device train batch after data-parallel sharding
        if cfg.eval:
            tune_batch = cfg.eval_batch_size if cfg.num_exemplars == 1 else 1
            dp = mesh.shape.get("data", 1) if mesh is not None else 1
            if dp > 1 and tune_batch % dp == 0:
                tune_batch //= dp
        else:
            dp = mesh.shape.get("data", 1) if mesh is not None else 1
            tune_batch = max(cfg.batch_size // max(dp, 1), 1)
        # precision relaxation is justified for inference score ranking
        # only — training must not inherit bf16-rounded matcher gradients.
        # train=True times the sweeps fwd+bwd (backward cost ranks
        # formulations differently) and caches under a separate key.
        autotune(cfg, cfg.image_size, tune_batch, log=log_info,
                 tune_precision=bool(cfg.eval), train=not cfg.eval)

    import os

    if not cfg.eval:
        # quantized weights (and stored-int8 trees) are inference-only:
        # fake_quant's rounding has (near-)zero gradient, so a training
        # trace inheriting int8 (e.g. from a sourced TMR_AUTOTUNE_EXPORT
        # file) would train the decoder against a quantization-noise
        # floor — and an int8 STORAGE leaf must never reach an optimizer.
        scrubbed = scrub_training_env()
        if scrubbed:
            from tmr_tpu.utils.profiling import log_info

            log_info(f"{'/'.join(scrubbed)} ignored for training "
                     "(inference-only knobs); running exact weights")
    if not cfg.eval and os.environ.get("TMR_DECODER_IMPL") == "fused":
        # unlike int8 the fused tail is gradient-valid and oracle-pinned,
        # so an explicit pin is honored — but its election evidence is
        # forward-only (autotune sweeps it for inference runs only), so a
        # pin inherited from a sourced TMR_AUTOTUNE_EXPORT file deserves
        # a visible notice before it shapes the training program
        from tmr_tpu.utils.profiling import log_info

        log_info("TMR_DECODER_IMPL=fused pinned for training: backward "
                 "cost was never swept (inference-only election); unset "
                 "to use the XLA module stack")
    trainer = Trainer(cfg, mesh=mesh)
    if cfg.eval:
        trainer.test()
    else:
        trainer.fit()


if __name__ == "__main__":
    main()

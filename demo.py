"""Interactive few-shot detection demo (reference demo.py).

Draw 1-3 exemplar boxes on an image; the detector finds every other instance
of the pattern. The reference is a gradio Blocks app around an
``Inference`` wrapper (demo.py:53-150: preprocess -> per-exemplar forward +
decode -> concat -> optional SAM refinement -> NMS -> cv2 box drawing);
here the same pipeline is a headless :class:`DemoEngine` driving the
bucketed-jit :class:`tmr_tpu.inference.Predictor` (the whole model+decode+NMS
chain is one XLA program per bucket), with the gradio UI as an optional shell
around it (gradio isn't a framework dependency — the engine is fully usable
from Python/tests without it).

Like the reference demo (demo.py:28-35), defaults differ from the eval
scripts: NMS_cls_threshold 0.7, NMS_iou_threshold 0.5, pos/neg 0.5, fusion.

Usage:
  python demo.py --ckpt outputs/FSCD147/checkpoints/best_model-v0 \
      [--backbone sam_vit_b] [--device tpu] [--share]
  # headless single-shot:
  python demo.py --image img.jpg --exemplar 100,120,180,200 --out pred.png
"""

from __future__ import annotations

import argparse
import os

import numpy as np


def draw_boxes(image_rgb: np.ndarray, boxes_norm: np.ndarray,
               max_width: int = 1024) -> "object":
    """cv2 rectangles on a <=1024-wide copy (demo.py:137-150). ``boxes_norm``
    is (N, 4) xyxy in [0,1]. Returns a PIL image."""
    import cv2
    from PIL import Image

    img = np.asarray(image_rgb)[..., :3].copy()
    H, W = img.shape[:2]
    r = max_width / W
    img = cv2.resize(img, (int(W * r), int(H * r)))
    for box in np.asarray(boxes_norm).reshape(-1, 4):
        x1, y1, x2, y2 = box
        pt1 = (int(x1 * W * r), int(y1 * H * r))
        pt2 = (int(x2 * W * r), int(y2 * H * r))
        img = cv2.rectangle(img, pt1, pt2, (255, 0, 0), 2)
    return Image.fromarray(img)


class DemoEngine:
    """Headless demo pipeline: image + pixel exemplar boxes -> detections +
    visualization. The reference Inference module (demo.py:53-150) minus
    gradio."""

    def __init__(self, cfg, params=None, model=None, refiner=None,
                 refiner_params=None):
        from tmr_tpu.inference import Predictor

        self.cfg = cfg
        self.predictor = Predictor(cfg, params=params, model=model,
                                   refiner=refiner,
                                   refiner_params=refiner_params)

    def attach_refiner(self, checkpoint: str = None, seed: int = 0):
        """Build the SAM box refiner once (vs. the reference's per-image
        PromptEncoder rebuild, box_refine.py:207). With ``checkpoint``,
        weights convert from the SAM .pth; else random init (smoke)."""
        import dataclasses

        from tmr_tpu.refine import build_refiner

        cfg = dataclasses.replace(self.cfg, refiner_checkpoint=checkpoint)
        refiner, rparams = build_refiner(cfg, seed=seed)
        self.predictor.refiner = refiner
        self.predictor.refiner_params = rparams

    def init_params(self, seed: int = 0):
        self.predictor.init_params(seed=seed, image_size=self.cfg.image_size)

    def load_checkpoint(self, path: str):
        """Restore model params from an orbax checkpoint directory — either a
        full TrainState saved by the CheckpointManager or a bare params tree.
        A training logpath's ``checkpoints/`` parent (containing
        ckpt_meta.json) resolves to its best version automatically, so
        ``--ckpt <logpath>/checkpoints`` works like the reference demo's
        --ckpt best_model.ckpt (demo.py:154-155); only model params are
        read, optimizer state (if present) is ignored."""
        import json

        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        meta_path = os.path.join(path, "ckpt_meta.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            v = meta.get("best_version", -1)
            path = os.path.join(
                path, f"best_model-v{v}" if v >= 0 else "last"
            )
        tree = ocp.StandardCheckpointer().restore(path)
        self.predictor.params = tree.get("params", tree)

    def infer(self, image_rgb: np.ndarray, exemplars_px, refine: bool = False):
        """image_rgb: (H, W, 3) uint8; exemplars_px: (K, 4) pixel xyxy.
        Returns (pred PIL image, boxes_norm (N,4), scores (N,)). Per-exemplar
        forwards + union NMS (demo.py:111-130) run through
        predict_multi_exemplar."""
        from tmr_tpu.data.transforms import resize_normalize

        h, w = np.asarray(image_rgb).shape[:2]
        scale = np.array([w, h, w, h], np.float32)
        ex_norm = np.asarray(exemplars_px, np.float32).reshape(-1, 4) / scale

        x = resize_normalize(image_rgb, self.cfg.image_size)[None]
        self.cfg.refine_box = bool(refine) and (
            self.predictor.refiner is not None
        )
        dets = self.predictor.predict_multi_exemplar(x, ex_norm)
        valid = np.asarray(dets["valid"][0])
        boxes = np.asarray(dets["boxes"][0])[valid]
        scores = np.asarray(dets["scores"][0])[valid]
        return draw_boxes(image_rgb, boxes), boxes, scores


def demo_config(args):
    from tmr_tpu.config import Config

    return Config(
        backbone=args.backbone, emb_dim=512, fusion=True,
        template_type="roi_align", feature_upsample=True,
        positive_threshold=0.5, negative_threshold=0.5,
        NMS_cls_threshold=args.NMS_cls_threshold,
        NMS_iou_threshold=args.NMS_iou_threshold,
        image_size=args.image_size,
    )


def launch_gradio(engine: "DemoEngine", share: bool = False):
    """The gradio Blocks shell (demo.py:152-195). Gradio is optional; this
    raises with instructions when it isn't installed."""
    try:
        import gradio as gr
    except ImportError as e:  # pragma: no cover - env without gradio
        raise SystemExit(
            "gradio is not installed in this environment. Use the headless "
            "mode instead:\n  python demo.py --image img.jpg "
            "--exemplar x1,y1,x2,y2 --out pred.png"
        ) from e

    def run(image, boxes_text, refine):
        if image is None:
            return None, "upload an image first"
        try:
            ex = [
                [float(v) for v in line.replace(",", " ").split()]
                for line in boxes_text.strip().splitlines() if line.strip()
            ]
            if not ex or any(len(b) != 4 for b in ex):
                return None, ("give 1-3 exemplar boxes as `x1,y1,x2,y2` "
                              "pixel coords, one per line")
        except ValueError:
            return None, "could not parse the exemplar boxes"
        pred, boxes, scores = engine.infer(np.asarray(image), ex, refine)
        return pred, f"{len(boxes)} detections"

    with gr.Blocks(title="TMR-TPU Few-Shot Pattern Detection") as app:
        gr.Markdown("# Few-Shot Pattern Detection (TPU)\n"
                    "Upload an image, give 1-3 exemplar boxes "
                    "(`x1,y1,x2,y2` pixels, one per line), run.")
        with gr.Row():
            inp = gr.Image(type="numpy", label="Query image")
            out = gr.Image(type="pil", label="Prediction")
        boxes_text = gr.Textbox(label="Exemplar boxes (px)",
                                placeholder="100,120,180,200")
        refine = gr.Checkbox(label="SAM box refinement", value=False)
        count = gr.Textbox(label="Count")
        gr.Button("Run").click(run, [inp, boxes_text, refine], [out, count])
    app.launch(share=share)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt", default=None, help="orbax checkpoint dir")
    p.add_argument("--backbone", default="sam_vit_b")
    p.add_argument("--image_size", default=1024, type=int)
    # demo defaults intentionally differ from eval scripts (demo.py:28-35)
    p.add_argument("--NMS_cls_threshold", default=0.7, type=float)
    p.add_argument("--NMS_iou_threshold", default=0.5, type=float)
    p.add_argument("--device", default="tpu")
    p.add_argument("--share", action="store_true")
    p.add_argument("--refine_box", action="store_true",
                   help="enable SAM box refinement (builds the refiner; "
                        "give --refiner_checkpoint for real weights)")
    p.add_argument("--refiner_checkpoint", default=None)
    # headless mode
    p.add_argument("--image", default=None, help="run once on this image")
    p.add_argument("--exemplar", action="append", default=None,
                   help="x1,y1,x2,y2 pixel box (repeatable)")
    p.add_argument("--out", default="prediction.png")
    args = p.parse_args(argv)

    from tmr_tpu.utils.cache import enable_compilation_cache, select_device

    select_device(args.device)
    enable_compilation_cache()

    engine = DemoEngine(demo_config(args))
    if args.ckpt:
        engine.load_checkpoint(args.ckpt)
    else:
        print("no --ckpt: running with random weights (smoke mode)")
        engine.init_params()
    if args.refine_box:
        engine.attach_refiner(args.refiner_checkpoint)

    if args.image:
        from PIL import Image

        img = np.asarray(Image.open(args.image).convert("RGB"))
        ex = [[float(v) for v in e.split(",")] for e in (args.exemplar or [])]
        if not ex:
            raise SystemExit("--image needs at least one --exemplar")
        pred, boxes, scores = engine.infer(img, ex, refine=args.refine_box)
        pred.save(args.out)
        print(f"{len(boxes)} detections -> {args.out}")
        return

    launch_gradio(engine, share=args.share)


if __name__ == "__main__":
    main()

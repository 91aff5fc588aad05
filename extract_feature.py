"""Single-image SAM-encoder feature extraction + statistics CLI.

TPU-native rebuild of the reference ``extract_feature.py:12-123``: load an
image, SAM-style preprocess (resize longest side to 1024, SAM pixel-stat
normalize, zero-pad — extract_feature.py:50-64), run the frozen encoder,
compute the 4 scientific statistics (mean / std / max / sparsity = fraction
<= 0, :78-82), print the analysis table with the rule-based Easy/Hard verdict
(thresholds 0.0130 / 0.0137, :95-100), and dump the features as
``<name>_feature.npy`` (:107-118). Falls back to a synthesized dummy image
when the requested file is missing (:116-121).

Usage:
  python extract_feature.py [image.jpg] [--output_dir feature]
      [--backbone sam_vit_b|sam_vit_h] [--checkpoint sam_hq_vit_b.pth]
      [--artifact exported/encoder.stablehlo] [--device tpu|cpu]
"""

from __future__ import annotations

import argparse
import os

import numpy as np

HARD_THRESHOLD = 0.0130  # extract_feature.py:96
EASY_THRESHOLD = 0.0137  # extract_feature.py:98


def analyze(features: np.ndarray) -> dict:
    """The 4 statistics of extract_feature.py:78-82 (exact mapper parity:
    sparsity counts elements <= 0)."""
    f = np.asarray(features, np.float32)
    return {
        "mean": float(f.mean()),
        "std": float(f.std()),
        "max": float(f.max()),
        "sparsity": float((f <= 0).mean()),
    }


def verdict(mean: float) -> str:
    """Rule-based verdict (extract_feature.py:95-100)."""
    if mean < HARD_THRESHOLD:
        return "HARD (low information)"
    if mean > EASY_THRESHOLD:
        return "EASY/NORMAL"
    return "MEDIUM"


def load_or_dummy(image_path: str) -> tuple[np.ndarray, str]:
    """Read the image; synthesize a 720x1280 dummy when absent
    (extract_feature.py:116-121)."""
    if os.path.exists(image_path):
        from PIL import Image

        return np.asarray(Image.open(image_path).convert("RGB")), image_path
    print(f"[1/4] {image_path} not found -> using a synthesized test image")
    return np.zeros((720, 1280, 3), np.uint8), "test_image.jpg"


def run_extraction_and_analyze(
    image_path: str,
    output_dir: str = "feature",
    backbone: str = "sam_vit_b",
    checkpoint: str | None = None,
    artifact: str | None = None,
    model=None,
    params=None,
    image_size: int = 1024,
) -> dict:
    """Full pipeline; returns the stats dict (also printed). ``model``/
    ``params`` may be injected (tests, preloaded weights); ``artifact`` runs
    a serialized exported encoder instead of building the model."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.data.transforms import sam_longest_side_preprocess

    image, image_path = load_or_dummy(image_path)
    print(f"[2/4] preprocessing {image_path} "
          f"({image.shape[1]}x{image.shape[0]})")
    x = sam_longest_side_preprocess(image, target=image_size)[None]

    print(f"[3/4] encoding on {jax.devices()[0].platform}")
    if artifact is not None:
        from tmr_tpu.utils.export import load_exported

        feats = load_exported(artifact)(jnp.asarray(x))
    else:
        if (model is None) != (params is None):
            raise ValueError("pass model and params together (or neither)")
        if model is None:
            from tmr_tpu.models import build_sam_encoder

            if not checkpoint:
                print("      no checkpoint: random weights (stats are still "
                      "well-defined, like the reference without weights)")
            model, params = build_sam_encoder(backbone, checkpoint, image_size)
        feats = jax.jit(
            lambda p, v: model.apply({"params": p}, v)
        )(params, jnp.asarray(x))

    feats = np.asarray(feats, np.float32)
    stats = analyze(feats)

    print("=" * 60)
    print(f" FEATURE ANALYSIS: {os.path.basename(image_path)}")
    print("=" * 60)
    print(f" 1. AVG ACTIVATION : {stats['mean']:.6f}")
    print(f" 2. STD            : {stats['std']:.6f}")
    print(f" 3. MAX CONFIDENCE : {stats['max']:.6f}")
    print(f" 4. SPARSITY       : {stats['sparsity'] * 100:.2f}%")
    print("-" * 60)
    print(f" => VERDICT: {verdict(stats['mean'])}")
    print("=" * 60)

    os.makedirs(output_dir, exist_ok=True)
    base = os.path.basename(image_path).split(".")[0]
    save_path = os.path.join(output_dir, f"{base}_feature.npy")
    np.save(save_path, feats)
    print(f"[4/4] saved features to {save_path}")
    stats["save_path"] = save_path
    return stats


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("image", nargs="?", default="demo/1.jpg")
    p.add_argument("--output_dir", default="feature")
    p.add_argument("--backbone", default="sam_vit_b",
                   help="sam_vit_b | sam_vit_h | sam (alias for vit_h)")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--artifact", default=None,
                   help="serialized encoder from export_encoder.py")
    p.add_argument("--image_size", default=1024, type=int)
    p.add_argument("--device", default="tpu")
    args = p.parse_args(argv)
    from tmr_tpu.utils.cache import enable_compilation_cache, select_device

    select_device(args.device)
    enable_compilation_cache()
    run_extraction_and_analyze(
        args.image, args.output_dir, args.backbone, args.checkpoint,
        args.artifact, image_size=args.image_size,
    )


if __name__ == "__main__":
    main()

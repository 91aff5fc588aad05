"""Produce the benchmark checkpoint: quickstart-train the flagship config.

Trains the bench's exact model (SAM ViT-B backbone, 512-d matcher, fusion —
bench.py's preset) on the synthetic quickstart fixture (data/synthetic.py)
and saves a PARAMS-ONLY orbax checkpoint; point bench.py at it explicitly
via ``TMR_BENCH_CKPT=<out>/params`` (there is deliberately NO default-path
auto-detect — the random-weights headline must stay a random-weights
measurement). This
closes the "random weights" asterisk on the bench metric: the measured
program then runs checkpoint-restored, post-training activations.

Params are resolution-independent (pos-embed/rel-pos interpolate), so
training at a smaller --image_size than the benched 1024 is valid and much
cheaper; the backbone is frozen (lr_backbone 0, the reference recipe), so
training shapes the detector head on real gradient signal.

``--epochs 0`` skips training and saves the initializer output — a fast
plumbing mode for tests.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--image_size", default=256, type=int)
    p.add_argument("--epochs", default=2, type=int)
    p.add_argument("--batch_size", default=2, type=int)
    p.add_argument("--n_train", default=8, type=int)
    p.add_argument("--out", default=os.path.join(REPO, "bench_ckpt"))
    p.add_argument("--compute_dtype", default="bfloat16")
    args = p.parse_args(argv)

    import jax
    import orbax.checkpoint as ocp

    from tmr_tpu.config import preset
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()

    out = os.path.abspath(os.path.join(args.out, "params"))
    with tempfile.TemporaryDirectory() as tmp:
        fixture = os.path.join(tmp, "data")
        cfg = preset(
            "TMR_FSCD147",
            backbone="sam_vit_b",
            image_size=args.image_size,
            compute_dtype=args.compute_dtype,
            batch_size=args.batch_size,
            datapath=fixture,
            logpath=os.path.join(tmp, "log"),
            max_epochs=args.epochs,
            AP_term=max(args.epochs, 1),  # one val pass at the cadence end
            num_workers=0,
            nowandb=True,
        )
        if args.epochs <= 0:
            from tmr_tpu.inference import Predictor

            predictor = Predictor(cfg)
            predictor.init_params(seed=0, image_size=args.image_size)
            params = predictor.params
        else:
            from tmr_tpu.data.synthetic import write_synthetic_fscd147
            from tmr_tpu.train.loop import Trainer

            write_synthetic_fscd147(
                fixture, n_train=args.n_train, n_val=2
            )
            trainer = Trainer(cfg)
            trainer.fit()
            params = trainer.state.params

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(out, params, force=True)
        ckptr.wait_until_finished()
    print(f"bench checkpoint saved: {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

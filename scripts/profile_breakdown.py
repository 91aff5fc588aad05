"""Per-component timing breakdown of the flagship inference program.

Times the dominant stages of the fused FSCD-147 eval program in isolation —
the full program, the SAM ViT-B backbone, one global- and one windowed-
attention block at real dims, the matcher x-corr at two capacity buckets,
the decode+NMS tail, and the two 1024-channel decoder conv stacks + heads
on the upsampled 128^2 grid (``decoder_heads`` — the post-attention budget
PERF.md lists as the never-measured remaining candidate) — with the SAME
methodology as bench.py (PERF.md Finding 1):
device-staged inputs, iterations chained through a scalar data dependency
inside each jitted program, one closing fetch, measured RTT floor
subtracted (the first `benchmark` PR replaces this method, ROADMAP Speed
item 1).

Run on the real TPU:   python scripts/profile_breakdown.py
Prints a JSON breakdown {stage: seconds_per_iteration}.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BATCH = int(os.environ.get("TMR_BENCH_BATCH", 4))
SIZE = int(os.environ.get("TMR_BENCH_SIZE", 1024))
CHAIN = int(os.environ.get("TMR_BENCH_CHAIN", 10))


def _progress(msg: str) -> None:
    """Stage marker on stderr, flushed: cold-cache compiles take minutes
    end-to-end, and without these lines a slow run is indistinguishable
    from a hung one."""
    print(f"[profile] {msg}", file=sys.stderr, flush=True)


def _rtt() -> float:
    from tmr_tpu.utils.profiling import measure_rtt_floor

    return measure_rtt_floor()


def chained(fn, *args, rtt: float = 0.0) -> float:
    """fn(*args, fb) -> (out, fb'): chained sec/iter with the RTT removed
    (the shared utils/profiling.py harness at this script's CHAIN count)."""
    from tmr_tpu.utils.profiling import chained_seconds_per_iter

    return chained_seconds_per_iter(fn, *args, iters=CHAIN, rtt=rtt)


def attributed(fn, *args, rtt: float = 0.0) -> dict:
    """Device-attributed split of one stage step (obs/devtime.py): a few
    blocking calls separating host dispatch (``dispatch_s``) from
    post-dispatch device execution (``device_s``, RTT floor removed) —
    the chained wall numbers above deliberately conflate the two, which
    is right for throughput but wrong for 'where did the time go'."""
    from tmr_tpu.obs.devtime import attribute_call

    fb0 = jnp.zeros((), jnp.float32)
    rec = attribute_call(lambda: fn(*args, fb0), iters=3, rtt=rtt)
    return {k: (round(v, 5) if isinstance(v, float) else v)
            for k, v in rec.items()}


def main():
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor
    from tmr_tpu.models.vit import Block
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    cfg = preset(
        "TMR_FSCD147", backbone="sam_vit_b", image_size=SIZE,
        compute_dtype="bfloat16", batch_size=BATCH,
    )
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=SIZE)
    params = pred.params
    rng = np.random.default_rng(0)
    image = jnp.asarray(
        rng.standard_normal((BATCH, SIZE, SIZE, 3)), jnp.float32
    )
    exemplars = jnp.tile(
        jnp.asarray([[[0.45, 0.45, 0.53, 0.55]]], jnp.float32), (BATCH, 1, 1)
    )
    _progress("measuring rtt floor")
    rtt = _rtt()
    report = {"rtt_floor_ms": round(rtt * 1000, 1)}

    # device-attributed seconds per stage ride alongside the chained
    # wall numbers (see `attributed`): {stage: {dispatch_s, device_s,
    # wall_s}} — stage numbers stop conflating host dispatch with
    # device execution
    report["devtime"] = {}

    # 1. full fused program (the production pipeline via its bench hook)
    _progress("stage 1: full fused program")
    fused = pred._get_fn(17, chain_feedback=True)
    step1 = lambda im, ex, fb: fused(params, None, im, ex, fb)  # noqa: E731
    report["full_program"] = chained(step1, image, exemplars, rtt=rtt)
    report["devtime"]["full_program"] = attributed(
        step1, image, exemplars, rtt=rtt
    )
    _progress(f"full_program: {report['full_program']*1000:.2f} ms")

    # 2. backbone alone (chained through the feature sum)
    bb = pred.model.backbone
    bb_params = params["backbone"]

    _progress("stage 2: backbone alone")

    @jax.jit
    def bb_step(p, im, fb):
        f = bb.apply({"params": p}, im + fb)
        return f, jnp.sum(f).astype(jnp.float32) * 0.0

    step2 = lambda im, fb: bb_step(bb_params, im, fb)  # noqa: E731
    report["backbone"] = chained(step2, image, rtt=rtt)
    report["devtime"]["backbone"] = attributed(step2, image, rtt=rtt)
    _progress(f"backbone: {report['backbone']*1000:.2f} ms")

    # 3. one global vs one windowed transformer block (768-d, real grid)
    grid = SIZE // 16
    tokens = jnp.asarray(
        rng.standard_normal((BATCH, grid, grid, 768)), jnp.bfloat16
    )
    # each block runs what ops/pallas_attn.global_formulation /
    # window_formulation answer here (no knob); the global row is labelled
    # with the answer
    from tmr_tpu.ops.pallas_attn import global_formulation

    glob = global_formulation((grid, grid), 12, 64, jnp.bfloat16)
    for label, win in ((f"one_global_block_{glob}", 0),
                       ("one_windowed_block", 14)):
        _progress(f"stage 3: {label}")
        blk = Block(num_heads=12, window_size=win,
                    rel_pos_size=(grid, grid), dtype=jnp.bfloat16)
        bp = jax.jit(blk.init)(jax.random.key(1), tokens)["params"]

        @jax.jit
        def blk_step(p, x, fb):
            y = blk.apply({"params": p}, x + fb.astype(x.dtype))
            return y, jnp.sum(y).astype(jnp.float32) * 0.0

        report[label] = chained(
            lambda x, fb: blk_step(bp, x, fb), tokens, rtt=rtt
        )
        _progress(f"{label}: {report[label]*1000:.2f} ms")

    # 4. matcher x-corr on the upsampled grid: every formulation at the
    # production capacity (TMR_XCORR_IMPL, read at trace time — ops/xcorr.py)
    # plus the default big-template path at 127
    from tmr_tpu.ops.xcorr import match_templates
    from tmr_tpu.utils.autotune import _restore

    up_hw = pred.feature_hw(SIZE)
    proj = jnp.asarray(
        rng.standard_normal((BATCH, cfg.emb_dim, up_hw, up_hw)), jnp.float32
    )
    ex0 = exemplars[:, 0, :]
    prev_xc = os.environ.get("TMR_XCORR_IMPL")
    prev_pr = os.environ.get("TMR_XCORR_PRECISION")
    try:
        for cap, impl, prec in (
            (17, "conv", "highest"), (17, "conv", "default"),
            (17, "conv", "bf16"), (17, "vmap", "highest"),
            (17, "vmap", "default"), (17, "vmap", "bf16"),
            (17, "fft", "highest"),
            (17, "pallas", "highest"), (17, "convnhwc", "highest"),
            (127, "auto", "highest"),
        ):
            _progress(f"stage 4: xcorr cap={cap} impl={impl} prec={prec}")
            os.environ["TMR_XCORR_IMPL"] = impl
            os.environ["TMR_XCORR_PRECISION"] = prec

            @jax.jit
            def xc_step(f, e, fb):
                y = match_templates(f + fb, e, capacity=cap)
                return y, jnp.sum(y) * 0.0

            label = f"xcorr_cap{cap}" + ("" if impl == "auto" else f"_{impl}")
            if prec != "highest":
                label += f"_{prec}"
            report[label] = chained(
                lambda f, e, fb: xc_step(f, e, fb), proj, ex0, rtt=rtt
            )
            _progress(f"{label}: {report[label]*1000:.2f} ms")
    finally:
        _restore(prev_xc, "TMR_XCORR_IMPL")
        _restore(prev_pr, "TMR_XCORR_PRECISION")

    # 5 + 6. the post-attention tail stages, via the SHARED stage
    # programs in utils/stage_bench — one definition feeds this
    # breakdown, bench.py's per-round ``stage_breakdown`` record, and the
    # autotune sweeps electing TMR_DECODER_IMPL / TMR_QUANT, so the three
    # surfaces can never measure different programs. Both builders read
    # the tail knobs (TMR_DECODER_IMPL, TMR_QUANT, TMR_DECODE_TAIL) at
    # trace time exactly like production: pin a knob and re-run the
    # breakdown to time that formulation — the fused-vs-xla /
    # int8-vs-exact / device-vs-host deltas the MFU push is after. The
    # decode-tail rationale (exemplar-sized synthetic boxes so the greedy
    # NMS suppression chains run production-deep) lives with the builder.
    from tmr_tpu.inference import decode_tail_mode
    from tmr_tpu.ops.fused_heads import decoder_impl
    from tmr_tpu.utils.stage_bench import (
        build_decode_tail_step,
        build_decoder_tail_step,
    )

    _progress("stage 5: decode+NMS tail")
    tail_step, tail_inputs = build_decode_tail_step(pred, BATCH, up_hw, SIZE)
    report[f"decode_nms_tail_n{cfg.max_detections}"] = chained(
        tail_step, *tail_inputs, rtt=rtt
    )
    report["devtime"]["decode_nms_tail"] = attributed(
        tail_step, *tail_inputs, rtt=rtt
    )

    c_cat = cfg.emb_dim * 2 if cfg.fusion else cfg.emb_dim
    _progress(f"stage 6: decoder_heads ({c_cat}ch @ {up_hw}^2)")
    dec_step, dec_inputs = build_decoder_tail_step(
        BATCH, up_hw, c_cat, cfg.decoder_num_layer,
        cfg.decoder_kernel_size, cfg.compute_dtype,
    )
    report["decoder_heads"] = chained(dec_step, *dec_inputs, rtt=rtt)
    report["devtime"]["decoder_heads"] = attributed(
        dec_step, *dec_inputs, rtt=rtt
    )
    _progress(f"decoder_heads: {report['decoder_heads']*1000:.2f} ms")

    # stamp which formulations the tail stages actually traced (a
    # gate-refused request falls back silently at this layer — the stamp
    # plus the gate_probe/v1 causes make the fallback attributable)
    impl, quant = decoder_impl(
        up_hw, up_hw, c_cat, c_cat, cfg.decoder_num_layer,
        cfg.decoder_kernel_size, cfg.compute_dtype,
    )
    report["decoder_impl"] = impl
    report["quant"] = "int8" if quant else "off"
    report["decode_tail_mode"] = decode_tail_mode()

    report = {
        k: (round(v, 5) if isinstance(v, float) else v)
        for k, v in report.items()
    }
    report["batch"] = BATCH
    report["device"] = str(jax.devices()[0])
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()

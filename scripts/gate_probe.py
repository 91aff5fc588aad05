"""Diagnose WHY each gated custom kernel is refused on the live backend.

Round-5 session-7 finding: on the real TPU every require_tpu formulation
(flash global/windowed, pallas global/windowed, pallas xcorr) fell back,
while the one pure-XLA alternative (blockfolded) won the headline — but
the gates swallow their refusal reason, so "Mosaic can't lower through
this backend" vs "kernel miscompiles numerically" vs "backend-name
mismatch" were indistinguishable. This script runs each gate at the
production geometry and, for the pallas paths, also calls the kernel
DIRECTLY (no gate) so a lowering exception surfaces with its full
traceback.

Since the structured-diagnostics layer (tmr_tpu/diagnostics.py) landed,
every gate refusal records a machine-readable cause (category, exception
class + message, tile config, device kind); the gates are cache_clear'd
here first so a cause is recorded even for verdicts another trace already
cached.

Output modes:
  default        one JSON line per probe on stdout (legacy watcher format);
                 tracebacks/debug on stderr
  --json         ONE gate_probe/v1 JSON document on stdout:
                 {"schema", "backend", "probes": [{..., "refusals": [...]}],
                  "refusals": [...]}   (the flat list aggregates all causes)
  --out FILE     additionally write the --json document to FILE
                 (gate_probe.json schema — the committed artifact)

One process per chip: run it alone.
"""

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["TMR_GATE_DEBUG"] = "1"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()  # probes jit self-checks; reuse them
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true", dest="as_doc",
                    help="emit ONE gate_probe/v1 JSON document")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    args = ap.parse_args(argv)

    from tmr_tpu.diagnostics import GATE_PROBE_SCHEMA, drain_gate_refusals

    probes = []

    def emit(**kw):
        probes.append(kw)
        if not args.as_doc:
            print(json.dumps(kw), flush=True)

    backend = dict(
        default_backend=jax.default_backend(),
        devices=[str(d) for d in jax.devices()],
        device_kind=jax.devices()[0].device_kind,
        platform=jax.devices()[0].platform,
        jax_version=jax.__version__,
    )
    emit(probe="backend", **backend)

    # 1. trivial pallas kernel, compiled mode — does Mosaic lower AT ALL?
    try:
        from jax.experimental import pallas as pl

        def add1(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1.0

        x = jnp.zeros((256, 256), jnp.float32)
        y = pl.pallas_call(
            add1, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
        )(x)
        ok = bool(np.asarray(y)[0, 0] == 1.0)
        emit(probe="pallas_trivial", ok=ok)
    except Exception as e:
        traceback.print_exc()
        emit(probe="pallas_trivial", ok=False,
             error=f"{type(e).__name__}: {e}")

    # 2. the global-attention pallas kernels DIRECT (no gate), bench
    # geometry: grid 64x64, head_dim 64, B1 H2 (the gate's own shape)
    from tmr_tpu.models.vit import blockwise_decomposed_attention
    from tmr_tpu.ops.pallas_attn import (
        pallas_decomposed_attention,
        pallas_fused_attention,
    )

    rng = np.random.default_rng(0)
    gh = gw = 64
    D = 64
    S = gh * gw
    q = jnp.asarray(rng.standard_normal((1, 2, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, S, D)), jnp.bfloat16)
    rh = jnp.asarray(rng.standard_normal((gh, gh, D)) * 0.2, jnp.float32)
    rw = jnp.asarray(rng.standard_normal((gw, gw, D)) * 0.2, jnp.float32)
    # the blockwise oracle is the same for every probed kernel: run once
    want = None
    for probe_name, attn_fn in (
        ("pallas_global_direct", pallas_decomposed_attention),
        ("pallas_fused_direct", pallas_fused_attention),
    ):
        try:
            got = jax.jit(
                lambda *a, _f=attn_fn: _f(*a, (gh, gw), D**-0.5)
            )(q, k, v, rh, rw)
            got.block_until_ready()

            if want is None:
                want = np.asarray(jax.jit(
                    lambda *a: blockwise_decomposed_attention(
                        *a, (gh, gw), D**-0.5)
                )(q, k, v, rh, rw), np.float32)
            err = float(np.abs(np.asarray(got, np.float32) - want).max())
            ref = float(np.abs(want).max())
            emit(probe=probe_name, ok=bool(err / (ref + 1e-6) < 0.05),
                 rel_err=err / (ref + 1e-6))
        except Exception as e:
            traceback.print_exc()
            emit(probe=probe_name, ok=False,
                 error=f"{type(e).__name__}: {e}")

    # 3. every production gate, cache-cleared so refusal causes record
    from tmr_tpu.ops.flash_attn import (
        blockfolded_ok,
        densefolded_ok,
        flash_attention_ok,
        flash_window_ok,
        xlaflash_ok,
    )
    from tmr_tpu.ops.pallas_attn import (
        effective_fused_tiles,
        effective_global_tiles,
        pallas_fused_ok,
        pallas_global_ok,
    )
    from tmr_tpu.ops.pallas_xcorr import pallas_xcorr_ok
    from tmr_tpu.models.vit import _scores_dtype

    for gate_fn in (blockfolded_ok, densefolded_ok, flash_attention_ok,
                    flash_window_ok, xlaflash_ok, pallas_fused_ok,
                    pallas_global_ok, pallas_xcorr_ok):
        clear = getattr(gate_fn, "cache_clear", None)  # not all are cached
        if clear is not None:
            clear()

    bq, bk = effective_global_tiles(64 * 64)
    fbq, fbk = effective_fused_tiles(64 * 64, 64)
    live_scores = _scores_dtype()
    gates = {
        "flash_global_64x64_d64": lambda: flash_attention_ok(64, 64, 64),
        f"blockfolded_64x64_d64_scores_{live_scores}":
            lambda: blockfolded_ok(64, 64, 64, live_scores),
        f"densefolded_64x64_d64_scores_{live_scores}":
            lambda: densefolded_ok(64, 64, 64, live_scores),
        "xlaflash_64x64_d64": lambda: xlaflash_ok(64, 64, 64),
        "flash_window_14x14_d64": lambda: flash_window_ok(14, 14, 64),
        "pallas_global_64x64_d64":
            lambda: pallas_global_ok(64, 64, 64, bq, bk),
        f"pallas_fused_64x64_d64_bq{fbq}_bk{fbk}":
            lambda: pallas_fused_ok(64, 64, 64, fbq, fbk),
        "pallas_xcorr_c256_64_t17": lambda: pallas_xcorr_ok(256, 64, 64, 17),
    }
    drain_gate_refusals()  # discard causes from the direct probes above
    for name, fn in gates.items():
        try:
            ok = bool(fn())
            emit(probe=name, ok=ok, refusals=drain_gate_refusals())
        except Exception as e:
            traceback.print_exc()
            emit(probe=name, ok=False, error=f"{type(e).__name__}: {e}",
                 refusals=drain_gate_refusals())

    # the bf16-score-tile gates (the env the check traces under must match
    # the cache key being probed — set it for the duration)
    if live_scores != "bf16":
        os.environ["TMR_GLOBAL_SCORES_DTYPE"] = "bf16"
        try:
            for name, fn in {
                "blockfolded_64x64_d64_scores_bf16":
                    lambda: blockfolded_ok(64, 64, 64, "bf16"),
                "densefolded_64x64_d64_scores_bf16":
                    lambda: densefolded_ok(64, 64, 64, "bf16"),
            }.items():
                try:
                    emit(probe=name, ok=bool(fn()),
                         refusals=drain_gate_refusals())
                except Exception as e:
                    traceback.print_exc()
                    emit(probe=name, ok=False,
                         error=f"{type(e).__name__}: {e}",
                         refusals=drain_gate_refusals())
        finally:
            os.environ.pop("TMR_GLOBAL_SCORES_DTYPE", None)

    # 4. the decoder-tail gates (PR-6 surface: fused decoder heads, int8
    # quant tiers, device decode tail) at the production geometry — the
    # 2x-upsampled 128^2 grid with c_cat 1024 (emb_dim 512, fusion
    # doubles it), decoder_num_layer 1, kernel 3. These gates key their
    # own dict caches (not lru_cache), so clear those the same way for a
    # recorded cause even when another trace already cached the verdict.
    from tmr_tpu.ops import fused_heads as _fh
    from tmr_tpu.ops import pallas_int8 as _pi8
    from tmr_tpu.ops import postprocess as _pp
    from tmr_tpu.ops import quant as _q

    _fh._OK_CACHE.clear()
    _q._OK_CACHE.clear()
    _pp._TAIL_OK.clear()
    _pi8.pallas_int8_ok.cache_clear()
    # production geometry on the TPU; the off-accelerator contract run
    # (tests/test_bench_cli.py) probes the same code path at a geometry a
    # CPU can turn around — the verdict is per-geometry either way
    ph, pc = (128, 1024) if jax.default_backend() == "tpu" else (32, 256)
    for name, fn in {
        f"fused_heads_{ph}x{ph}_c{pc}": lambda: _fh.fused_heads_ok(
            ph, ph, pc, pc, 1, 3, "bfloat16"),
        f"quant_int8_{ph}x{ph}_c{pc}": lambda: _q.quant_ok(
            ph, ph, pc, pc, 1, 3),
        # the TMR_QUANT_STORAGE surface: the equality-tier storage pin,
        # the both-operand-int8 tolerance tier, the Mosaic int8 MXU
        # kernel self-check, and the matcher's int8dot conv tier
        f"quant_storage_{ph}x{ph}_c{pc}": lambda: _q.quant_storage_ok(
            ph, ph, pc, pc, 1, 3),
        f"quant_int8dot_{ph}x{ph}_c{pc}": lambda: _q.quant_int8dot_ok(
            ph, ph, pc, pc, 1, 3),
        "pallas_int8_mm_256": lambda: _pi8.pallas_int8_ok(),
        "quant_xcorr_c256_64_t17": lambda: _q.quant_xcorr_ok(
            256, 64, 64, 17),
        "quant_xcorr_int8dot_c256_64_t17": lambda: _q.quant_xcorr_ok(
            256, 64, 64, 17, kernel="int8dot"),
        "device_decode_tail": lambda: _pp.device_tail_ok(),
    }.items():
        try:
            emit(probe=name, ok=bool(fn()), refusals=drain_gate_refusals())
        except Exception as e:
            traceback.print_exc()
            emit(probe=name, ok=False, error=f"{type(e).__name__}: {e}",
                 refusals=drain_gate_refusals())

    # 4b. the fused gallery program's gate (serve/gallery.py): the
    # trace-only backbone-amortization invariant — the jaxpr of the
    # one-backbone-pass multi-pattern program must consume the frame
    # through exactly one backbone entry conv. Production bank shape
    # (N=8, k=1) at the smallest capacity bucket; production image
    # geometry on TPU, reduced on CPU like the decoder-tail gates.
    # No params needed: the gate traces over eval_shape abstract params.
    try:
        from tmr_tpu.config import preset as _preset
        from tmr_tpu.inference import Predictor as _Predictor
        from tmr_tpu.serve import gallery as _gallery

        _gallery._GATE_CACHE.clear()
        gsize = 1024 if jax.default_backend() == "tpu" else 64
        gpred = _Predictor(_preset(
            "TMR_FSCD147", backbone="sam_vit_b", image_size=gsize,
            compute_dtype="float32",
        ))
        emit(probe=f"gallery_fused_{gsize}_n8_k1",
             ok=bool(_gallery.gallery_fused_ok(gpred, 9, 8, 1)),
             refusals=drain_gate_refusals())
    except Exception as e:
        traceback.print_exc()
        emit(probe="gallery_fused", ok=False,
             error=f"{type(e).__name__}: {e}",
             refusals=drain_gate_refusals())

    # 5. the program-tier audit (tmr_tpu/analysis): the bucketed
    # production programs traced to jaxprs under the CURRENT env knobs
    # and checked structurally (no-S^2 attention, no-f64, quant-widen,
    # transfer guard). Trace-only — no compile — so it is cheap;
    # production geometry on TPU, reduced on CPU, same
    # split as the decoder-tail gates above. A failing audit records a
    # program_audit cause through the same gate_refused contract, so the
    # refusal travels with the probes like every kernel gate's.
    try:
        from tmr_tpu.analysis import Baseline, default_baseline_path
        from tmr_tpu.analysis.program_audit import (
            audit_production_programs,
        )

        audit = audit_production_programs(
            # committed baseline: the per-platform transfer_guard pin
            # overrides must apply here exactly as in analyze.py
            baseline=Baseline.load(default_baseline_path()),
            image_size=1024 if jax.default_backend() == "tpu" else 64,
            attention_grids=((64, 64), (96, 96)),
            record_refusals=True,
        )
        emit(probe="program_audit", ok=bool(audit["ok"]),
             problems=audit["problems"],
             gate_state=audit["states"][0]["gate_state"],
             refusals=drain_gate_refusals())
    except Exception as e:
        traceback.print_exc()
        emit(probe="program_audit", ok=False,
             error=f"{type(e).__name__}: {e}",
             refusals=drain_gate_refusals())

    doc = {
        "schema": GATE_PROBE_SCHEMA,
        "backend": backend,
        "probes": probes,
        "refusals": [
            r for p in probes for r in p.get("refusals", ())
        ],
    }
    if args.as_doc:
        print(json.dumps(doc), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full FSCD-147 width the repo benches (ViT-B/1024, emb 512,
2x feature upsample, fusion, bf16), with weights and planted-pattern images
made from ``--seed``:

1. ``predict`` — ``Predictor.__call__`` (2 images x 1 exemplar) and
   ``predict_multi_exemplar`` (3 exemplars); the dense head outputs agree
   with the same model in float32 through the plain-XLA oracle formulations.
2. ``serve`` — a ``ServeEngine`` answers 8 requests bitwise like the direct
   ``Predictor`` calls, the repeat hits the result cache, nothing compiles
   after warm-up.
3. ``train`` — ``main.py``'s own ``main()`` takes three optimizer steps on
   a synthetic FSCD-147 fixture.

With ``--chips 4`` (four chips on one host) it runs the mesh-sharded
serving path (``ServeEngine(mesh="dp2tp2")``) against the one-device engine,
decides on the tensor-parallel programs' dense head maps against the
one-device program, and runs no other phase.

Its first act is to require a TPU: with no accelerator it exits non-zero
and prints no result. A failed check or a raised phase makes the exit code
non-zero; nothing here turns a failure into ``ok``. It starts no child
process, reads no recorded benchmark file, and writes only under
``chiprun_out/chip_smoke/`` and the compile cache. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import shutil
import sys
import time
import types
from typing import NamedTuple
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

#: stated bf16 tolerance: max |bf16 - f32| over max |f32| of a dense head
#: output, ViT-B depth 12, random weights
BF16_REL_TOL = 0.1
#: tensor-parallel against one device, both through the XLA formulations
#: (a program XLA partitions holds no kernel): max |tp - one| over max |one|
#: of a dense head output. Only the order of the collectives' bf16
#: reductions differs; a wrong PartitionSpec moves it to order 1.
TP_REL_TOL = 0.05

#: the plain-XLA oracle formulations of the float32 reference run
ORACLE_ENV = {
    "TMR_GLOBAL_ATTN": "blockwise",
    "TMR_XCORR_IMPL": "conv",
    "TMR_DECODER_IMPL": "xla",
}

FIELDS = ("boxes", "scores", "refs", "valid")


class Size(NamedTuple):
    """What is run. ``main()`` always runs ``FULL``; a rehearsal off the
    chip imports this module and hands the phases something smaller."""

    backbone: str = "sam_vit_b"
    image_size: int = 1024
    emb_dim: int = 512
    square: int = 96  # planted pattern side, pixels


FULL = Size()

_failures: list = []
_jax_events: collections.Counter = collections.Counter()


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """A comparison that decides the result: recorded, never raised, so
    the other phases still show what they do; any entry fails the run."""
    say(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        _failures.append(what)


# ------------------------------------------------------------- inputs
def planted_image(seed: int, size: Size) -> np.ndarray:
    """(S, S, 3) float32, roughly normalized: dim noise with one bright
    textured patch pasted at three places; the exemplar box of
    :func:`exemplars` sits on the first."""
    s, q = size.image_size, size.square
    rng = np.random.default_rng(seed)
    img = rng.normal(0.0, 0.2, (s, s, 3)).astype(np.float32)
    patch = rng.normal(1.5, 0.5, (q, q, 3)).astype(np.float32)
    for fy, fx in ((0.25, 0.25), (0.6, 0.7), (0.75, 0.3)):
        y, x = int(fy * s) - q // 2, int(fx * s) - q // 2
        img[y:y + q, x:x + q] = patch
    return img


def exemplars(size: Size, k: int) -> np.ndarray:
    """(k, 4) normalized xyxy boxes on the planted patches."""
    half = size.square / 2 / size.image_size
    centers = ((0.25, 0.25), (0.6, 0.7), (0.75, 0.3))[:k]
    return np.asarray(
        [[fx - half, fy - half, fx + half, fy + half] for fy, fx in centers],
        np.float32,
    )


def _np(dets: dict) -> dict:
    return {k: np.asarray(dets[k]) for k in FIELDS}


def _bitwise(a: dict, b: dict) -> bool:
    return all(
        a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
        and np.array_equal(a[k], b[k]) for k in FIELDS
    )


def _set_agreement(a: dict, b: dict):
    """(Jaccard index of the two valid-detection sets keyed by reference
    point, max abs difference of the matched scores and boxes)."""
    def keyed(d):
        v = d["valid"][0]
        return {tuple(np.round(r, 5)): (s, bx) for r, s, bx in
                zip(d["refs"][0][v], d["scores"][0][v], d["boxes"][0][v])}

    ka, kb = keyed(a), keyed(b)
    common = set(ka) & set(kb)
    union = len(set(ka) | set(kb))
    err = max((max(abs(ka[c][0] - kb[c][0]),
                   float(np.abs(ka[c][1] - kb[c][1]).max()))
               for c in common), default=0.0)
    return (len(common) / union if union else 1.0), float(err)


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_hbm(label: str) -> None:
    import jax

    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        say(f"  hbm[{label}] device {d.id}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')} bytes_in_use="
            f"{stats.get('bytes_in_use')} bytes_limit="
            f"{stats.get('bytes_limit')}")


def _backend_compiles() -> int:
    return _jax_events["/jax/core/compile/backend_compile_duration"]


# -------------------------------------------------------------- gates
def report_gates(phase: str) -> None:
    """Print every refusal the gates recorded since the last call, with its
    structured cause. ``exception`` is a compile or trace error — a bug or
    a path to retire, not a verdict — and fails the run."""
    from tmr_tpu.diagnostics import drain_gate_refusals

    seen = collections.Counter(
        (r["gate"], r["cause"], json.dumps(r["config"], sort_keys=True),
         r["message"][:300]) for r in drain_gate_refusals())
    for (gate, cause, config, message), n in seen.items():
        say(f"  gate[{phase}] {gate} refused x{n}: cause={cause} "
            f"config={config} message={message!r}")
        check(cause != "exception",
              f"gate {gate} refused with a verdict, not an exception")


def _is_trunk(size: Size) -> bool:
    """A backbone given as a list of typed layers (models/lm_trunk.py)."""
    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS

    return size.backbone in TRUNK_CONFIGS


def check_grouped_products(size: Size, seed: int, batch: int = 2) -> None:
    """The experts' grouped products on the chip are the Pallas kernel
    (``ops/moe.py:grouped_formulation``: kept so that the device trace keeps
    the layer's scope path, not for speed), which no test off the chip runs:
    held equal here to ``lax.ragged_dot``, at the backbone's own sizes and
    with groups as uneven as a router leaves them."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS
    from tmr_tpu.ops import moe

    z = TRUNK_CONFIGS[size.backbone]
    d, width, held = z["hidden"], z["expert_width"], z["experts_held"]
    rows = batch * (size.image_size // 16) ** 2 * z["top_k"]
    bf = jnp.bfloat16
    if moe.grouped_formulation(rows, d, width, bf) != "gmm":
        say("  grouped products: ragged_dot at these sizes, nothing to hold")
        return
    keys = jax.random.split(jax.random.key(seed), 5)
    # about half the pairs fall on held experts; the rest of the rows idle
    owner = jax.random.categorical(
        keys[0], jax.random.normal(keys[0], (2 * held,)), shape=(rows,))
    sizes = jnp.bincount(owner, length=2 * held)[:held].astype(jnp.int32)
    xs = jax.random.normal(keys[1], (rows, d), bf)
    gate = jax.random.normal(keys[2], (held, d, width), bf) * d ** -0.5
    up = jax.random.normal(keys[3], (held, d, width), bf) * d ** -0.5
    down = jax.random.normal(keys[4], (held, width, d), bf) * width ** -0.5
    run = jax.jit(moe.grouped_ffn, static_argnums=(5, 6))
    used = int(sizes.sum())
    a = np.asarray(run(xs, sizes, gate, up, down, bf, "gmm")[:used],
                   np.float32)
    b = np.asarray(run(xs, sizes, gate, up, down, bf, "ragged_dot")[:used],
                   np.float32)
    gap = float(np.abs(a - b).max() / np.abs(b).max())
    say(f"  grouped products, {used} of {rows} rows over {held} experts "
        f"(largest group {int(sizes.max())}): gmm against ragged_dot, widest "
        f"gap {gap:.5f} of the range")
    check(gap < 0.02, "the Pallas grouped product equals lax.ragged_dot at "
                      "the backbone's sizes")


def check_pairs_kernels(size: Size, seed: int, batch: int = 2) -> bool:
    """The row kernels that bring the experts' results to their tokens on
    the chip (``ops/moe.py:sum_rows``), which off the chip run only in the
    interpreter: held here to ``combine`` as XLA runs it, at the backbone's
    own tokens, choices a token and width, with the share of the experts it
    holds, and garbage in the rows past the groups' total. Returns whether
    a trace of the backbone takes the kernels."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS
    from tmr_tpu.ops import moe

    z = TRUNK_CONFIGS[size.backbone]
    d, k, held = z["hidden"], z["top_k"], z["experts_held"]
    tokens, bf = batch * (size.image_size // 16) ** 2, jnp.bfloat16
    formulation = moe.pairs_formulation(tokens * k, k, d, bf)
    say(f"  pairs: pairs_formulation({tokens * k}, {k}, {d}, bfloat16) = "
        f"{formulation}")
    if formulation != "row_dma":
        return False
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (tokens, d)).astype(bf)
    _, idx = jax.lax.top_k(
        jax.random.normal(keys[1], (tokens, z["num_experts"])), k)
    weights = jax.random.uniform(keys[2], (tokens, k), minval=0.05)
    ys = jax.random.normal(keys[3], (tokens * k, d)).astype(bf)

    @jax.jit
    def gap(x, idx, weights, ys):
        _, sizes, here, slot = moe.dispatch(x, idx, held)
        total = sizes.sum()
        ys = jnp.where((jnp.arange(tokens * k) < total)[:, None], ys, jnp.nan)
        got = moe.combine(ys, weights, here, slot, "row_dma")
        want = moe.combine(ys, weights, here, slot)
        return (total, jnp.isfinite(got).all(),
                jnp.abs(got - want).max() / jnp.abs(want).max())

    total, finite, widest = (float(v) for v in gap(x, idx, weights, ys))
    say(f"  pairs, {tokens} tokens x {k} of {d}, {int(total)} of "
        f"{tokens * k} rows held here, NaN past them: the kernels' sums "
        f"against combine's, widest gap {widest:.2g} of the range (float32 "
        f"sums of the same bfloat16 rows)")
    check(bool(finite) and widest < 1e-5,
          "the row kernels' weighted sums equal combine's at the backbone's "
          "shape and no row past the total reaches them")
    return True


def check_kda_kernel(size: Size, seed: int, batch: int = 2) -> bool:
    """The recurrence's Pallas kernel (``ops/kda.py:kda_chunk_kernel``),
    which off the chip runs only in the interpreter: held here to
    ``kda_chunked`` at the backbone's own shape and to the token recurrence
    on 256 tokens, a decay near 0 and one near 1 among the heads. Returns
    whether a trace of the backbone takes the kernel."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS
    from tmr_tpu.ops import kda

    z = TRUNK_CONFIGS[size.backbone]
    if not any(mixer == "kda" for mixer, _ in z["layers"]):
        say("  recurrence: the backbone has no kda layer, nothing to hold")
        return False
    h, d, bf = z["num_heads"], z["kda_head_dim"], jnp.bfloat16
    seq = (size.image_size // 16) ** 2
    formulation = kda.kda_formulation(seq, d, d, bf, h)
    say(f"  recurrence: kda_formulation({seq}, {d}, {d}, bfloat16, {h}) = "
        f"{formulation}, {kda._heads_per_step(h)} heads a grid step")
    if formulation != "chunk_kernel":
        return False

    def inputs(key, tokens):
        ks = jax.random.split(key, 6)
        unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
        shape = (batch, tokens, h, d)
        # log-decays as the mixer makes them: -exp(A_log) softplus(. + bias)
        rate = jnp.exp(jax.random.uniform(ks[5], (h, 1), maxval=2.8))
        g = -rate * jax.nn.softplus(
            2.0 * jax.random.normal(ks[3], shape) - 4.0)
        return (unit(jax.random.normal(ks[0], shape)) * d ** -0.5,
                unit(jax.random.normal(ks[1], shape)),
                jax.random.normal(ks[2], shape).astype(bf), g,
                jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))

    kernel = jax.jit(lambda *a: kda.kda_chunk_kernel(*a, bf))
    chunked = jax.jit(lambda *a: kda.kda_chunked(*a, dtype=bf))
    args = inputs(jax.random.key(seed), seq)
    want = np.asarray(chunked(*args))
    gap = float(np.abs(np.asarray(kernel(*args)) - want).max()
                / np.abs(want).max())
    say(f"  recurrence, {batch} x {seq} tokens x {h} heads of {d}: the "
        f"kernel against kda_chunked, widest gap {gap:.5f} of the range")
    check(gap < 0.01, "the recurrence's kernel equals kda_chunked at the "
                      "backbone's shape")
    # as the mixer calls it: q and k as the convolutions left them, the
    # norms on either side of the recurrence inside the kernel
    _, _, v, g, beta = inputs(jax.random.key(seed + 1), 256)
    keys = jax.random.split(jax.random.key(seed + 2), 3)
    q, k = (jax.random.normal(key, v.shape).astype(bf) for key in keys[:2])
    weight = 1.0 + 0.1 * jax.random.normal(keys[2], (d,))
    g = g.at[:, :, 0].set(np.log(1e-9)).at[:, :, 1].set(np.log(1 - 1e-6))
    want = np.asarray(jax.jit(lambda q, k, v, g, beta, w: kda.rms_norm(
        kda.kda_recurrent(kda.l2norm(q) * d ** -0.5, kda.l2norm(k), v, g,
                          beta), w, 1e-5))(q, k, v, g, beta, weight))
    got = np.asarray(jax.jit(lambda q, k, v, g, beta, w: kda.kda_chunk_kernel(
        q, k, v, g, beta, bf, d ** -0.5, (w, 1e-5)))(q, k, v, g, beta,
                                                     weight))
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    say(f"  recurrence with its norms, 256 tokens, decays 1e-9 and 1 - 1e-6 "
        f"among them: the kernel against the token recurrence, widest gap "
        f"{gap:.5f} of the range (bfloat16 operands against float32)")
    check(np.isfinite(got).all() and gap < 0.03,
          "the recurrence's kernel, the norms in it, equals the token "
          "recurrence on 256 tokens")
    return True


def check_mla_kernel(size: Size, seed: int, batch: int = 2) -> bool:
    """Latent attention's Pallas kernel
    (``ops/causal_attn.py:latent_attention_kernel``), which off the chip
    runs only in the interpreter: held here to the blocked XLA form at the
    backbone's own shape (its heads, its tokens, its rotation where it has
    one). Returns whether a trace of the backbone takes the kernel."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS
    from tmr_tpu.ops import causal_attn, rope

    z = TRUNK_CONFIGS[size.backbone]
    if not any(mixer == "mla" for mixer, _ in z["layers"]):
        say("  latent attention: the backbone has no mla layer, nothing to "
            "hold")
        return False
    h, dn, dp, dv = (z["num_heads"], z["qk_nope_dim"], z["qk_pe_dim"],
                     z["v_dim"])
    seq, bf = (size.image_size // 16) ** 2, jnp.bfloat16
    rot, scale = None, (dn + dp) ** -0.5
    if z.get("rope"):
        inv_freq, gain, temper = rope.yarn_rotation(dp, z["rope"])
        rot, scale = (tuple(inv_freq.tolist()), gain), scale * temper
    formulation = causal_attn.mla_formulation(seq, h, dn, dp, dv, bf,
                                              rot is not None)
    say(f"  latent attention: mla_formulation({seq}, {h}, {dn}, {dp}, {dv}, "
        f"bfloat16, rope={rot is not None}) = {formulation}")
    if formulation != "causal_kernel":
        return False
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (batch, seq, h * (dn + dp))).astype(bf)
    kv = jax.random.normal(ks[1], (batch, seq, h * (dn + dv))).astype(bf)
    k_pe = jax.random.normal(ks[2], (batch, seq, dp)).astype(bf)
    run = lambda fn: np.asarray(jax.jit(
        lambda *a: fn(*a, h, scale, rot))(q, kv, k_pe), np.float32)
    got = run(causal_attn.latent_attention_kernel)
    want = run(causal_attn.latent_attention_blocked)
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    say(f"  latent attention, {batch} x {seq} tokens x {h} heads of {dn} + "
        f"{dp} / {dv}: the kernel against the blocked form, widest gap "
        f"{gap:.5f} of the range (bfloat16 on both sides)")
    # one bfloat16 step near the top of the range is 0.008 of it
    check(np.isfinite(got).all() and gap < 0.02,
          "latent attention's kernel equals the blocked form at the "
          "backbone's shape")
    return True


def check_ssd_scan(size: Size, seed: int) -> None:
    """The state-space recurrence's chunked form (``ops/ssd.py:ssd_chunked``)
    in bfloat16 at the backbone's own shape (its heads, its state, its chunk,
    one image's tokens), held on the chip to the token recurrence in
    float32, with step sizes and decays by the family's rule: a head's
    memory from under one token to a thousand."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS
    from tmr_tpu.ops import ssd

    z = TRUNK_CONFIGS[size.backbone]
    if not any(mixer == "ssm" for mixer, _ in z["layers"]):
        say("  state-space scan: the backbone has no ssm layer, nothing to "
            "hold")
        return
    h, p, n, g = (z["ssm_heads"], z["ssm_head_dim"], z["ssm_state"],
                  z["ssm_groups"])
    seq, chunk, bf = (size.image_size // 16) ** 2, z["ssm_chunk"], jnp.bfloat16
    say(f"  state-space scan: ssd_formulation = "
        f"{ssd.ssd_formulation(seq, h, p, n, bf)}, chunks of {chunk}")
    ks = jax.random.split(jax.random.key(seed), 5)
    u = jax.random.normal(ks[0], (1, seq, h, p)).astype(bf)
    b_in = jax.random.normal(ks[1], (1, seq, g, n)).astype(bf)
    c_in = jax.random.normal(ks[2], (1, seq, g, n)).astype(bf)
    delta = 1e-3 * 100.0 ** jax.random.uniform(ks[3], (1, seq, h))
    a = jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    d = jnp.ones((h,))
    got = np.asarray(jax.jit(lambda *t: ssd.ssd_chunked(
        *t, chunk=chunk, dtype=bf))(u, delta, a, b_in, c_in, d), np.float32)
    want = np.asarray(jax.jit(ssd.ssd_recurrent)(u, delta, a, b_in, c_in, d))
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    say(f"  state-space scan, {seq} tokens x {h} heads of {p} on a state of "
        f"{n}: the chunked form in bfloat16 against the token recurrence, "
        f"widest gap {gap:.5f} of the range")
    check(np.isfinite(got).all() and gap < 0.03,
          "the chunked state-space scan equals the token recurrence at the "
          "backbone's shape")


def _vit_heads(size: Size) -> tuple:
    """(heads, head dim) of the SAM encoder ``size`` names."""
    from tmr_tpu.models.vit import VIT_CONFIGS

    vc = VIT_CONFIGS["vit_b" if size.backbone == "sam_vit_b" else "vit_h"]
    return vc["num_heads"], vc["embed_dim"] // vc["num_heads"]


def decide_gates(cfg, size: Size) -> dict:
    """Ask, outside any trace, every gate the ``auto`` path of this
    configuration consults; the model's traces then hit their caches."""
    from tmr_tpu.ops.flash_attn import flash_attention_ok
    from tmr_tpu.ops.pallas_attn import packed_global_ok, packed_window_ok
    from tmr_tpu.ops.pallas_nms import pallas_nms_compiled_ok

    if _is_trunk(size):
        # a trunk of typed layers asks no attention gate of the ViT's: its
        # formulations are on its compile span (report_window_formulation)
        verdicts = {"flash_attention_ok": False, "packed_global_ok": False,
                    "packed_window_ok": False,
                    "pallas_nms_compiled_ok": pallas_nms_compiled_ok()}
        say(f"  gate pallas_nms_compiled_ok: "
            f"{'pass' if verdicts['pallas_nms_compiled_ok'] else 'refused'}")
        verdicts["kda_chunk_ok"] = check_kda_kernel(size, seed=0)
        verdicts["latent_kernel_ok"] = check_mla_kernel(size, seed=0)
        check_ssd_scan(size, seed=0)
        report_gates("decide")
        check_grouped_products(size, seed=0)
        verdicts["pairs_kernels_ok"] = check_pairs_kernels(size, seed=0)
        return verdicts
    num_heads, head_dim = _vit_heads(size)
    grid = size.image_size // 16
    verdicts = {
        "flash_attention_ok": flash_attention_ok(grid, grid, head_dim),
        "packed_global_ok": packed_global_ok(grid, grid, head_dim, num_heads),
        "packed_window_ok": packed_window_ok(14, 14, head_dim, num_heads),
        "pallas_nms_compiled_ok": pallas_nms_compiled_ok(),
    }
    for gate, ok in verdicts.items():
        say(f"  gate {gate}: {'pass' if ok else 'refused'}")
    report_gates("decide")
    return verdicts


def report_formulations(cfg, size: Size, verdicts: dict) -> None:
    """The formulation each layer runs under the current environment: the
    knob when set, else what ``auto`` resolves to given the gates; for the
    ViT's blocks, ``global_formulation``'s answer (with ``TMR_GLOBAL_ATTN``
    unset or ``auto``) and ``window_formulation``'s (no knob)."""
    import jax.numpy as jnp

    from tmr_tpu.inference import decode_tail_mode
    from tmr_tpu.ops.pallas_attn import global_formulation, window_formulation
    from tmr_tpu.ops.xcorr import small_impl_default

    env = os.environ.get
    win = glob = "none"
    glob_by = "global_formulation"
    if not _is_trunk(size):
        dtype = jnp.dtype(cfg.compute_dtype)
        win = window_formulation((14, 14), *_vit_heads(size), dtype)
        if env("TMR_GLOBAL_ATTN", "auto") != "auto":
            glob, glob_by = env("TMR_GLOBAL_ATTN"), "TMR_GLOBAL_ATTN"
        else:
            grid = size.image_size // 16
            glob = global_formulation((grid, grid), *_vit_heads(size), dtype)
    say("  formulations: " + json.dumps({
        f"global_attention({glob_by})": glob,
        "windowed_attention(window_formulation)": win,
        "xcorr_small(TMR_XCORR_IMPL[_SMALL])": env(
            "TMR_XCORR_IMPL", env("TMR_XCORR_IMPL_SMALL",
                                  small_impl_default())),
        "xcorr_precision(TMR_XCORR_PRECISION)": env(
            "TMR_XCORR_PRECISION", "highest"),
        "decoder(TMR_DECODER_IMPL)": env("TMR_DECODER_IMPL", "auto->xla"),
        "quant(TMR_QUANT)": env("TMR_QUANT", "off"),
        "decode_tail(TMR_DECODE_TAIL)": decode_tail_mode(),
        "nms": "pallas" if verdicts["pallas_nms_compiled_ok"] else "xla",
    }))


def report_window_formulation() -> None:
    """What the programs compiled so far traced their windowed and their
    global blocks with: the ``vit.win_attn.<formulation>`` and
    ``vit.global_attn.<formulation>`` counters (one count a block a trace)
    and each ``compile`` span's own share of them."""
    from tmr_tpu import obs

    say("  windowed blocks traced, by formulation: " + json.dumps(
        obs.get_registry().counters("vit.win_attn.")))
    say("  global blocks traced, by formulation: " + json.dumps(
        obs.get_registry().counters("vit.global_attn.")))
    for rec in obs.spans():
        if rec["name"] == "compile":
            a = rec["attrs"]
            trunk = {k: v for k, v in a.items()
                     if k.startswith("trunk_") or k == "experts_held"}
            say(f"  compile {a.get('kind')} {a.get('key')}: win_attn="
                f"{a.get('win_attn')} x{a.get('win_attn_blocks')} global_attn="
                f"{a.get('global_attn')} x{a.get('global_attn_blocks')}"
                + (f" {json.dumps(trunk)}" if trunk else ""))


def report_autotune(cfg, size: Size, batch: int) -> None:
    """Seed/cache export only — no on-device sweep on this path — and what
    AUTOTUNE_SEED.json holds for this device kind, fresh or stale."""
    import jax

    from tmr_tpu.utils import autotune as at

    report = at.autotune(cfg, size.image_size, batch, sweep=False)
    pending = report.pop("_pending", [])
    say("  autotune (no sweep), batch %d: exported %s; no winner on record "
        "for %s" % (batch, json.dumps(
            {k: v["picked"] for k, v in report.items()}), pending))
    kind = jax.devices()[0].device_kind
    for key, entry in at.seed_load().items():
        if not key.startswith(kind + "|"):
            say(f"  AUTOTUNE_SEED.json key {key!r}: another device kind")
            continue
        knobs = {}
        for k in at._VERSIONED_KNOBS:
            if k in entry:
                fresh = entry.get("_variants_" + k) == at._variants_sig(k)
                knobs[k] = f"{entry[k]} ({'fresh' if fresh else 'stale'})"
        say(f"  AUTOTUNE_SEED.json key {key!r} matches device kind "
            f"{kind!r}: {json.dumps(knobs)}")


# ------------------------------------------------------------- phases
def _heads_out(out, _exemplars):
    """The dense head maps of the configuration's one feature level."""
    (objectness,), (regressions,) = out["objectness"], out["regressions"]
    return {"objectness": objectness, "regressions": regressions}


def build_predictor(size: Size, seed: int, dtype: str = "bfloat16"):
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset("TMR_FSCD147", backbone=size.backbone,
                 image_size=size.image_size, emb_dim=size.emb_dim,
                 compute_dtype=dtype)
    pred = Predictor(cfg)
    _, t = _timed(lambda: pred.init_params(seed))
    say(f"  init_params(seed={seed}): {t:.1f}s")
    return pred


def phase_predict(pred, size: Size, seed: int, verdicts: dict) -> None:
    """``Predictor.__call__`` and ``predict_multi_exemplar`` at the full
    width. For a trunk of typed layers every compiled program's ``compile``
    span must name the formulation of each kind of layer the trunk has
    (``trunk_kda``, ``trunk_mla``, ``trunk_ssm``, ``trunk_gqa`` by its
    mixers, ``trunk_moe``, ``trunk_pairs``, and ``trunk_hc`` where it has
    streams): a trunk with ``ssm`` / ``gqa`` layers and no ``kda`` / ``mla``
    layer names the former pair and neither of the latter; its experts'
    pairs travel by ``row_dma`` where ``check_pairs_kernels`` found the
    gate's yes."""
    import inspect

    import jax
    import jax.numpy as jnp

    from tmr_tpu.inference import Predictor

    say("phase predict")
    images = np.stack([planted_image(seed + i, size) for i in range(2)])
    ex1 = np.stack([exemplars(size, 1)] * 2)  # (2, 1, 4)
    ex3 = exemplars(size, 3)

    dets, t_first = _timed(lambda: pred(images, ex1))
    _, t_run = _timed(lambda: pred(images, ex1))
    say(f"  __call__ 2x1: first call (compile+run) {t_first:.2f}s, "
        f"run {t_run:.3f}s, valid={np.asarray(dets['valid']).sum(1)}")
    report_window_formulation()
    multi, t_first = _timed(
        lambda: pred.predict_multi_exemplar(images[:1], ex3))
    _, t_run = _timed(lambda: pred.predict_multi_exemplar(images[:1], ex3))
    say(f"  predict_multi_exemplar k=3: first call {t_first:.2f}s, "
        f"run {t_run:.3f}s, valid={np.asarray(multi['valid']).sum(1)}")
    platform = jax.devices()[0].platform
    for name, d in (("__call__", dets), ("multi", multi)):
        check(all(next(iter(d[k].devices())).platform == platform
                  for k in FIELDS),
              f"{name} outputs live on the {platform} device")
        check(all(np.isfinite(np.asarray(d[k], np.float32)).all()
                  for k in ("boxes", "scores", "refs")),
              f"{name} outputs are finite")
    n_slots = dets["valid"].shape[1]
    check(dets["boxes"].shape == (2, n_slots, 4)
          and dets["scores"].shape == (2, n_slots)
          and dets["refs"].shape == (2, n_slots, 2)
          and 0 < n_slots <= pred.cfg.max_detections,
          f"__call__ output shapes (2, {n_slots} <= max_detections "
          f"{pred.cfg.max_detections}, ...)")

    # the compiled predict program itself: is a kernel in it
    cap = pred.pick_capacity(ex1, size.image_size)
    jitted = inspect.unwrap(pred._get_fn(cap),
                            stop=lambda f: hasattr(f, "lower"))
    compiled = jitted.lower(pred.exec_params(), pred.refiner_params,
                            jnp.asarray(images), jnp.asarray(ex1)).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    say(f"  compiled predict program: {n_kernels} tpu_custom_call "
        f"occurrences; memory_analysis arguments "
        f"{mem.argument_size_in_bytes} outputs {mem.output_size_in_bytes} "
        f"temporaries {mem.temp_size_in_bytes} bytes")
    if any(verdicts.values()):
        check(n_kernels > 0, "the gates admitted a kernel and the compiled "
                             "predict program contains a tpu_custom_call")

    if _is_trunk(size):
        from tmr_tpu import obs
        from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS

        z = TRUNK_CONFIGS[size.backbone]
        traced = [{k: v for k, v in r["attrs"].items()
                   if k.startswith("trunk_")}
                  for r in obs.spans() if r["name"] == "compile"]
        traced = [t for t in traced if t]
        say(f"  formulations the compiled programs traced: {traced}")
        kinds = {f"trunk_{mixer}" for mixer, _ in z["layers"]} | {
            "trunk_moe", "trunk_pairs"}
        if z.get("hc_mult"):
            kinds.add("trunk_hc")
        check(traced and all(kinds <= set(t) for t in traced),
              f"every compiled program names its {sorted(kinds)}")
        if "trunk_kda" in kinds:
            want = ("chunk_kernel" if verdicts.get("kda_chunk_ok")
                    else "chunked_xla")
            check(all(t["trunk_kda"].startswith(want + " x") for t in traced),
                  f"every compiled program traced its recurrence as {want}")
        if "trunk_mla" in kinds:
            want = ("causal_kernel" if verdicts.get("latent_kernel_ok")
                    else "blocked_xla") + ("_rope" if z.get("rope") else "")
            check(all(t["trunk_mla"].startswith(want + " x") for t in traced),
                  f"every compiled program traced latent attention as {want}")
        want = "row_dma" if verdicts.get("pairs_kernels_ok") else "xla_gather"
        say("  trunk_pairs of every compiled program: "
            f"{[t.get('trunk_pairs') for t in traced]}")
        check(all(t["trunk_pairs"].startswith(want + " x") for t in traced),
              f"every compiled program moves its experts' pairs by {want}")
        for kind, want in (("trunk_ssm", "chunked_xla"),
                           ("trunk_gqa", "blocked_xla")):
            if kind in kinds:
                check(all(t[kind].startswith(want + " x") for t in traced),
                      f"every compiled program traced its {kind} as {want}")
        # a float32 copy left to route by itself breaks ties otherwise and
        # sends those tokens through other experts: that comparison is the
        # benchmark cell's, where the reference follows ties (PERF.md)
        say("  bf16 against a float32 oracle: skipped for a backbone with "
            "routed experts; the benchmark's trunk cells' check makes it")
        report_gates("predict")
        _peak_hbm("predict")
        return
    # dense head outputs against float32 through the oracle formulations
    got, t_first = _timed(lambda: pred._get_fn(cap, loss_fn=_heads_out)(
        pred.exec_params(), pred.refiner_params, jnp.asarray(images),
        jnp.asarray(ex1))[0])
    say(f"  bf16 heads program: first call {t_first:.2f}s")
    oracle = Predictor(
        dataclasses.replace(pred.cfg, compute_dtype="float32"),
        params=pred.params)
    with mock.patch.dict(os.environ, ORACLE_ENV):  # read at trace time
        want, t_first = _timed(
            lambda: oracle._get_fn(cap, loss_fn=_heads_out)(
                oracle.exec_params(), None, jnp.asarray(images),
                jnp.asarray(ex1))[0])
    say(f"  float32 oracle program {ORACLE_ENV}: first call {t_first:.2f}s")
    for name in ("objectness", "regressions"):
        a = np.asarray(got[name], np.float32)
        b = np.asarray(want[name], np.float32)
        rel = float(np.abs(a - b).max() / (np.abs(b).max() + 1e-12))
        check(a.shape == b.shape and np.isfinite(a).all()
              and rel <= BF16_REL_TOL,
              f"{name} {a.shape}: bf16 vs float32 oracle max rel err "
              f"{rel:.4g} <= tolerance {BF16_REL_TOL}")

    if verdicts["pallas_nms_compiled_ok"]:
        from tmr_tpu.ops.postprocess import batched_nms

        cand = {k: jnp.asarray(v) for k, v in _np(dets).items()}
        rng = np.random.default_rng(seed)
        xy = rng.uniform(0.0, 0.8, (2, n_slots, 2)).astype(np.float32)
        wh = rng.uniform(0.02, 0.2, (2, n_slots, 2)).astype(np.float32)
        cand["boxes"] = jnp.asarray(np.concatenate([xy, xy + wh], -1))
        cand["scores"] = jnp.asarray(
            rng.uniform(size=(2, n_slots)).astype(np.float32))
        cand["valid"] = cand["scores"] > 0.3
        thr = pred.cfg.NMS_iou_threshold
        a = batched_nms(cand, thr, backend="pallas")["valid"]
        b = batched_nms(cand, thr, backend="xla")["valid"]
        check(bool(jnp.array_equal(a, b)) and int(a.sum()) > 0,
              f"Pallas NMS keeps exactly what the XLA fixpoint keeps "
              f"({int(a.sum())} of {int(cand['valid'].sum())} boxes)")
    report_gates("predict")
    _peak_hbm("predict")


def serve_requests(size: Size, seed: int) -> list:
    """8 requests: 1-exemplar and 3-exemplar sets over distinct images,
    request 6 repeating request 0."""
    ex1, ex3 = exemplars(size, 1), exemplars(size, 3)
    reqs = []
    for i in range(8):
        img = planted_image(seed + 100 + i, size)
        reqs.append((img, ex3, True) if i % 3 == 2 else (img, ex1, False))
    reqs[6] = reqs[0]
    return reqs


def direct_results(pred, reqs: list) -> list:
    return [
        _np(pred.predict_multi_exemplar(img[None], ex) if multi
            else pred(img[None], ex[None]))
        for img, ex, multi in reqs
    ]


def serve_through(engine, reqs: list, size: Size, seed: int):
    """Warm the engine's programs, then answer ``reqs`` in two waves (the
    repeat goes second, so it can only be served from the result cache).
    Returns (results, compiles after warm-up)."""
    from tmr_tpu import obs

    warm = planted_image(seed + 999, size)
    _, t_warm = _timed(lambda: [
        engine.submit(warm, exemplars(size, 1)).result(timeout=1200),
        engine.submit(warm, exemplars(size, 3), multi=True
                      ).result(timeout=1200),
    ])
    seq0, jax0 = obs.compile_event_seq(), _backend_compiles()
    t0 = time.perf_counter()
    futs = [engine.submit(i, e, multi=m) for i, e, m in reqs[:6]]
    results = [f.result(timeout=1200) for f in futs]
    futs = [engine.submit(i, e, multi=m) for i, e, m in reqs[6:]]
    results += [f.result(timeout=1200) for f in futs]
    seconds = time.perf_counter() - t0
    events, _ = obs.compile_events_since(seq0)
    say(f"  engine warm-up {t_warm:.2f}s; 8 requests {seconds:.3f}s")
    return results, (len(events), _backend_compiles() - jax0)


def report_batch_stages(since: float, batches: int) -> None:
    """The serve pipeline's always-on batch spans (obs/tracing.py) of the
    engine started at ``since``: each of its ``batches`` batches left one
    record of each stage, and what a stage cost the host a batch."""
    from tmr_tpu import obs

    stages = [r for r in obs.spans() if r["scope"] == "batch"
              and r["name"].startswith("serve.") and r["ts"] >= since]
    once = collections.Counter((r["attrs"]["batch"], r["name"])
                               for r in stages)
    check(len(once) == 4 * batches and set(once.values()) == {1}
          and all(r["attrs"]["rows"] <= r["attrs"]["slots"] for r in stages),
          f"each of {batches} batches left one span of each of the four "
          f"batch stages ({len(stages)} spans)")
    for name in sorted({r["name"] for r in stages}):
        durs = [r["dur"] for r in stages if r["name"] == name]
        say(f"  {name}: mean {1e3 * sum(durs) / len(durs):.3f} ms a batch "
            f"over {len(durs)}")


def phase_serve(pred, size: Size, seed: int) -> None:
    from tmr_tpu.serve import ServeEngine

    say("phase serve")
    reqs = serve_requests(size, seed)
    want, t_direct = _timed(lambda: direct_results(pred, reqs))
    say(f"  direct Predictor calls: first pass {t_direct:.2f}s")
    t_engine = time.perf_counter()
    # bound 1: every dispatch runs the B=1 program the direct call runs —
    # the bitwise property tests/test_serve.py pins on CPU
    with ServeEngine(pred, batch=1, max_wait_ms=5, feature_cache=0) as eng:
        results, compiles = serve_through(eng, reqs, size, seed)
        stats = eng.stats()
    report_batch_stages(t_engine, stats["batches"])
    check(len(results) == 8, "all 8 futures resolved")
    check(all(_bitwise(_np(r), w) for r, w in zip(results, want)),
          "serve results bitwise equal to the direct Predictor calls")
    check(stats["result_cache"]["hits"] >= 1,
          f"the repeated request hit the result cache "
          f"(hits={stats['result_cache']['hits']})")
    check(stats["errors"] == 0, "no serve errors")
    check(compiles == (0, 0),
          f"no compile after warm-up (compile events, XLA backend "
          f"compiles) = {compiles}")
    report_gates("serve")
    _peak_hbm("serve")


def phase_train(size: Size, seed: int) -> None:
    import jax

    import main as cli
    import tmr_tpu.train.loop as loop
    from tmr_tpu.data.synthetic import write_synthetic_fscd147

    say("phase train")
    root = os.path.join(OUT_DIR, "train")
    write_synthetic_fscd147(os.path.join(root, "data"), n_train=3, n_val=1,
                            image_size=size.image_size, square=size.square,
                            seed=seed)
    log = types.SimpleNamespace(losses=[], seconds=[], before=None,
                                after=None)

    class Recorded(loop.Trainer):
        """main()'s own Trainer, observed: a host copy of the parameters
        before the first step and after the last, each step's losses."""

        def _init_state(self, sample_batch, steps_per_epoch):
            super()._init_state(sample_batch, steps_per_epoch)
            log.before = jax.device_get(self.state.params)
            inner = self._train_step

            def step(state, batch):
                (state, losses), t = _timed(lambda: inner(state, batch))
                log.losses.append(
                    {k: float(v) for k, v in losses.items()})
                log.seconds.append(t)
                return state, losses

            self._train_step = step

        def fit(self, *a, **kw):
            super().fit(*a, **kw)
            log.after = jax.device_get(self.state.params)

    # the flags of scripts/train/TMR_FSCD147.sh, except the backbone, the
    # batch, the epochs and the paths
    argv = [
        "--project_name", "chip_smoke", "--datapath",
        os.path.join(root, "data"), "--logpath", os.path.join(root, "log"),
        "--modeltype", "matching_net", "--template_type", "roi_align",
        "--dataset", "FSCD147", "--num_workers", "4", "--max_epochs", "1",
        "--batch_size", "1", "--num_exemplars", "1",
        "--backbone", size.backbone, "--image_size", str(size.image_size),
        "--encoder", "original", "--emb_dim", str(size.emb_dim),
        "--decoder_num_layer", "1", "--decoder_kernel_size", "3",
        "--feature_upsample", "--positive_threshold", "0.5",
        "--negative_threshold", "0.5", "--NMS_cls_threshold", "0.1",
        "--NMS_iou_threshold", "0.5", "--fusion", "--lr", "1e-4",
        "--lr_backbone", "0", "--lr_drop", "--nowandb",
        "--device", jax.devices()[0].platform,
        "--mesh_data", "-1", "--multi_gpu", "--seed", str(seed),
    ]
    trainer_cls, loop.Trainer = loop.Trainer, Recorded
    try:
        _, t = _timed(lambda: cli.main(argv))
    finally:
        loop.Trainer = trainer_cls
    say(f"  main.main(): {t:.1f}s; step seconds (first compiles) "
        f"{[round(s, 3) for s in log.seconds]}")
    for i, losses in enumerate(log.losses):
        say(f"  step {i}: {json.dumps(losses)}")
    check(len(log.losses) == 3, f"three optimizer steps "
                                f"(took {len(log.losses)})")
    check(all(np.isfinite(v) for l in log.losses for v in l.values())
          and all(l["skipped_nonfinite"] == 0 for l in log.losses),
          "finite loss and gradients at every step")
    changed = {
        name: any(
            not np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(log.before[name]),
                jax.tree.leaves(log.after[name])))
        for name in log.before
    }
    say(f"  parameter groups changed: {changed}")
    check(not changed.pop("backbone"), "the frozen backbone is unchanged")
    check(all(changed.values()), "every head parameter group changed")
    report_gates("train")
    _peak_hbm("train")
    # checkpoints of a ViT-B state are hundreds of MB: what comes back from
    # a chip run is this script's output, not its working files
    shutil.rmtree(root)


def tp_heads_agree(pred, eng, size: Size, reqs: list) -> None:
    """What decides the tensor-parallel result: the dense head maps of one
    3-exemplar request (a row per exemplar) and one 1-exemplar request, from
    every replica group's sharded program against the one-device program,
    both traced through the XLA formulations."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.diagnostics import mosaic_kernels_off
    from tmr_tpu.inference import Predictor
    from tmr_tpu.parallel.compat import compile_sharded

    (img3, ex3, _), (img1, ex1, _) = reqs[2], reqs[0]
    images = np.stack([img3] * len(ex3) + [img1])
    boxes = np.concatenate([ex3, ex1])[:, None, :]
    cap = pred.pick_capacity(boxes, size.image_size)
    # its own Predictor: the program traced here must not be handed to a
    # caller that wants the kernels
    one = Predictor(pred.cfg, params=pred.params)
    with mosaic_kernels_off("one-device side of the tensor-parallel "
                            "comparison"):
        want, t_one = _timed(lambda: one._get_fn(cap, loss_fn=_heads_out)(
            one.exec_params(), None, jnp.asarray(images),
            jnp.asarray(boxes))[0])
    body = pred._single_pipeline(
        pred.model.clone(template_capacity=cap), refine=False)
    for target in eng._plan.group_targets:
        pshard, repl = pred._sharded_shardings(target)
        run = compile_sharded(
            lambda p, im, ex: _heads_out(body(p, None, im, ex)[1], ex),
            target.mesh, in_shardings=(pshard, repl, repl),
            out_shardings=repl)
        got, t_tp = _timed(lambda: run(
            eng._run_params(target, "single")[0],
            jax.device_put(images, repl), jax.device_put(boxes, repl)))
        say(f"  dense heads, {len(images)} rows: one-device program "
            f"{t_one:.2f}s, {target.name} program {t_tp:.2f}s (first calls)")
        for name in ("objectness", "regressions"):
            a = np.asarray(got[name], np.float32)
            b = np.asarray(want[name], np.float32)
            rows = np.abs(a - b).reshape(len(images), -1).max(1)
            rel = float(rows.max() / (np.abs(b).max() + 1e-12))
            check(a.shape == b.shape and np.isfinite(a).all()
                  and rel <= TP_REL_TOL,
                  f"{target.name} {name} {a.shape}: tensor-parallel vs "
                  f"one device max rel err {rel:.4g} <= tolerance "
                  f"{TP_REL_TOL} (3-exemplar rows "
                  f"{float(rows[:-1].max() / np.abs(b).max()):.4g}, "
                  f"1-exemplar row "
                  f"{float(rows[-1] / np.abs(b).max()):.4g})")


def phase_mesh(pred, size: Size, seed: int, spec: str = "dp2tp2") -> None:
    """The mesh-sharded serving path against the one-device engine."""
    import jax

    from tmr_tpu.parallel.sharding import serve_param_shardings
    from tmr_tpu.serve import ServeEngine

    say(f"phase mesh ({spec})")
    reqs = serve_requests(size, seed)
    devices = jax.local_devices()
    with ServeEngine(pred, batch=1, max_wait_ms=5, feature_cache=0,
                     devices=devices[:1]) as eng:
        want, _ = serve_through(eng, reqs, size, seed)
    _peak_hbm("one-device engine")
    buckets = [pred.bucket_key(size.image_size, exemplars(size, 1)),
               pred.bucket_key(size.image_size, exemplars(size, 3),
                               multi=True)]
    t0 = time.perf_counter()
    with ServeEngine(pred, batch=1, max_wait_ms=5, feature_cache=0,
                     mesh=spec, warmup_buckets=buckets) as eng:
        plan = eng._plan
        say(f"  engine start, every bucket compiled on every target: "
            f"{time.perf_counter() - t0:.1f}s")
        say(f"  plan: {json.dumps(plan.describe())}")
        say(f"  mesh devices: "
            f"{[[str(d) for d in row] for row in plan.mesh.devices]} "
            f"coords: {[getattr(d, 'coords', None) for d in devices]}")
        modes = {r[2]: plan.mode_for(pred.bucket_key(
            size.image_size, r[1], multi=r[2])) for r in reqs}
        say(f"  bucket modes (multi -> mode): {modes}")
        got, compiles = serve_through(eng, reqs, size, seed)
        stats = eng.stats()
        if plan.tp > 1:
            tp_heads_agree(pred, eng, size, reqs)
        # where the parameters live: every chip of a group holds its shard
        # of a tp-sharded leaf, and no chip holds a whole one
        for target in plan.group_targets:
            qkv = eng._run_params(target, "single")[0]["backbone"][
                "blocks_0"]["attn"]["qkv"]["kernel"]
            held = {sh.device.id: sh.data.shape
                    for sh in qkv.addressable_shards}
            say(f"  {target.name} blocks_0/attn/qkv/kernel {qkv.shape} "
                f"{qkv.sharding.spec}: shards {held}")
            check(set(held) == {d.id for d in target.devices}
                  and all(np.prod(sh) * target.tp == qkv.size
                          for sh in held.values()),
                  f"{target.name}: each of its {target.tp} chips holds "
                  f"1/{target.tp} of the qkv kernel")
    say(f"  warm-up: {json.dumps(stats['warmup'])}")
    check(stats["warmup"]["skipped"] == 0,
          "every warm-up program compiled and ran")
    check(len(got) == 8, "all 8 futures resolved on the mesh")
    check(stats["errors"] == 0, "no serve errors on the mesh")
    check(compiles == (0, 0), f"no compile after warm-up on the mesh "
                              f"{compiles}")
    for i, (g, w, r) in enumerate(zip(got, want, reqs)):
        g, w = _np(g), _np(w)
        if modes[r[2]] == "dp" and plan.tp == 1:
            check(_bitwise(g, w), f"request {i} (dp): bitwise equal to "
                                  f"the one-device engine")
        else:
            # not a check: the one-device engine runs the kernels, the
            # partitioned programs the XLA formulations, and the sets are
            # cut at a threshold where last-bit differences flip peaks
            jac, err = _set_agreement(g, w)
            say(f"  request {i} (tp) against the one-device engine: "
                f"detection-set Jaccard {jac:.3f}, matched scores/boxes "
                f"max abs err {err:.3g}")

    # where everything lives: "all on device 0" must be visible
    target = plan.group_targets[0]
    shardings = serve_param_shardings(pred.params, target.mesh)
    groups = collections.Counter(
        (path[0].key, str(s.spec)) for path, s in
        jax.tree_util.tree_flatten_with_path(shardings)[0])
    say(f"  parameter leaf groups -> PartitionSpec (count), group0 mesh "
        f"{dict(target.mesh.shape)}: "
        + json.dumps({f"{k[0]} {k[1]}": v for k, v in groups.items()}))
    _peak_hbm("mesh")
    for d in devices[:plan.dp * plan.tp]:
        stats_d = d.memory_stats()
        if stats_d is not None:  # the CPU backend of a rehearsal has none
            check(stats_d["peak_bytes_in_use"] > 0,
                  f"device {d.id} held data")
    ran = {g: sum(occ.values())
           for g, occ in stats["per_group_occupancy"].items()}
    say(f"  batches per replica group: {json.dumps(ran)}; per device: "
        f"{json.dumps(stats['per_device_batches'], default=str)}")
    check(all(ran.get(t.name, 0) > 0 for t in plan.group_targets),
          "every replica group ran a batch")
    report_gates("mesh")


# --------------------------------------------------------------- main
def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh-sharded serving phase only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backbone", default=FULL.backbone,
                    help="a name build_backbone knows; a trunk of typed "
                         "layers (kimi_linear_a3b_share2, xing4_a4b_stage6, "
                         "granite4_h_small_share2) runs the predict and "
                         "serve phases")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2

    from jax import monitoring

    from tmr_tpu.utils.cache import enable_compilation_cache

    monitoring.register_event_listener(
        lambda name, **kw: _jax_events.update([name]))
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: _jax_events.update([name]))
    cache_dir = enable_compilation_cache()
    if args.chips == 4:
        # loaded from the persistent cache, replica group 1's tensor-
        # parallel program halts its cores (serve/meshplan.py
        # refuse_cached_subslice_tp): this phase compiles in the process
        jax.config.update("jax_enable_compilation_cache", False)
    import importlib.metadata as md

    import jaxlib

    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu "
        f"{md.version('libtpu')} flax {md.version('flax')}; device_kind "
        f"{dev.device_kind!r} x{len(jax.devices())}")
    entries0 = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f", {'on' if jax.config.jax_enable_compilation_cache else 'OFF'}), "
        f"{entries0} entries")
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    sys.path.insert(0, REPO)

    t0 = time.perf_counter()
    size = FULL._replace(backbone=args.backbone)
    pred = build_predictor(size, args.seed)
    report_autotune(pred.cfg, size, batch=2 if args.chips == 1 else 1)
    verdicts = decide_gates(pred.cfg, size)
    report_formulations(pred.cfg, size, verdicts)
    if args.chips == 4:
        phase_mesh(pred, size, args.seed)
    else:
        phase_predict(pred, size, args.seed, verdicts)
        phase_serve(pred, size, args.seed)
        del pred
        if _is_trunk(size):
            say("phase train: skipped, nothing trains through this "
                "backbone's scan yet (ROADMAP Reach 11)")
        else:
            phase_train(size, args.seed)

    say(f"compile cache: {cache_entries(cache_dir)} entries (was "
        f"{entries0}); persistent-cache hits "
        f"{_jax_events['/jax/compilation_cache/cache_hits']} misses "
        f"{_jax_events['/jax/compilation_cache/cache_misses']}; XLA backend "
        f"compiles {_backend_compiles()}; wall {time.perf_counter() - t0:.0f}s")
    if _failures:
        say(f"FAILED {len(_failures)} check(s):")
        for f in _failures:
            say(f"  - {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

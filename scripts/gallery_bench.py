"""Gallery-tier benchmark: patterns×frames throughput, backbone
amortization, and prefilter recall (tmr_tpu/serve/gallery.py).

Drives a GalleryBank over a synthetic streaming workload and prints ONE
``gallery_report/v1`` JSON document (schema + validator in
tmr_tpu/diagnostics.py):

- **N-loop baseline** — every (frame, pattern) pair through
  ``predict_multi_exemplar``, the way N independent requests would pay:
  the backbone runs frames×N times.
- **Gallery full match** (prefilter off) — the same pairs through
  ``GalleryBank.search``: the fused one-backbone-pass program per cold
  frame. Checks: per-pair results BITWISE-identical to the N-loop, and
  backbone executions == frames (never frames×N), proven from the
  flight recorder's per-program call table (``TMR_FLIGHT`` devtime).
- **Prefilter sweep** — top-k rungs over the coarse channel-pooled
  low-res correlation ranking: detection-level recall vs the full
  match and the full-match invocation cut per rung; the smallest rung
  meeting recall >= 0.99 AND cut >= 2x is ELECTED and persisted to the
  autotune cache (``TMR_GALLERY_PREFILTER_TOPK=auto`` consumes it —
  the prefilter itself stays off/exact by default).
- **N-ladder sweep** — full-bank search wall under ladder caps
  (chunked heads programs vs the one fused rung); the winner persists
  as the measured ``TMR_GALLERY_NMAX``.
- **Index N-sweep** (``--sweep 1000,10000,100000``) — catalog-scale
  banks of random-geometry entries, per point: the exact linear
  prefilter pass timed and kept as the selection oracle, the
  coarse-to-fine sketch index (serve/gallery_index.py) timed on the
  same frame features, SELECTION recall (index top-k ∩ linear top-k)
  against ``--index-recall-floor``, and the argpartition-vs-stable-
  sort tie contract recomputed from the raw scores. The log-log
  wall-vs-N exponents of both arms land in the report
  (``n_sweep.fit``) with the sublinearity check; ``--fleet-patterns P``
  additionally re-runs the PR 17 chaos gauntlet with ``P`` bulk
  patterns per shard and gates on its rc.

The synthetic workload is the WATCHLIST shape: of the N registered
patterns only a fixed quarter are present in the stream frames
(texture instances on a featureless background); the rest are
registered over exact-zero background, whose NCC-centered template
carries ~zero energy — the structural "this pattern is not in the
frame" that frame-relative template extraction permits. Because a
random-init objectness head fires ~uniformly at sigmoid~0.5 (a
meaningless recall denominator), the bench surgically calibrates the
pipeline into a deterministic template-response detector (identity +
mean-centering input projection, identity decoder, channel-mean head
scaled so present-entry responses sit at logit +margin and
absent-entry responses at -margin — see ``_craft_detector``).
Detections then track the template-match response, which is precisely
the signal the coarse prefilter approximates, and the prefilter's job
— rank present patterns above absent ones — is real and measured, not
assumed. Recall is over the UNION of detection locations (feature
cells, coarsened one level to absorb per-entry RoIAlign jitter): the
fraction of the full match's detected locations the prefiltered top-k
still covers. The report carries the union size and the per-side
detection counts so a zero- or saturated-detection run can never read
as a hollow recall pass.

Usage:  python scripts/gallery_bench.py [--tiny] [--out FILE]
        [--patterns N] [--frames F] [--topk K] [--seed S]
        [--sweep N1,N2,...] [--index-recall-floor R] [--nprobe P]
        [--fleet-patterns P]

``--tiny`` (or TMR_BENCH_TINY=1) shrinks geometry so the whole sweep
smoke-runs on CPU (tier-1 runs it under JAX_PLATFORMS=cpu); real
numbers use the 1024^2 deployment geometry. Same one-JSON-line
contract as bench.py via the shared bench_guard.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: detection fields compared bitwise between the fused gallery arm and
#: the N-loop baseline (count rides only under TMR_DECODE_TAIL=device)
_FIELDS = ("boxes", "scores", "refs", "valid")


def _progress(msg: str) -> None:
    print(f"[gallery_bench] {msg}", file=sys.stderr, flush=True)


def _make_workload(size: int, n_patterns: int, n_frames: int, seed: int):
    """(boxes, present, frames): the watchlist shape — of N registered
    patterns, only ``present`` (a fixed quarter of the bank, min 2) are
    IN the stream frames; the rest are registered over featureless
    (zero) background. ``boxes[i]`` is entry i's (1, 4) normalized
    exemplar: present entries' boxes sit over pasted instances of a
    shared texture (patch-aligned, off the borders, so an untrained
    backbone's position sensitivity does not decide the match); absent
    entries' boxes sit over exact-zero background, whose NCC-centered
    template carries ~zero energy — the structural realization of "this
    pattern is not in the frame" that frame-relative template
    extraction permits. Frames differ by a small RELATIVE perturbation
    of the instance pixels (distinct digests per frame): perturbing the
    high-amplitude content keeps the post-LayerNorm token shift small,
    where any fresh content dropped onto the zero background would be
    LayerNorm-AMPLIFIED to unit scale and attention-mixed into every
    token of the frame (measured: a noise block anywhere shifts the
    whole feature map enough to defeat any fixed calibration)."""
    rng = np.random.default_rng(seed)
    step = 16
    # patch-aligned, border-clear, non-overlapping slots
    tops, bpix = None, None
    for cand in range(max((size // 4) // 16 * 16, 16), 0, -16):
        for gap in (step, 0):  # prefer spaced slots, tile if tight
            pos = list(range(step, size - cand - step + 1, cand + gap))
            slots = [(y, x) for y in pos for x in pos]
            if len(slots) >= n_patterns:
                tops, bpix = slots[:n_patterns], cand
                break
        if tops is not None:
            break
    if tops is None:
        raise ValueError(
            f"workload: no patch-aligned layout fits {n_patterns} "
            f"slots at size={size}"
        )
    n_present = max(2, n_patterns // 4)
    stride = max(n_patterns // n_present, 1)
    present = sorted(set(
        list(range(0, n_patterns, stride))[:n_present]
    ) | {0})
    trng = np.random.default_rng(10_000 + seed)
    texture = trng.standard_normal((bpix, bpix, 3)).astype(np.float32) \
        * 3.0
    boxes = [
        np.asarray([[x / size, y / size, (x + bpix) / size,
                     (y + bpix) / size]], np.float32)
        for (y, x) in tops
    ]
    frames = []
    for _f in range(n_frames):
        img = np.zeros((size, size, 3), np.float32)
        for e in present:
            y, x = tops[e]
            img[y:y + bpix, x:x + bpix, :] = texture + rng.standard_normal(
                (bpix, bpix, 3)
            ).astype(np.float32) * 0.05
        frames.append(img)
    return boxes, present, frames


def _craft_detector(pred, frame, boxes, present, capacity: int,
                    margin: float = 4.0) -> dict:
    """Calibrate the pipeline into a deterministic template-response
    detector (see module docstring). Three surgical edits, all on the
    ordinary param tree (no program forks):

    - ``input_proj``: identity into the first C channels with bias
      ``-mean_token`` (the probe frame's spatial-mean BACKGROUND token)
      — the matcher then correlates CENTERED raw features: the NCC
      mean-subtraction that kills the untrained backbone's huge DC
      token similarity, and what makes an absent entry's zero-region
      template carry ~zero energy;
    - objectness decoder: centered-delta identity kernels, zero bias;
    - objectness head: channel mean of the f_tm half, scaled/biased so
      the probe frame's weakest PRESENT-entry self response maps to
      logit ``+margin`` and the strongest ABSENT-entry response to
      ``-margin``.

    Returns the calibration evidence for the report."""
    import jax

    model = pred.model.clone(template_capacity=int(capacity))
    p = jax.tree.map(np.asarray, pred.params)
    bb = pred._get_backbone_fn()
    feats = np.asarray(bb(pred.params, frame[None]))[0]
    # background tokens only: patches of the probe frame that are
    # entirely zero (the workload's featureless background)
    size = int(frame.shape[0])
    ph = size // feats.shape[0]
    patch_zero = np.asarray([
        [not frame[y * ph:(y + 1) * ph, x * ph:(x + 1) * ph].any()
         for x in range(feats.shape[1])]
        for y in range(feats.shape[0])
    ])
    sel = feats[patch_zero] if patch_zero.any() else feats.reshape(
        -1, feats.shape[-1]
    )
    mean_tok = sel.reshape(-1, feats.shape[-1]).mean(axis=0)
    c_in = int(mean_tok.shape[0])
    pk = np.zeros_like(p["input_proj_0"]["kernel"])  # (1, 1, C_in, emb)
    pk[0, 0, np.arange(c_in), np.arange(c_in)] = 1.0
    p["input_proj_0"]["kernel"] = pk
    pb = np.zeros_like(p["input_proj_0"]["bias"])
    pb[:c_in] = -mean_tok
    p["input_proj_0"]["bias"] = pb
    dk = p["decoder_o_0"]["conv_0"]["kernel"]
    ident = np.zeros_like(dk)
    idx = np.arange(dk.shape[2])
    ident[dk.shape[0] // 2, dk.shape[1] // 2, idx, idx] = 1.0
    p["decoder_o_0"]["conv_0"]["kernel"] = ident
    p["decoder_o_0"]["conv_0"]["bias"] = np.zeros_like(
        p["decoder_o_0"]["conv_0"]["bias"]
    )
    pred.params = p

    # probe the crafted matcher response per entry; out["f_tm"] is the
    # relu'd matcher output — exactly what the identity decoder + mean
    # head read (up to the 0.01 leaky slope on negatives)
    probe = jax.jit(
        lambda pp, im, ex: model.apply({"params": pp}, im, ex)["f_tm"][0]
    )
    grid = pred.feature_hw(size)
    present_floor, absent_ceiling = np.inf, -np.inf
    emb = None
    for i, b in enumerate(boxes):
        m = np.asarray(probe(pred.params, frame[None], b[None]))[0]
        emb = m.shape[-1]
        resp = m.mean(axis=-1)
        if i in present:
            cx = int((b[0, 0] + b[0, 2]) / 2 * grid)
            cy = int((b[0, 1] + b[0, 3]) / 2 * grid)
            present_floor = min(
                present_floor,
                float(resp[max(cy - 1, 0):cy + 2,
                           max(cx - 1, 0):cx + 2].max()),
            )
        else:
            absent_ceiling = max(absent_ceiling, float(resp.max()))
    scale = 2.0 * margin / max(present_floor - absent_ceiling, 1e-6)
    bias = -scale * (present_floor + absent_ceiling) / 2.0
    hk = np.zeros_like(p["objectness_head_0"]["conv"]["kernel"])
    hk[0, 0, -emb:, 0] = scale / emb
    p["objectness_head_0"]["conv"]["kernel"] = hk
    p["objectness_head_0"]["conv"]["bias"] = np.asarray(
        [bias], np.float32
    )
    pred.params = p
    return {"margin": margin,
            "present_floor": round(present_floor, 6),
            "absent_ceiling": round(absent_ceiling, 6),
            "separated": bool(present_floor > absent_ceiling),
            "scale": round(scale, 4)}


def _sweep_boxes(n: int, seed: int) -> list:
    """``n`` random-geometry (1, 4) normalized exemplar boxes for
    catalog-scale banks. The patch-aligned watchlist layout tops out at
    ~hundreds of non-overlapping slots; index sweep points need
    10^3..10^5 entries whose SELECTION (not detection quality) is under
    test, so arbitrary overlapping geometry is exactly right."""
    rng = np.random.default_rng(seed)
    wh = rng.uniform(0.04, 0.25, size=(n, 2)).astype(np.float32)
    xy = rng.uniform(size=(n, 2)).astype(np.float32) * (1.0 - wh)
    boxes = np.concatenate([xy, xy + wh], axis=1)
    return [boxes[i:i + 1] for i in range(n)]


def _linear_scan(bank, feats):
    """The exact linear prefilter pass, run bench-side so the sweep
    can time it AND keep every raw per-entry score for the stable-sort
    tie reference (the bank's own scan tail-caps its scores dict at
    catalog scale)."""
    names, chunks = [], []
    for g in bank._groups_locked():
        fn = bank._pred._get_gallery_prefilter_fn(g.n_bucket, g.k_bucket)
        s = np.asarray(fn(feats, g.ex_dev, g.k_dev, g.n_dev))
        names.extend(g.names)
        chunks.append(s[:g.n_real])
    return names, np.concatenate(chunks)


def _loglog_exponent(ns, walls):
    """Least-squares slope of log(wall) vs log(N) — the measured
    scaling exponent (1.0 = linear, 0.5 = sqrt)."""
    if len(ns) < 2 or any(w <= 0 for w in walls):
        return None
    slope = np.polyfit(np.log(np.asarray(ns, np.float64)),
                       np.log(np.asarray(walls, np.float64)), 1)[0]
    return round(float(slope), 3)


def _run_fleet_probe(patterns_per_shard: int) -> dict:
    """Re-run the PR 17 serve chaos gauntlet with the streamed
    bulk-ingest phase at ``patterns_per_shard`` — the index/bulk paths
    proven under kills, corrupt replicas, and journal faults."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "scripts", "serve_chaos_probe.py"),
         "--tiny", "--patterns-per-shard", str(patterns_per_shard)],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    doc = {}
    for ln in proc.stdout.splitlines():
        try:
            doc = json.loads(ln)
            break
        except ValueError:
            continue
    out = {
        "patterns_per_shard": int(patterns_per_shard),
        "rc": int(proc.returncode),
        "checks": doc.get("checks"),
    }
    if "error" in doc:
        out["error"] = doc["error"]
    bulk = next((p for p in doc.get("phases", ())
                 if isinstance(p, dict) and p.get("name") == "bulk_ingest"),
                None)
    if bulk is not None:
        out["bulk_ingest"] = bulk
    return out


def _run_sweep(pred, size: int, args) -> dict:
    """The index N-sweep (module docstring). Per point: one bank holds
    both arms — the same frame features flow through the exact linear
    scan (oracle + timing) and the sketch-index election (timing +
    recall + counters)."""
    import jax.numpy as jnp

    from tmr_tpu.serve import GalleryBank
    from tmr_tpu.serve.gallery import _topk_flat

    ns = sorted({int(x) for x in args.sweep.split(",") if x.strip()})
    floor = float(args.index_recall_floor)
    rng = np.random.default_rng(args.seed + 77)
    # structured query frame (low-frequency field + mild detail): the
    # regime a GEOMETRIC index serves. Real stream frames have smooth
    # feature maps, so nearby boxes score nearby; pure white noise
    # decorrelates at the patch scale and defeats any coarse routing —
    # an adversarial input the index answers with its counted linear
    # fallback, not a recall claim
    coarse = rng.standard_normal((8, 8, 3)).astype(np.float32)
    frame = np.repeat(np.repeat(coarse, size // 8, 0), size // 8, 1)
    frame = frame + rng.standard_normal(
        (size, size, 3)
    ).astype(np.float32) * 0.1
    bb = pred._get_backbone_fn()
    feats = bb(pred.exec_params(), jnp.asarray(frame[None]))
    points = []
    for n in ns:
        topk = max(1, min(32, n // 4))
        _progress(f"sweep N={n}: registering")
        boxes = _sweep_boxes(n, args.seed + n)
        t0 = time.perf_counter()
        bank = GalleryBank(pred, feature_cache=0, max_n_bucket=32,
                           index=True, index_min_n=1,
                           index_nprobe=args.nprobe or None)
        for i, b in enumerate(boxes):
            bank.register(f"sku{i:06d}", b)
        reg_s = time.perf_counter() - t0
        groups = bank._groups_locked()
        # warm pass: compiles both arms' programs; the first index
        # election also pays the k-means build (recorded via
        # index_stats, kept out of the timed query)
        t0 = time.perf_counter()
        _linear_scan(bank, feats)
        bank._prefilter_select(feats, groups, topk, jnp)
        warm_s = time.perf_counter() - t0
        c0 = {k: bank.counters[k]
              for k in ("index_queries", "index_probes",
                        "index_candidates", "index_fallbacks")}
        t0 = time.perf_counter()
        names, flat = _linear_scan(bank, feats)
        lin_idx = _topk_flat(flat, topk)
        linear_ms = (time.perf_counter() - t0) * 1e3
        linear_sel = {names[i] for i in lin_idx}
        # the argpartition/tie contract, recomputed from raw scores:
        # identical selection SET to the stable descending sort's
        # first top-k (ties in flat group order)
        ranked = sorted(range(len(names)), key=lambda i: -flat[i])
        off_exact = {names[i] for i in ranked[:topk]} == linear_sel
        t0 = time.perf_counter()
        index_sel, _ = bank._prefilter_select(feats, groups, topk, jnp)
        index_ms = (time.perf_counter() - t0) * 1e3
        delta = {k: int(bank.counters[k] - c0[k]) for k in c0}
        istats = bank.index_stats()
        recall = len(index_sel & linear_sel) / float(topk)
        points.append({
            "n": int(n), "topk": int(topk),
            "register_s": round(reg_s, 3),
            "warm_s": round(warm_s, 3),
            "linear_ms": round(linear_ms, 3),
            "index_ms": round(index_ms, 3),
            "recall": round(recall, 4),
            "off_exact": bool(off_exact),
            "indexed": bool(delta["index_queries"] >= 1
                            and delta["index_fallbacks"] == 0),
            "centroids": int(istats.get("centroids") or 0),
            "probes": delta["index_probes"],
            "candidates": delta["index_candidates"],
            "groups": len(groups),
            "rebuild_wall_s": istats.get("rebuild_wall_s"),
        })
        _progress(
            f"N={n}: linear {linear_ms:.1f}ms index {index_ms:.1f}ms "
            f"recall {recall:.3f} (probes {delta['index_probes']}, "
            f"candidates {delta['index_candidates']})"
        )
    exp_linear = _loglog_exponent([p["n"] for p in points],
                                  [p["linear_ms"] for p in points])
    exp_index = _loglog_exponent([p["n"] for p in points],
                                 [p["index_ms"] for p in points])
    if exp_index is not None:
        # sublinear in measured exponent, or decisively below the
        # linear arm's own measured scaling (fixed per-call dispatch
        # overhead can flatten BOTH curves at small N)
        sublinear = bool(exp_index <= 0.8
                         or (exp_linear is not None
                             and exp_index <= 0.8 * exp_linear))
    else:  # single-point sweep: no fit — gate on the direct wall win
        sublinear = bool(points
                         and points[-1]["index_ms"]
                         <= points[-1]["linear_ms"])
    checks = {
        "index_sublinear": sublinear and all(p["indexed"]
                                             for p in points),
        "index_recall_ok": bool(points) and all(
            p["recall"] >= floor for p in points
        ),
        "index_off_exact": bool(points) and all(
            p["off_exact"] for p in points
        ),
    }
    sweep = {
        "points": points,
        "recall_floor": floor,
        "fit": {"linear_exponent": exp_linear,
                "index_exponent": exp_index},
        "checks": checks,
    }
    if args.fleet_patterns > 0:
        _progress(f"fleet probe re-run: {args.fleet_patterns} "
                  "patterns/shard through the bulk sink")
        probe = _run_fleet_probe(args.fleet_patterns)
        sweep["fleet_probe"] = probe
        checks["fleet_probe_ok"] = bool(probe["rc"] == 0)
        _progress(f"fleet probe rc={probe['rc']}")
    return sweep


def _det_count(result: dict) -> int:
    return int(np.asarray(result["valid"]).sum())


def _det_cells(result: dict, grid: int) -> set:
    """Detected locations as COARSE feature cells (one level coarser
    than the grid, absorbing the one-cell RoIAlign jitter between
    entries' near-identical templates)."""
    valid = np.asarray(result["valid"])[0]
    refs = np.asarray(result["refs"])[0]
    out = set()
    for r in refs[valid]:
        out.add((int(r[0] * grid) // 2, int(r[1] * grid) // 2))
    return out


def _program_calls(kinds) -> dict:
    """Executed-call counts per devtime program kind (warmup calls
    included — an execution is an execution)."""
    from tmr_tpu import obs

    out: dict = {}
    for prog in obs.mfu_report()["programs"]:
        if prog["kind"] in kinds:
            out[prog["kind"]] = out.get(prog["kind"], 0) \
                + int(prog["calls"]) + int(prog["warmup_calls"])
    return out


def _run(cancel_watchdog, argv=None) -> int:
    from tmr_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke geometry (also TMR_BENCH_TINY=1)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document to this path")
    ap.add_argument("--patterns", type=int, default=8,
                    help="bank size N (acceptance floor: 8)")
    ap.add_argument("--frames", type=int, default=4,
                    help="measured stream frames")
    ap.add_argument("--topk", type=int, default=None,
                    help="pin one prefilter top-k instead of sweeping")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated catalog sizes for the index "
                         "N-sweep (e.g. 1000,10000,100000; empty = "
                         "skipped)")
    ap.add_argument("--index-recall-floor", type=float, default=0.9,
                    help="minimum index-vs-linear selection recall "
                         "per sweep point")
    ap.add_argument("--nprobe", type=int, default=0,
                    help="buckets probed per indexed sweep query "
                         "(0 = auto = ceil(sqrt(C)))")
    ap.add_argument("--fleet-patterns", type=int, default=0,
                    help="re-run the serve chaos gauntlet with this "
                         "many bulk patterns per shard (0 = skipped)")
    args = ap.parse_args(argv)

    tiny = args.tiny or os.environ.get("TMR_BENCH_TINY", "") not in (
        "", "0", "false"
    )
    size = int(os.environ.get("TMR_BENCH_SIZE", 256 if tiny else 1024))
    dtype = "float32" if tiny else "bfloat16"

    import jax

    from tmr_tpu import obs
    from tmr_tpu.config import preset
    from tmr_tpu.diagnostics import (
        GALLERY_REPORT_SCHEMA,
        validate_gallery_report,
    )
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve import GalleryBank
    from tmr_tpu.utils.autotune import record_gallery_winners

    _progress(f"backend: {jax.devices()[0]} size={size} tiny={tiny} "
              f"patterns={args.patterns} frames={args.frames}")
    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=size,
                 compute_dtype=dtype, batch_size=1)
    pred = Predictor(cfg)
    _progress("init_params (jitted init)")
    pred.init_params(seed=0, image_size=size)

    n_pat, n_frames = int(args.patterns), int(args.frames)
    boxes, present, frames = _make_workload(size, n_pat, n_frames,
                                            args.seed)
    wall0 = time.perf_counter()
    # the flight recorder is the backbone-amortization witness: every
    # program execution lands in the devtime call table
    obs.flight_configure(enabled=True)

    cap0 = pred.pick_capacity(boxes[0], size)
    calibration = _craft_detector(pred, frames[0], boxes, present, cap0)
    _progress(f"calibrated detector (present={present}): {calibration}")

    # ladder cap pinned to the bank size: the acceptance phases must
    # measure the fused single-group arm deterministically, not inherit
    # whatever a previous sweep persisted into the autotune cache
    bank = GalleryBank(pred, feature_cache=8, max_n_bucket=32)
    for i, box in enumerate(boxes):
        bank.register(f"pattern{i}", box)
    stats0 = bank.stats()
    _progress(f"bank: {stats0['entries']} entries, groups "
              f"{stats0['groups']}")

    # ---- warmup: compile the N-loop program and the fused gallery
    # program outside every timed window, on throwaway frames
    rng_w = np.random.default_rng(991)
    warm = rng_w.standard_normal((size, size, 3)).astype(np.float32)
    _progress("warmup compiles (n-loop + fused gallery)")
    pred.predict_multi_exemplar(warm[None], boxes[0], k_real=1)
    bank.search(rng_w.standard_normal((size, size, 3)).astype(np.float32))

    # ---- N-loop baseline: one predict_multi_exemplar per (frame,
    # pattern) pair — the N-independent-requests cost
    _progress("phase n_loop baseline")
    nloop: dict = {}
    t0 = time.perf_counter()
    for f, frame in enumerate(frames):
        for i, box in enumerate(boxes):
            dets = pred.predict_multi_exemplar(frame[None], box, k_real=1)
            nloop[(f, i)] = {
                k: np.asarray(dets[k]) for k in _FIELDS if k in dets
            }
    jax.block_until_ready(dets["scores"])
    nloop_dt = time.perf_counter() - t0
    nloop_tput = (n_pat * n_frames) / nloop_dt
    _progress(f"n_loop: {nloop_tput:.3f} pattern-frames/s")

    # ---- gallery full match (prefilter off), fresh devtime window
    _progress("phase gallery full match")
    from tmr_tpu.obs import devtime

    devtime.reset()
    fm0 = bank.counters["full_match_entries"]
    gallery: dict = {}
    t0 = time.perf_counter()
    for f, frame in enumerate(frames):
        results = bank.search(frame)
        for i in range(n_pat):
            gallery[(f, i)] = results[f"pattern{i}"]
    gal_dt = time.perf_counter() - t0
    gal_tput = (n_pat * n_frames) / gal_dt
    by_program = _program_calls(
        ("gallery", "gallery_heads", "backbone", "multi")
    )
    backbone_execs = by_program.get("gallery", 0) \
        + by_program.get("backbone", 0)
    full_matches_off = bank.counters["full_match_entries"] - fm0
    counters_full = dict(bank.counters)
    _progress(
        f"gallery: {gal_tput:.3f} pattern-frames/s "
        f"({gal_tput / nloop_tput:.2f}x n-loop), backbone executions "
        f"{backbone_execs} for {n_frames} frames (by_program "
        f"{by_program})"
    )

    # ---- fused-arm exactness: bitwise vs the N-loop, per pair
    mismatches = 0
    for key, want in nloop.items():
        got = gallery[key]
        if not all(
            np.array_equal(np.asarray(want[k]), np.asarray(got[k]))
            for k in _FIELDS
        ):
            mismatches += 1
    exact = mismatches == 0
    grid = pred.feature_hw(size)
    # the full match's detected locations per frame, as the UNION over
    # entries of coarse feature cells — the recall denominator (entry
    # detection sets nearly coincide on the counting workload, so the
    # union is what a stream consumer actually loses to the prefilter)
    full_union = {
        f: set().union(*(
            _det_cells(gallery[(f, i)], grid) for i in range(n_pat)
        ))
        for f in range(n_frames)
    }
    total_dets = sum(_det_count(r) for r in gallery.values())
    union_cells = sum(len(u) for u in full_union.values())
    slots = int(np.asarray(gallery[(0, 0)]["valid"]).shape[1])
    _progress(f"exactness: {mismatches} mismatching pairs of "
              f"{len(nloop)}; detections {total_dets} "
              f"({union_cells} union cells, {slots} slots/entry)")

    # ---- prefilter sweep: union recall + invocation cut per top-k rung
    if args.topk:
        rung_list = [int(args.topk)]
    else:
        rung_list = sorted({
            max(1, n_pat // 4), max(1, n_pat // 2),
            max(1, (3 * n_pat) // 4),
        })
    rungs = []
    elected = None
    for topk in rung_list:
        _progress(f"prefilter top-{topk}")
        fm0 = bank.counters["full_match_entries"]
        covered = 0
        for f, frame in enumerate(frames):
            results = bank.search(frame, prefilter_topk=topk)
            pre_union: set = set()
            for i in range(n_pat):
                pre_union |= _det_cells(results[f"pattern{i}"], grid) \
                    if "refs" in results[f"pattern{i}"] else set()
            covered += len(pre_union & full_union[f])
        full_matches = bank.counters["full_match_entries"] - fm0
        recall = (covered / union_cells) if union_cells else 0.0
        cut = (n_pat * n_frames) / max(full_matches, 1)
        rungs.append({
            "topk": topk,
            "recall": round(recall, 4),
            "full_matches": full_matches,
            "full_matches_without": n_pat * n_frames,
            "invocation_cut": round(cut, 3),
        })
        if elected is None and recall >= 0.99 and cut >= 2.0:
            elected = topk
        _progress(f"top-{topk}: recall {recall:.4f}, cut {cut:.2f}x")

    # ---- N-ladder sweep: full-bank search wall under ladder caps
    # (chunked heads programs vs the fused rung) — the measured
    # TMR_GALLERY_NMAX, elected like the batch bound
    ladder_rungs = sorted({
        r for r in (2, 4, 8, 16, 32) if r <= n_pat
    } | {n_pat if n_pat in (1, 2, 4, 8, 16, 32) else 0} - {0})
    ladder = []
    sweep_frames = frames[: min(2, len(frames))]
    for rung in ladder_rungs:
        b = GalleryBank(pred, feature_cache=0, max_n_bucket=rung)
        for i, box in enumerate(boxes):
            b.register(f"pattern{i}", box)
        for frame in sweep_frames:  # warm this rung's programs
            b.search(frame)
        t0 = time.perf_counter()
        for frame in sweep_frames:
            b.search(frame)
        ladder.append({"n_bucket": rung, "wall_s": round(
            time.perf_counter() - t0, 4
        )})
        _progress(f"ladder rung {rung}: {ladder[-1]['wall_s']}s")
    # election policy (the pick_quant decisive-win shape): the LARGEST
    # rung is the structural default — one fused single-group program,
    # bitwise arm intact — and a smaller rung must beat it by >10% to
    # win, so timing noise can never chunk production banks
    nmax_winner = None
    if ladder:
        best = max(r["n_bucket"] for r in ladder)
        best_wall = next(r["wall_s"] for r in ladder
                         if r["n_bucket"] == best)
        for r in sorted(ladder, key=lambda r: r["n_bucket"]):
            if r["wall_s"] < 0.9 * best_wall:
                best, best_wall = r["n_bucket"], r["wall_s"]
                break
        nmax_winner = best
    record_gallery_winners(size, nmax=nmax_winner, topk=elected)

    # ---- index N-sweep: sketch index vs linear scan at catalog scale
    n_sweep = _run_sweep(pred, size, args) if args.sweep else None

    # a recall pass must be NON-HOLLOW: detections exist and do not
    # saturate the slot capacity (a fire-everywhere detector makes any
    # union recall read 1.0)
    nontrivial = bool(
        union_cells > 0
        and total_dets < n_frames * n_pat * slots // 2
    )
    prefilter_recall_ok = bool(elected is not None and nontrivial)
    elected_rec = next(
        (r for r in rungs if r["topk"] == elected), None
    )
    report = {
        "schema": GALLERY_REPORT_SCHEMA,
        "device": str(jax.devices()[0]),
        "config": {
            "image_size": size,
            "patterns": n_pat,
            "frames": n_frames,
            "present": list(present),
            "seed": int(args.seed),
            "dtype": dtype,
        },
        "bank": {
            "entries": stats0["entries"],
            "groups": stats0["groups"],
            "max_n_bucket": stats0["max_n_bucket"],
        },
        "throughput": {
            "gallery_pattern_frames_per_sec": round(gal_tput, 3),
            "n_loop_pattern_frames_per_sec": round(nloop_tput, 3),
            "speedup": round(gal_tput / nloop_tput, 3),
        },
        "backbone": {
            "frames": n_frames,
            "executions": int(backbone_execs),
            "pattern_frame_pairs": n_pat * n_frames,
            "by_program": by_program,
        },
        "exact": {
            "pairs": len(nloop),
            "mismatches": mismatches,
            "total_detections": total_dets,
            "union_cells": union_cells,
            "slots_per_entry": slots,
        },
        "calibration": calibration,
        "prefilter": {
            "rungs": rungs,
            "elected_topk": elected,
            "recall_at_elected": (
                elected_rec["recall"] if elected_rec else None
            ),
            "cut_at_elected": (
                elected_rec["invocation_cut"] if elected_rec else None
            ),
        },
        "ladder": {"rungs": ladder, "elected_nmax": nmax_winner},
        **({"n_sweep": n_sweep} if n_sweep is not None else {}),
        "counters": counters_full,
        "checks": {
            "bitwise_exact": bool(exact),
            "backbone_amortized": bool(backbone_execs == n_frames),
            "full_match_entries_off": int(full_matches_off),
            "speedup_vs_n_loop": round(gal_tput / nloop_tput, 3),
            "prefilter_recall_ok": prefilter_recall_ok,
            "prefilter_cut_ok": bool(
                elected_rec is not None
                and elected_rec["invocation_cut"] >= 2.0
            ),
            "detections_nonzero": bool(total_dets > 0),
            "detections_nontrivial": nontrivial,
        },
    }
    report["wall_s"] = round(time.perf_counter() - wall0, 1)
    problems = validate_gallery_report(report)
    if problems:  # self-check: the emitted document must validate
        report["validator_problems"] = problems

    cancel_watchdog()  # before the success print: no success-then-watchdog
    line = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


def main(argv=None) -> int:
    """One gallery_report/v1 JSON line on stdout, success or not: the
    shared bench_guard (same watchdog bench.py runs under) funnels
    wedges and crashes into a contractual error record."""
    from tmr_tpu.diagnostics import GALLERY_REPORT_SCHEMA
    from tmr_tpu.utils.bench_guard import run_guarded

    return run_guarded(
        lambda cancel: _run(cancel, argv),
        lambda msg: print(
            json.dumps({"schema": GALLERY_REPORT_SCHEMA, "error": msg}),
            flush=True,
        ),
    )


if __name__ == "__main__":
    sys.exit(main())

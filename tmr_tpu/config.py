"""Typed configuration for the framework.

Mirrors the reference CLI surface (reference ``main.py:14-83``) so a user of
the reference finds every knob, but as one typed dataclass threaded through
the stack instead of a raw argparse namespace. The shell scripts under the
reference's ``scripts/`` become the presets at the bottom of this file.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class Config:
    # seed
    seed: int = 42

    # logging (reference main.py:21-25)
    project_name: str = "Few-Shot Pattern Detection"
    logpath: str = "./outputs/default"
    nowandb: bool = True
    AP_term: int = 5
    best_model_count: bool = False

    # dataset (reference main.py:28-33)
    datapath: str = "/home/"
    dataset: str = "RPINE"
    batch_size: int = 1
    # TPU extension: eval/test batch size (reference pins 1,
    # datamodules.py:27,47,50 — kept as the parity default). >1 batches
    # same-size-bucket images through the fused eval program; per-image
    # outputs and metrics are unchanged, logged losses become batch means.
    eval_batch_size: int = 1
    num_workers: int = 8
    num_exemplars: int = 1
    image_size: int = 1024

    # training (reference main.py:36-38)
    resume: bool = False
    max_epochs: int = 30
    multi_gpu: bool = False  # kept for parity; TPU uses `mesh` below

    # optimizer (reference main.py:41-45)
    weight_decay: float = 1e-4
    clip_max_norm: float = 0.1
    lr_drop: bool = False
    lr: float = 1e-4
    lr_backbone: float = 1e-5
    # TPU extension: accumulate gradients over k micro-steps before one
    # optimizer update (optax.MultiSteps) — a single chip reaches the
    # reference's 4-GPU effective batch (4 x bs4) with grad_accum_steps=4
    grad_accum_steps: int = 1

    # eval / viz (reference main.py:48-51)
    eval: bool = False
    visualize: bool = False

    # model (reference main.py:54-71)
    modeltype: str = "matching_net"
    emb_dim: int = 512
    no_matcher: bool = False
    squeeze: bool = False
    fusion: bool = False
    positive_threshold: float = 0.7
    negative_threshold: float = 0.7
    NMS_cls_threshold: float = 0.1
    NMS_iou_threshold: float = 0.15
    refine_box: bool = False
    # SAM .pth for the --refine_box mask decoder (the reference downloads
    # from fbaipublicfiles at refiner construction, box_refine.py:41-60;
    # airgapped runs fall back to random init with a warning)
    refiner_checkpoint: Optional[str] = None
    ablation_no_box_regression: bool = False
    template_type: str = "roi_align"  # or "prototype"
    feature_upsample: bool = False
    eval_multi_scale: bool = False  # dead flag in reference; kept for parity
    regression_scaling_imgsize: bool = False
    regression_scaling_WH_only: bool = False
    focal_loss: bool = False

    # backbone (reference main.py:74-76)
    backbone: str = "resnet50"
    encoder: str = "original"
    dilation: bool = True

    # heads (reference main.py:79-80)
    decoder_num_layer: int = 1
    decoder_kernel_size: int = 3

    # ---- TPU-native additions (no reference equivalent) ----
    device: str = "tpu"  # BASELINE.json requires a --device tpu flag
    # Static template-kernel capacities (odd). 127/191 cover exemplars up to
    # the full upsampled feature grid at 1024/1536 input (128/192 cells), so
    # no legal exemplar ever clamps (reference roi_align handles any size,
    # template_matching.py:55-76); capacities > 65 run the FFT correlation
    # path (ops/xcorr.py) whose cost is independent of template size.
    template_buckets: Tuple[int, ...] = (9, 17, 33, 65, 127, 191)
    # fixed detection capacity. AP's maxDets tops out at 1100
    # (log_utils.py:193), so 2000 leaves headroom for MAE/RMSE counting on
    # extremely dense images (the reference's post-NMS count is unbounded;
    # ours caps here — only images with > max_detections surviving peaks
    # can diverge).
    max_detections: int = 2000
    # compute dtype for the encoder ("bfloat16" or "float32").
    compute_dtype: str = "bfloat16"
    # when set, the train loop captures an XLA profiler trace of the first
    # epoch into this directory (view with TensorBoard/xprof).
    profile_dir: Optional[str] = None
    # rematerialize ViT blocks on backward (jax.checkpoint): activation
    # memory ~1/depth at the cost of one extra forward — enables larger
    # train batches / the 1536 bucket on small-HBM chips.
    remat_backbone: bool = False
    # mesh axes: (data, model). Products must equal device count.
    mesh_shape: Tuple[int, int] = (1, 1)
    # pipeline parallelism (--mesh_pipe): GPipe stages over a 'pipe' axis.
    # Must equal the backbone's stage count (= #global-attention blocks:
    # 4 for vit_b/vit_h). pp_microbatches 0 -> one per stage.
    mesh_pipe: int = 1
    pp_microbatches: int = 0
    max_gt_boxes: int = 800  # padding capacity for GT boxes per image

    @property
    def box_reg(self) -> bool:
        return not self.ablation_no_box_regression


def preset(name: str, **overrides) -> Config:
    """Named presets replacing the reference's shell scripts (scripts/*.sh)."""
    base = dict(
        backbone="sam_vit_b",
        emb_dim=512,
        template_type="roi_align",
        feature_upsample=True,
        fusion=True,
        positive_threshold=0.5,
        negative_threshold=0.5,
        lr=1e-4,
        lr_backbone=0.0,
        lr_drop=True,
        max_epochs=200,
        batch_size=4,
    )
    presets = {
        # eval NMS cls thresholds per scripts/eval/*.sh:19
        "TMR_FSCD147": dict(dataset="FSCD147", NMS_cls_threshold=0.25,
                            NMS_iou_threshold=0.5),
        "TMR_RPINE": dict(dataset="RPINE", NMS_cls_threshold=0.4,
                          NMS_iou_threshold=0.5),
        "TMR_FSCD_LVIS_Seen": dict(dataset="FSCD_LVIS_Seen",
                                   NMS_cls_threshold=0.1,
                                   NMS_iou_threshold=0.5),
        "TMR_FSCD_LVIS_Unseen": dict(dataset="FSCD_LVIS_Unseen",
                                     NMS_cls_threshold=0.1,
                                     NMS_iou_threshold=0.5),
    }
    if name not in presets:
        raise KeyError(f"unknown preset {name!r}; options: {sorted(presets)}")
    base.update(presets[name])
    base.update(overrides)
    return Config(**base)


#: The TMR_* environment-knob registry — the single source of truth for
#: every env knob consumed anywhere under ``tmr_tpu/``. The knob surface
#: grew across PRs 1-5 with no one place saying what exists; tier-1 now
#: enforces (tests/test_small_utils.py, AST scan of every ``os.environ``
#: / ``os.getenv`` read) that a knob consumed in code appears here and a
#: knob listed here is actually consumed — documentation that cannot go
#: stale. Values are one-line summaries; QUICKSTART_RUN.md carries the
#: long-form usage for the user-facing ones.
ENV_KNOBS = {
    # formulation dispatch (trace-time; autotune exports winners here)
    "TMR_XCORR_IMPL": "template-correlation formulation: auto|conv|"
        "convnhwc|vmap|fft|pallas",
    "TMR_XCORR_IMPL_SMALL": "small-bucket override of TMR_XCORR_IMPL",
    "TMR_XCORR_PRECISION": "correlation MXU precision: highest|default|"
        "bf16 (decisive-win elected)",
    "TMR_DECODER_IMPL": "decoder-tail formulation: auto|xla|fused "
        "(ops/fused_heads.py, oracle-gated)",
    "TMR_QUANT": "int8-weight quantized tail: off|int8|auto "
        "(ops/quant.py, tiered-oracle-gated)",
    "TMR_QUANT_STORAGE": "offline int8 param-tree storage: off|int8 "
        "(programs receive int8 weight leaves; bitwise the fake-quant "
        "numerics, equality-tier gated)",
    "TMR_QUANT_KERNEL": "stored-int8 matmul arm: auto|dequant|int8dot|"
        "pallas (dequant = bitwise pin; int8dot/pallas = both-operand "
        "int8, tolerance-gated)",
    "TMR_DECODE_TAIL": "detection decode tail: host|device "
        "(device = on-device compaction, self-check-gated)",
    # kill-switches (gates refuse with a recorded cause)
    "TMR_NO_FLASH_ATTN": "force-disable the flash attention family",
    "TMR_NO_PALLAS_XCORR": "force-disable the Pallas correlation kernel",
    "TMR_NO_FUSED_HEADS": "force-disable the fused decoder-head path",
    "TMR_NO_DEVICE_TAIL": "force-disable the device decode tail",
    "TMR_NO_PALLAS_INT8": "force-disable the Mosaic int8 MXU matmul "
        "kernel",
    # autotune / bench machinery
    "TMR_AUTOTUNE_CACHE": "autotune winner-cache path (0/off disables)",
    "TMR_AUTOTUNE_FORCE": "re-sweep even when cached winners exist",
    "TMR_AUTOTUNE_SEED": "seed-cache path promoted into a fresh cache",
    "TMR_BENCH_BATCH": "bench.py batch-size override",
    "TMR_BENCH_ALARM": "bench.py watchdog timeout seconds",
    "TMR_BENCH_STAGES": "bench.py per-stage tail timings (0 skips)",
    # serving layer
    "TMR_SERVE_BATCH": "ServeEngine release-batch override",
    "TMR_SERVE_MAX_WAIT_MS": "ServeEngine micro-batch wait bound",
    "TMR_SERVE_EXEMPLAR_CACHE": "result-cache capacity (entries)",
    "TMR_SERVE_FEATURE_CACHE": "device feature-cache capacity (entries)",
    "TMR_SERVE_FEATURE_CACHE_MB": "byte bound on the device feature "
        "cache (MB; unset = count-only, the original behavior)",
    # gallery tier (serve/gallery.py: persistent template banks +
    # streaming-image search)
    "TMR_GALLERY_PREFILTER_TOPK": "coarse-prefilter top-k: 0/unset = "
        "off (exact), auto = the gallery_bench-elected winner, int = "
        "that many entries earn the full match per frame",
    "TMR_GALLERY_NMAX": "gallery N-bucket ladder cap (entries per "
        "fused program; default the measured winner, else 32)",
    "TMR_GALLERY_FEATURE_CACHE": "gallery frame-feature cache capacity "
        "(entries)",
    "TMR_GALLERY_FEATURE_CACHE_MB": "byte bound on the gallery "
        "frame-feature cache (MB)",
    # coarse-to-fine sketch index (serve/gallery_index.py; off =
    # today's exact linear prefilter scan, bitwise)
    "TMR_GALLERY_INDEX": "gallery sketch index: unset/0/off = linear "
        "prefilter scan (exact), anything else = IVF coarse-to-fine "
        "candidate election (sublinear in N; recall bench-pinned)",
    "TMR_GALLERY_INDEX_NPROBE": "indexed prefilter: how many coarse "
        "buckets' members earn the exact sketch rescore per frame "
        "(0/unset = auto = max(2*ceil(sqrt(centroids)), "
        "min(centroids, topk)))",
    "TMR_GALLERY_INDEX_MIN_N": "banks below this entry count stay on "
        "the linear scan even with the index on (default 256 — the "
        "index only pays past catalog scale)",
    "TMR_GALLERY_INDEX_REBUILD": "register/evict churn fraction of the "
        "built entry count past which an indexed query reclusters "
        "(default 0.25; every rebuild leaves a journaled stamp)",
    # replicated gallery fleet (serve/gallery_fleet.py; off unless a
    # fleet is constructed — the single-bank path never reads these)
    "TMR_GALLERY_REPLICAS": "gallery fleet: copies kept per pattern "
        "(primary + mirrors) on live workers; fewer live workers than "
        "R counts as under-replication, never an error (default 2)",
    "TMR_GALLERY_FLEET_TIMEOUT_S": "gallery fleet: per-round-trip "
        "timeout for pattern pushes and fan-out searches — past it the "
        "shard degrades to partition_unavailable for that frame",
    "TMR_SERVE_MESH": "serving device mesh spec (dp<N>/tp<M>, e.g. "
        "dp4, tp4, dp2tp2); unset = unsharded round-robin serving",
    "TMR_SERVE_AOT": "ahead-of-time compile+warmup of the bucketed "
        "program set at engine start (default: on under a mesh plan "
        "or explicit warmup buckets; 0 disables)",
    "TMR_SERVE_WARMUP_TIMEOUT_S": "AOT warmup wall-clock budget; "
        "programs past it compile lazily instead",
    "TMR_SERVE_TP_SIZE": "image-size floor for tensor-parallel replica-"
        "group execution (buckets >= it run tp, smaller fan out dp)",
    "TMR_SERVE_DEADLINE_MS": "default per-request deadline; expired "
        "requests shed before device work (0/unset = none)",
    "TMR_SERVE_DRAIN_TIMEOUT_S": "close() drain bound; leftover futures "
        "get structured shutdown rejections past it",
    # admission control (serve/admission.py; default OFF = PR 3 behavior)
    "TMR_ADMIT": "bounded admission on/off (default off)",
    "TMR_ADMIT_MAX_PENDING": "total in-system request bound",
    "TMR_ADMIT_CLASS_PENDING": "comma-separated per-priority-class "
        "in-system bounds (class beyond list reuses last)",
    "TMR_ADMIT_RATE": "token-bucket arrival-rate limit, req/s (0 = off)",
    "TMR_ADMIT_BURST": "token-bucket burst capacity",
    "TMR_ADMIT_CLASS_WEIGHTS": "comma-separated batcher pop weights per "
        "priority class (default doubling ladder)",
    # adaptive degradation (serve/degrade.py; default OFF)
    "TMR_DEGRADE": "degrade ladder: off|auto|<forced level int>",
    "TMR_DEGRADE_MAX_LEVEL": "ladder ceiling (1..3)",
    "TMR_DEGRADE_COOLDOWN": "calm health passes before de-escalation",
    "TMR_DEGRADE_MIN_SIZE": "downscale floor: images at/below never "
        "route to the half-resolution bucket",
    # observability
    "TMR_TRACE": "span tracing on/off (default off)",
    "TMR_TRACE_RING": "per-thread span ring-buffer capacity",
    "TMR_TRACE_ANNOTATE": "mirror spans as jax.profiler annotations",
    "TMR_GATE_DEBUG": "print gate refusals to stderr as they happen, and "
    "which self-checks ran or were answered from disk",
    "TMR_FLIGHT": "performance flight recorder on/off (default off): "
        "per-program device-time/MFU attribution + request/shard ring",
    "TMR_FLIGHT_RING": "flight-recorder ring capacity (records)",
    "TMR_HEALTH_INTERVAL_S": "health-heartbeat JSONL write interval "
        "seconds",
    "TMR_FLEET_OBS": "fleet observability plane on/off (default off): "
        "cross-process trace propagation, beat-borne metrics rollup, "
        "stitched cluster timeline, fleet HealthWatch",
    "TMR_FLEET_OBS_BEAT_BYTES": "per-beat observability attachment "
        "byte cap (spans drop first, an oversized metrics delta rolls "
        "back and the beat counts as truncated)",
    "TMR_FLEET_OBS_SPANS": "max completed spans shipped per beat",
    # elastic map phase (parallel/elastic.py coordinator/worker leases)
    "TMR_ELASTIC_TTL_S": "lease heartbeat budget seconds: a lease not "
        "beaten for this long is revoked and its shard reassigned",
    "TMR_ELASTIC_HB_S": "worker heartbeat cadence seconds (default "
        "TTL/4 so one dropped beat never revokes)",
    "TMR_ELASTIC_CHECK_S": "coordinator liveness-check interval seconds",
    "TMR_ELASTIC_STRAGGLER_FACTOR": "straggler bound as a multiple of "
        "the rolling median shard wall time (0 disables speculative "
        "duplicate leases)",
    "TMR_ELASTIC_STRAGGLER_MIN_S": "straggler bound floor seconds",
    "TMR_ELASTIC_MAX_REASSIGNS": "per-shard reassignment bound before "
        "the shard is quarantined outright",
    "TMR_ELASTIC_POISON_FAILURES": "distinct failed shards before a "
        "worker is drained and its shards redistributed",
    "TMR_ELASTIC_CONNECT_TIMEOUT_S": "connect timeout for every "
        "lease-protocol dial (coordinator/front-door/worker data "
        "plane); a black-holed address fails fast instead of hanging "
        "a worker in hello",
    # elastic serve fleet (serve/fleet.py; lease liveness rides the
    # TMR_ELASTIC_* family above)
    "TMR_FLEET_SATURATION_PENDING": "fleet backlog depth (open "
        "requests + worker-reported queue) that counts as queue "
        "saturation",
    "TMR_FLEET_RECRUIT_PASSES": "consecutive saturated control passes "
        "before a recruitment round fires",
    "TMR_FLEET_RECRUIT_GRACE": "control passes a fresh recruit gets "
        "to absorb load before saturation can recruit (or degrade) "
        "again",
    "TMR_FLEET_MAX_WORKERS": "recruitment ceiling: saturation past it "
        "reaches the degrade ladder instead of the spawner",
    "TMR_FLEET_MAX_RESUBMITS": "per-request resubmission bound after "
        "worker loss; past it the future fails with structured cause "
        "worker_lost",
    "TMR_FLEET_CHECK_S": "fleet front-door control-pass interval "
        "(liveness, deadlines, recruitment election)",
    # fault injection (tests/chaos probe)
    "TMR_FAULTS": "deterministic fault-injection schedule",
    "TMR_FAULTS_SEED": "fault-schedule RNG seed",
    # stream sessions (serve/streams.py)
    "TMR_STREAM_REUSE": "stream sessions: temporal feature reuse "
        "election (0 = off, the default: every frame pays the full "
        "frame-independent path)",
    "TMR_STREAM_DELTA": "stream sessions: block-mean delta threshold — "
        "a frame STRICTLY above it vs the session anchor is 'changed' "
        "(full path, new anchor); at or below reuses the anchor's "
        "features",
    "TMR_STREAM_IDLE_S": "stream sessions: idle bound — sessions "
        "inactive past it evict lazily on the next submit",
    "TMR_STREAM_CACHE_MB": "stream sessions: byte bound on the "
        "per-stream anchor-feature cache",
    # disaggregated feature tier (serve/feature_tier.py)
    "TMR_FEATURE_TIER_WINDOW": "feature-tier client: bounded in-flight "
        "extract window per engine — past it a fetch fails fast to the "
        "counted local fallback instead of queueing",
    "TMR_FEATURE_TIER_TIMEOUT_S": "feature-tier client: per-extract "
        "round-trip timeout before the counted local fallback",
    # continuous in-production autotune (autotune_live.py)
    "TMR_LIVE_TUNE": "continuous autotune master switch (0 = off, the "
        "default: no sampling, no bank writes, serving stays "
        "bitwise-identical — attach_live_tuner refuses)",
    "TMR_LIVE_TUNE_SAMPLE": "continuous autotune: sampled fraction of "
        "served batches shadow-measured (default 0.002; each sample "
        "runs incumbent + candidate, keeping shadow work well under "
        "1% of steady-state device seconds)",
    "TMR_LIVE_TUNE_BUDGET": "continuous autotune: device-seconds token "
        "budget for shadow execution — once spent, sampling stops "
        "(counted) until the next election resets the ledger",
    "TMR_LIVE_TUNE_WINS": "continuous autotune: consecutive decisive "
        "(>10%) wins a candidate needs before promotion",
    "TMR_LIVE_TUNE_BANK": "continuous autotune: winner-bank file path "
        "override (default <repo>/.tmr_cache/winner_bank.json)",
    # bench.py driver knobs (consumed outside tmr_tpu/ but part of the
    # same surface; the parity test scans bench.py + scripts/ for these)
    "TMR_AUTOTUNE": "bench.py: run the autotune sweep (0 skips)",
    "TMR_BENCH_AUDIT": "bench.py: program-tier audit of the elected "
        "configuration (0 skips)",
    "TMR_AUTOTUNE_EXPORT": "bench.py: write elected winners as K=V lines",
    "TMR_BENCH_CHAIN": "bench.py: chained-iteration count override",
    "TMR_BENCH_CKPT": "bench.py: trained-checkpoint path to measure",
    "TMR_BENCH_PROFILE": "bench.py: capture an xprof trace directory",
    "TMR_BENCH_SELFTEST_FAIL": "bench.py self-test: force a failed probe",
    "TMR_BENCH_SELFTEST_PRELIM": "bench.py self-test: force prelim emit",
    "TMR_BENCH_SIZE": "bench.py: image-size override",
    "TMR_BENCH_TINY": "bench.py: tiny CPU-geometry smoke mode",
    "TMR_BENCH_PROXY": "bench.py: CPU-proxy round — measure the local "
        "(reduced) geometry honestly under cpu_proxy, carry the "
        "committed TPU headline into value (carried: true)",
    "TMR_BENCH_TREND": "bench.py: embed the bench_trend/v1 history "
        "record (1 enables)",
}

"""End-to-end inference: one jitted program per (image-size, template) bucket.

Covers the reference's eval/demo inference paths:
- trainer.py each_step test branch (:143-150): forward -> Get_pred_boxes ->
  [refine] -> NMS;
- each_step_multi_exemplars (:75-121): per-exemplar forward + decode, concat,
  one NMS over the union;
- demo.py Inference.infer (:102-132).

The whole chain — encoder, template match, heads, peak decode, NMS — is ONE
XLA program (the fused-inference north star of BASELINE.json). Dynamic shape
sources (input resolution 1024/1536, template size) become a small set of
host-selected static buckets, each compiled once and cached.
"""

from __future__ import annotations

import functools

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flax.linen as nn

from tmr_tpu.models import build_model
from tmr_tpu.models.matching_net import select_capacity_bucket
from tmr_tpu.obs import span, track_compile, track_devtime
from tmr_tpu.ops.postprocess import (
    batched_nms,
    compact_detections,
    decode_detections,
    device_tail_ok,
)

#: legal TMR_DECODE_TAIL values (config registry imports this)
DECODE_TAIL_MODES = ("host", "device")


def decode_tail_mode() -> str:
    """Resolve TMR_DECODE_TAIL at trace time. "device" is admitted only
    through the ops/postprocess.device_tail_ok self-check — a refusal
    records its gate_probe/v1 cause and runs the host path, never a
    silent reorder."""
    import os

    mode = os.environ.get("TMR_DECODE_TAIL", "host")
    if mode not in DECODE_TAIL_MODES:
        raise ValueError(
            f"TMR_DECODE_TAIL={mode!r}: expected "
            + "|".join(DECODE_TAIL_MODES)
        )
    if mode == "device" and not device_tail_ok():
        import warnings

        from tmr_tpu.diagnostics import FormulationFallbackWarning

        warnings.warn(FormulationFallbackWarning(
            "TMR_DECODE_TAIL",
            "TMR_DECODE_TAIL=device: compaction self-check refused; "
            "running the host decode tail"
        ))
        return "host"
    return mode


class _PassthroughBackbone(nn.Module):
    """Stand-in backbone for head-only programs fed precomputed features."""

    @nn.compact
    def __call__(self, x):
        return x


class Predictor:
    """Bucketed-jit inference wrapper around MatchingNet.

    With ``refiner`` set (and cfg.refine_box), the pipeline becomes
    forward -> decode -> SAM box refinement -> NMS, the reference test-step
    order (trainer.py:143-150) — still one fused XLA program. The refiner
    consumes the model's own pre-upsample backbone features instead of the
    reference's second ViT-H pass (trainer.py:146-147).
    """

    def __init__(self, cfg, params=None, model=None, refiner=None,
                 refiner_params=None):
        self.cfg = cfg
        self.model = model if model is not None else build_model(cfg)
        self.params = params
        self.refiner = refiner
        self.refiner_params = refiner_params
        self._compiled: Dict[tuple, callable] = {}
        #: (params identity, QuantizedParams|None) — the resolved int8
        #: storage state for the CURRENT param tree (TMR_QUANT_STORAGE)
        self._storage_cache: Optional[tuple] = None

    def invalidate_compiled(self, kinds=None) -> int:
        """Drop compiled programs so the next call re-traces under the
        current env knobs — the live-autotune hot-swap hook
        (autotune_live.apply_winner): a promoted formulation takes
        effect without a restart, paying exactly the re-traces its knob
        scope requires.

        ``kinds`` is None for everything (formulation knobs every
        program embeds — attention impls, quant numerics; the int8
        storage cache drops too so TMR_QUANT_STORAGE re-resolves), or an
        iterable of program kinds ("single", "multi", "multi_batched",
        "backbone", "heads", "gallery", "gallery_heads") matching the
        ``_compiled`` key convention: keys lead with their kind string
        except the single-image program, whose key leads with the int
        capacity. Returns the number of programs dropped."""
        if kinds is None:
            n = len(self._compiled)
            self._compiled.clear()
            self._storage_cache = None
            return n
        wanted = set(kinds)
        drop = [
            key for key in self._compiled
            if (key[0] if isinstance(key[0], str) else "single") in wanted
        ]
        for key in drop:
            del self._compiled[key]
        return len(drop)

    # ------------------------------------------------------- int8 storage
    def _storage_state(self):
        """The offline-quantized param tree for TMR_QUANT_STORAGE=int8,
        or None (knob off / params unset / admission refused — refusals
        record gate_probe/v1 causes, see quant.stored_params_for).

        Materialized once per (process, checkpoint digest) and cached
        per param-tree identity here, so a second Predictor over the
        same weights assembles from the digest cache instead of
        re-quantizing. The compiled programs then RECEIVE the int8
        arrays (HBM weight bytes for those leaves drop 4x) and every
        program key carries the digest — a checkpoint swap can never
        reuse a program compiled against other scales."""
        from tmr_tpu.ops import quant as _q

        if self.params is None or _q.quant_storage_mode() != "int8":
            return None
        if getattr(self.model, "quant_storage", None) is None:
            # non-MatchingNet models have no stored-tail formulation
            return None
        cached = self._storage_cache
        if cached is not None and cached[0] is self.params:
            return cached[1]
        hw = self.feature_hw(int(self.cfg.image_size))
        c_cat = (self.cfg.emb_dim * 2 if self.cfg.fusion
                 else self.cfg.emb_dim)
        st = _q.stored_params_for(
            self.params, hw, hw, c_cat, c_cat,
            self.cfg.decoder_num_layer, self.cfg.decoder_kernel_size,
            dtype_name=self.cfg.compute_dtype,
            box_reg=self.cfg.box_reg,
        )
        self._storage_cache = (self.params, st)
        return st

    def exec_params(self):
        """The param tree the compiled programs consume: the stored int8
        tree under an admitted TMR_QUANT_STORAGE=int8, else
        ``self.params`` unchanged. The serving layer stages THIS tree
        (serve/engine.py), so serve-side weight traffic drops with it
        (4x for the quantized leaves)."""
        st = self._storage_state()
        return st.tree if st is not None else self.params

    def quant_stamp(self) -> Optional[dict]:
        """Provenance for stats()/health()/serve_report: which quant
        mode + storage the programs run, or None when fully exact."""
        from tmr_tpu.ops.quant import quant_mode

        st = self._storage_state()
        if st is not None:
            return st.stamp()
        if quant_mode() == "int8":
            return {"mode": "int8", "storage": "off"}
        return None

    def feature_stamp(self) -> tuple:
        """Cache-key provenance for extracted backbone features:
        ``(param-tree digest, backbone formulation)``. Every serving
        feature-cache key carries this tuple alongside the image digest,
        so a checkpoint swap or a storage-knob flip can never serve
        features extracted under OTHER weights (the stale-feature bug
        class the image-digest-only key allowed). The stored-int8 tree
        contributes its content digest; an f32 tree contributes its
        in-process identity — a fresh tree is a fresh identity, and the
        caches these keys feed are in-process."""
        st = self._storage_state()
        params_digest = (st.digest if st is not None
                         else f"id{id(self.params)}")
        return (params_digest, str(self.cfg.backbone))

    def _storage_model(self, model, st):
        """Clone ``model`` for a stored-tree program when storage is
        active (the flag routes MatchingNet onto the fused stored
        tail)."""
        return model.clone(quant_storage=True) if st is not None else model

    @staticmethod
    def _variables(params, scales):
        v = {"params": params}
        if scales is not None:
            v["quant_scales"] = scales
        return v

    def _storage_entry(self, run, st):
        """Caller-proofing for storage-compiled programs: the direct
        consumers of ``_compiled`` entries (bench.py, bench_extra,
        profile_breakdown, …) historically pass ``predictor.params``;
        under TMR_QUANT_STORAGE=int8 the program needs the stored int8
        tree instead. This wrapper swaps the tree when the caller passed
        EXACTLY ``self.params`` (identity — device-placed copies pass
        through untouched); any other f32 tree still fails the trace
        loudly via the int8-dtype check in fused_heads._maybe_quant,
        never silently dequantizing unquantized weights."""
        if st is None:
            return run

        def swapped(params, *args, **kw):
            if params is self.params:
                params = st.tree
            return run(params, *args, **kw)

        swapped.__wrapped__ = run
        return swapped

    def init_params(self, seed: int = 0, image_size: Optional[int] = None):
        s = image_size or self.cfg.image_size
        image = jnp.zeros((1, s, s, 3), jnp.float32)
        exemplars = jnp.array([[[0.4, 0.4, 0.6, 0.6]]], jnp.float32)
        # jit the init: eager init dispatches thousands of tiny ops
        self.params = jax.jit(self.model.init)(
            jax.random.key(seed), image, exemplars
        )["params"]
        return self.params

    def feature_hw(self, image_size: int) -> int:
        bb = self.model.backbone
        stride = getattr(bb, "feature_stride", None) or getattr(
            bb, "patch_size", 16
        )
        base = image_size // stride
        return base * 2 if self.cfg.feature_upsample else base

    def _decode(self, out: dict, exemplars: jnp.ndarray) -> dict:
        """Peak-pick + decode model outputs into fixed detection slots
        (shared by the single- and multi-exemplar programs)."""
        cfg = self.cfg
        return decode_detections(
            out["objectness"],
            out["regressions"],
            exemplars,
            cls_threshold=cfg.NMS_cls_threshold,
            max_detections=cfg.max_detections,
            box_reg=cfg.box_reg,
            scale_imgsize=cfg.regression_scaling_imgsize,
            scale_wh_only=cfg.regression_scaling_WH_only,
        )

    def _refine_nms(self, dets: dict, feature, image_hw, refiner_params,
                    refine: bool) -> dict:
        """[refine ->] NMS tail (reference test-step order trainer.py:143-150,
        shared by the single- and multi-exemplar programs). Under
        TMR_DECODE_TAIL=device the survivors are additionally compacted to
        the leading slots on device with a ``count`` vector
        (ops/postprocess.compact_detections) — same fixed output shape,
        host postprocess becomes a prefix slice instead of a 2000-slot
        boolean scan, per-image results bitwise-identical to the host
        path (tests/test_decode_tail.py)."""
        if refine:
            dets = self.refiner.refine(
                refiner_params, feature, dets, image_hw
            )
        dets = batched_nms(dets, self.cfg.NMS_iou_threshold)
        if decode_tail_mode() == "device":
            dets = compact_detections(dets)
        return dets

    def _single_pipeline(self, model, refine: bool, scales=None):
        """The ONE traced body of the fused single-exemplar program:
        forward -> decode -> [refine] -> NMS. Both the plain jit
        (:meth:`_get_fn`) and the mesh-sharded variants
        (:meth:`_get_sharded_fn`) close over this exact function — the
        dp bitwise-parity contract depends on the two programs tracing
        the identical op sequence, so there must never be a second
        copy to drift. ``scales`` (storage mode) is the offline
        quant_scales collection, closed over as trace-time constants —
        tiny, and the program key carries the tree digest. Returns
        ``(dets, model_out)`` (the loss path consumes ``model_out``;
        other callers drop it)."""

        def body(params, refiner_params, image, exemplars):
            out = model.apply(self._variables(params, scales), image,
                              exemplars)
            dets = self._decode(out, exemplars[:, 0, :])
            dets = self._refine_nms(
                dets, out["backbone_feature"],
                (image.shape[1], image.shape[2]), refiner_params, refine,
            )
            return dets, out

        return body

    def _multi_batched_pipeline(self, model, heads, k_bucket: int,
                                refine: bool, scales=None):
        """The ONE traced body of the batched union-NMS program (see
        :meth:`_single_pipeline` for why it is shared between the plain
        and mesh-sharded builders)."""

        def body(params, refiner_params, image, exemplars, k_real):
            b = image.shape[0]
            feat = model.backbone.apply(
                {"params": params["backbone"]}, image
            )
            if isinstance(feat, (list, tuple)):
                if len(feat) != 1:
                    raise NotImplementedError(
                        "fused multi-exemplar inference supports single-"
                        "level backbones only (every shipped backbone is)"
                    )
                feat = feat[0]
            head_params = {n: v for n, v in params.items()
                           if n != "backbone"}
            out = heads.apply(
                self._variables(head_params, scales),
                jnp.repeat(feat, k_bucket, axis=0),  # image-major (B*k,)
                exemplars.reshape(b * k_bucket, 1, 4),
            )
            dets = self._decode(out, exemplars.reshape(b * k_bucket, 4))
            row_ok = jnp.arange(k_bucket)[None, :] < k_real[:, None]
            dets["valid"] = dets["valid"] & row_ok.reshape(-1)[:, None]
            merged = {
                name: dets[name].reshape((b, -1) + dets[name].shape[2:])
                for name in ("boxes", "scores", "refs", "valid")
            }
            return self._refine_nms(
                merged, feat, (image.shape[1], image.shape[2]),
                refiner_params, refine,
            )

        return body

    def _get_fn(self, capacity: int, loss_fn=None,
                chain_feedback: bool = False, donate: bool = False):
        """Compiled forward -> decode -> [refine] -> NMS program for one
        template-capacity bucket.

        ``donate=True`` donates the staged image buffer to the program
        (``donate_argnums``): the serving layer's H2D staging buffers are
        single-use, so XLA may alias them for scratch/output instead of
        holding both live — only meaningful on backends that implement
        donation (TPU/GPU; XLA:CPU ignores it with a warning, so the serve
        engine requests it only there).

        With ``loss_fn(model_out, exemplars, *extra) -> losses`` the program
        additionally returns losses computed from the SAME forward — the
        trainer's eval step (the reference's each_step computes loss and
        Get_pred_boxes from one forward, trainer.py:123-153) — and the
        returned callable takes the extra loss inputs after ``exemplars``.

        ``chain_feedback=True`` is the benchmark hook: the callable takes a
        trailing scalar that is added to the image INSIDE the program and
        returns ``(dets, scalar)``, so chained timing loops execute
        back-to-back on device while measuring this exact production
        program (bench.py / scripts/bench_extra.py).

        There is exactly one copy of this pipeline; every consumer
        (inference, trainer eval, the benchmarks) compiles through it.
        """
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        # int() the capacity: a numpy-int bucket (e.g. derived from array
        # geometry by a caller) must land on the same compiled entry as the
        # equal Python int — tuple keys compare equal but a second jit
        # wrapper per int flavor would silently recompile
        capacity = int(capacity)
        # storage mode forks the key on the checkpoint digest: the
        # program closes over that tree's scales, so a param swap (new
        # digest) must compile a new entry, never reuse stale scales
        st = self._storage_state()
        key = (capacity, refine, loss_fn, chain_feedback, donate) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        jit = (
            functools.partial(jax.jit, donate_argnums=(2,)) if donate
            else jax.jit
        )

        body = self._single_pipeline(
            model, refine, scales=st.scales if st is not None else None
        )

        @jit
        def run_single(params, refiner_params, image, exemplars, *extra):
            if chain_feedback:
                image = image + extra[-1]
                extra = extra[:-1]
            dets, out = body(params, refiner_params, image, exemplars)
            fb = jnp.sum(dets["scores"]) * 0.0
            if loss_fn is not None:
                dets = (loss_fn(out, exemplars, *extra), dets)
            if chain_feedback:
                return dets, fb
            return dets

        # compile-event accounting (obs/compile.py): the first call of
        # every fresh cache entry records (key, wall, cold|key-change) —
        # recompile storms become visible events instead of latency
        # cliffs. The devtime wrapper outside it (obs/devtime.py) is the
        # flight recorder's per-execution device-time attribution seam;
        # with TMR_FLIGHT=0 (default) it is one bool check.
        run = self._storage_entry(track_devtime(
            track_compile(run_single, "single", key,
                          bucket={"capacity": capacity}, batch_arg=2),
            "single", key, bucket={"capacity": capacity},
        ), st)
        self._compiled[key] = run
        return run

    def pick_capacity(self, exemplars: np.ndarray, image_size: int) -> int:
        """Host-side template bucket for a batch: the largest per-exemplar
        need. Always a Python int (numpy ints from array-derived geometry
        must not fork the ``_compiled`` key space)."""
        hw = self.feature_hw(int(image_size))
        need = 1
        for ex in np.asarray(exemplars).reshape(-1, 4):
            need = max(
                need,
                select_capacity_bucket(ex, hw, hw, self.cfg.template_buckets),
            )
        return int(need)

    def bucket_key(self, image_size: int, exemplars,
                   multi: bool = False, k_real: Optional[int] = None
                   ) -> Tuple[str, int, int, int]:
        """The static-program bucket a request compiles into, as one
        hashable tuple — the serving layer's coalescing key.

        Returns ``("single", image_size, capacity, K)`` for the
        ``__call__`` path (K = exemplar slots carried per image; the
        matcher consumes slot 0) or ``("multi", image_size, capacity,
        k_bucket)`` for the union-NMS multi-exemplar path. Requests with
        equal keys batch into one jitted program; every element is a
        Python int (see :meth:`pick_capacity`)."""
        image_size = int(image_size)
        exemplars = np.asarray(exemplars, np.float32).reshape(-1, 4)
        if multi:
            k = int(k_real) if k_real is not None else len(exemplars)
            cap = self.pick_capacity(exemplars[:k], image_size)
            k_bucket = int(next((b for b in self.K_BUCKETS if b >= k), k))
            return ("multi", image_size, cap, k_bucket)
        # __call__ sizes the template bucket from every carried slot
        # (pick_capacity over the full (K, 4)) — mirror it exactly so a
        # batched-serve request compiles into the same-capacity program as
        # the sequential call it must match bitwise
        return ("single", image_size, self.pick_capacity(exemplars,
                                                         image_size),
                len(exemplars))

    def __call__(self, image, exemplars) -> dict:
        """image (B, S, S, 3) float32 normalized; exemplars (B, K, 4).
        Returns dict boxes/scores/refs/valid as fixed-shape device arrays."""
        if self.params is None:
            raise RuntimeError("call init_params() or load params first")
        with span("predict.stage", scope="batch", program="run_single",
                  rows=int(image.shape[0])) as sp:
            cap = self.pick_capacity(exemplars, int(image.shape[1]))
            sp.set_attr(capacity=cap)
            fn = self._get_fn(cap)
            args = (self.exec_params(), self.refiner_params,
                    jnp.asarray(image), jnp.asarray(exemplars))
        return fn(*args)

    #: static exemplar-count buckets for the multi-exemplar program: the
    #: compiled fn is keyed by bucket, real counts pad up and padded rows'
    #: detections are masked out — variable per-image exemplar counts
    #: (FSCD-LVIS) don't trigger a full recompile each. The paper's
    #: contract is k <= 3; the 16/32 power-of-two rungs exist for the
    #: gallery tier (serve/gallery.py), where a standing pattern set's
    #: union of boxes rides the same ladder — without them every distinct
    #: k past 8 fell through to its own compiled program (a recompile per
    #: ragged count, pinned against by tests/test_gallery.py).
    K_BUCKETS = (1, 2, 3, 4, 6, 8, 16, 32)

    #: static entry-count buckets for the fused gallery programs: N bank
    #: entries pad up to a rung and mask with ``n_real`` exactly like the
    #: k ladder — ragged bank sizes inside one rung never recompile. The
    #: serving-side ladder cap is autotune-elected like the batch bound
    #: (utils/autotune.measured_gallery_nmax).
    N_BUCKETS = (1, 2, 4, 8, 16, 32)

    def _get_multi_fn(self, capacity: int, k_bucket: int, loss_fn=None):
        """One fused program for K-exemplar inference: encoder ONCE, then the
        matcher/decode pipeline batched over the K exemplars, union NMS.

        The reference runs a full forward per exemplar and one union NMS at
        the end (trainer.py:75-121: per-exemplar Get_pred_boxes with NO
        per-exemplar NMS, concat, [refine], NMS — demo.py:111-132 likewise),
        recomputing the frozen encoder K times. Here the encoder output is
        broadcast to a K-batch for the heads — identical numerics (the
        encoder is deterministic), ~K x fewer encoder FLOPs, one dispatch.

        ``loss_fn(out_k, exemplar_k, *extra) -> losses`` computes one
        exemplar's losses from its B=1 slice of the heads output; the
        program vmaps it over the K axis, masks padded rows, and returns the
        SUM over real exemplars — the reference's multi-exemplar loss
        semantics (trainer.py:102-104,121 sums per-exemplar losses).
        """
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        # int-normalized key: a numpy-int capacity/k_bucket (callers deriving
        # them from array shapes) must hit the same compiled entry as the
        # equal Python int instead of silently recompiling
        capacity, k_bucket = int(capacity), int(k_bucket)
        st = self._storage_state()
        key = ("multi", capacity, k_bucket, refine, loss_fn) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        heads = model.clone(backbone=_PassthroughBackbone())
        scales = st.scales if st is not None else None

        @jax.jit
        def run_multi(params, refiner_params, image, exemplars, k_real,
                      *extra):
            # image (1, S, S, 3); exemplars (k_bucket, 4); k_real () int32
            feat = model.backbone.apply(
                {"params": params["backbone"]}, image
            )
            if isinstance(feat, (list, tuple)):
                if len(feat) != 1:
                    raise NotImplementedError(
                        "fused multi-exemplar inference supports single-"
                        "level backbones only (every shipped backbone is)"
                    )
                feat = feat[0]
            head_params = {n: v for n, v in params.items() if n != "backbone"}
            out = heads.apply(
                self._variables(head_params, scales),
                jnp.repeat(feat, k_bucket, axis=0),
                exemplars[:, None, :],
            )
            dets = self._decode(out, exemplars)
            # mask padded exemplar rows, then concat the K per-exemplar slot
            # sets into one image's union
            row_ok = jnp.arange(k_bucket) < k_real
            dets["valid"] = dets["valid"] & row_ok[:, None]
            merged = {
                name: dets[name].reshape((1, -1) + dets[name].shape[2:])
                for name in ("boxes", "scores", "refs", "valid")
            }
            final = self._refine_nms(
                merged, feat, (image.shape[1], image.shape[2]),
                refiner_params, refine,
            )
            if loss_fn is None:
                return final

            def one_exemplar_losses(obj_k, reg_k, ex_k):
                out_k = {
                    "objectness": [o[None] for o in obj_k],
                    # None levels = box regression ablated (matching_net)
                    "regressions": [
                        r[None] if r is not None else None for r in reg_k
                    ],
                }
                return loss_fn(out_k, ex_k[None, None, :], *extra)

            per_k = jax.vmap(one_exemplar_losses)(
                [o for o in out["objectness"]],
                [r for r in out["regressions"]],
                exemplars,
            )
            losses = jax.tree.map(
                lambda v: jnp.where(row_ok, v, 0.0).sum(), per_k
            )
            return losses, final

        run = self._storage_entry(track_devtime(
            track_compile(run_multi, "multi", key,
                          bucket={"capacity": capacity,
                                  "k_bucket": k_bucket}, batch_arg=2),
            "multi", key, bucket={"capacity": capacity,
                                  "k_bucket": k_bucket},
        ), st)
        self._compiled[key] = run
        return run

    def predict_multi_exemplar(self, image, exemplars, loss_fn=None,
                               loss_args=(), k_real=None):
        """Reference multi-exemplar eval (trainer.py:75-121): per-exemplar
        decode, concatenated, single NMS over the union. image (1, S, S, 3);
        exemplars (K, 4). With ``loss_fn`` (see _get_multi_fn) returns
        (losses summed over exemplars, dets); else just dets.

        ``k_real`` marks how many leading exemplar rows are real when the
        caller hands over a pre-padded array (the serving layer does); rows
        past it are ignored. Any integer flavor is accepted — the bucket
        key is int-normalized, so a numpy-int ``k_real`` can never fork
        ``_compiled`` into a recompile (pinned by tests/test_serve.py)."""
        if self.params is None:
            raise RuntimeError("call init_params() or load params first")
        exemplars = np.asarray(exemplars, np.float32).reshape(-1, 4)
        k = int(k_real) if k_real is not None else len(exemplars)
        if not 1 <= k <= len(exemplars):
            raise ValueError(
                f"k_real={k} out of range for {len(exemplars)} exemplar rows"
            )
        with span("predict.stage", scope="batch", program="run_multi",
                  rows=int(image.shape[0])) as sp:
            exemplars = exemplars[:k]
            k_bucket = int(next((b for b in self.K_BUCKETS if b >= k), k))
            pad = np.tile(exemplars[-1:], (k_bucket - k, 1))  # masked below
            cap = self.pick_capacity(exemplars, int(image.shape[1]))
            sp.set_attr(capacity=cap)
            fn = self._get_multi_fn(cap, k_bucket, loss_fn=loss_fn)
            args = (
                self.exec_params(),
                self.refiner_params,
                jnp.asarray(image),
                jnp.asarray(np.concatenate([exemplars, pad], axis=0)),
                jnp.asarray(k, jnp.int32),
            )
        return fn(*args, *loss_args)


    # ---------------------------------------------------------------- serve
    # Batched entry points for the throughput serving layer (tmr_tpu/serve):
    # the batcher coalesces single-image requests into these fixed-(B, K)
    # programs, pads ragged tails, and unpads per request. They reuse the
    # exact _decode/_refine_nms pipeline, so serve results stay the
    # production numerics.

    def _get_multi_batched_fn(self, capacity: int, k_bucket: int,
                              donate: bool = False):
        """The B>1 generalization of :meth:`_get_multi_fn`: encoder once per
        image, heads batched over B*k_bucket exemplar rows, one union NMS
        per image. image (B, S, S, 3); exemplars (B, k_bucket, 4); k_real
        (B,) int32 — each image masks its own padded rows, so a batch can
        mix real exemplar counts inside one k bucket. The B=1 slice traces
        the same op sequence as ``_get_multi_fn``."""
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity, k_bucket = int(capacity), int(k_bucket)
        st = self._storage_state()
        key = ("multi_batched", capacity, k_bucket, refine, donate) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        heads = model.clone(backbone=_PassthroughBackbone())
        jit = (
            functools.partial(jax.jit, donate_argnums=(2,)) if donate
            else jax.jit
        )
        body = self._multi_batched_pipeline(
            model, heads, k_bucket, refine,
            scales=st.scales if st is not None else None,
        )

        @jit
        def run_multi_batched(params, refiner_params, image, exemplars,
                              k_real):
            return body(params, refiner_params, image, exemplars, k_real)

        run = self._storage_entry(track_devtime(
            track_compile(run_multi_batched, "multi_batched", key,
                          bucket={"capacity": capacity,
                                  "k_bucket": k_bucket}, batch_arg=2),
            "multi_batched", key, bucket={"capacity": capacity,
                                          "k_bucket": k_bucket},
        ), st)
        self._compiled[key] = run
        return run

    def predict_multi_batch(self, images, exemplars, k_real,
                            donate: bool = False) -> dict:
        """Batched union-NMS inference: images (B, S, S, 3), exemplars
        (B, k_bucket, 4) pre-padded to one k bucket, k_real (B,) real row
        counts. Returns fixed-slot dets with leading dim B."""
        if self.params is None:
            raise RuntimeError("call init_params() or load params first")
        with span("predict.stage", scope="batch",
                  program="run_multi_batched",
                  rows=int(images.shape[0])) as sp:
            exemplars = jnp.asarray(exemplars)
            cap = self.pick_capacity(exemplars, int(images.shape[1]))
            sp.set_attr(capacity=cap)
            fn = self._get_multi_batched_fn(
                cap, int(exemplars.shape[1]), donate=donate,
            )
            args = (
                self.exec_params(), self.refiner_params,
                jnp.asarray(images), exemplars,
                jnp.asarray(k_real, jnp.int32),
            )
        return fn(*args)

    def _get_backbone_fn(self):
        """Encoder-only program: image (B, S, S, 3) -> pre-upsample backbone
        features (B, h, w, C) — the tensor the serving layer's image-feature
        cache stores, and exactly what :meth:`_get_heads_fn` consumes."""
        key = ("backbone",)
        if key in self._compiled:
            return self._compiled[key]

        @jax.jit
        def run_backbone(params, image):
            f = self.model.backbone.apply({"params": params["backbone"]},
                                          image)
            if isinstance(f, (list, tuple)):
                if len(f) != 1:
                    raise NotImplementedError(
                        "feature-cached serving supports single-level "
                        "backbones only (every shipped backbone is)"
                    )
                f = f[0]
            return f

        run = track_devtime(
            track_compile(run_backbone, "backbone", key, batch_arg=1),
            "backbone", key)
        self._compiled[key] = run
        return run

    def _get_heads_fn(self, capacity: int, image_size: int):
        """Heads-on-precomputed-features program for one capacity bucket:
        features (B, h, w, C) from :meth:`_get_backbone_fn` -> the same
        upsample/proj/match/decode/[refine]/NMS tail as ``_get_fn``.

        Feature-cache hits skip the encoder (the dominant cost) through
        this program. Because the tail compiles as its OWN XLA program
        here, its outputs can differ from the fused single program at the
        last-ULP level (different fusion decisions); the serving layer
        documents this and keeps the bitwise-exactness contract on the
        fused path only."""
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity, image_size = int(capacity), int(image_size)
        st = self._storage_state()
        key = ("heads", capacity, image_size, refine) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        scales = st.scales if st is not None else None

        @jax.jit
        def run_heads(params, refiner_params, features, exemplars):
            out = model.apply(
                self._variables(params, scales),
                jnp.zeros((features.shape[0], 1, 1, 3), jnp.float32),
                exemplars, features=features,
            )
            dets = self._decode(out, exemplars[:, 0, :])
            return self._refine_nms(
                dets, out["backbone_feature"], (image_size, image_size),
                refiner_params, refine,
            )

        run = self._storage_entry(track_devtime(
            track_compile(run_heads, "heads", key,
                          bucket={"capacity": capacity,
                                  "image_size": image_size}, batch_arg=2),
            "heads", key, bucket={"capacity": capacity,
                                  "image_size": image_size},
        ), st)
        self._compiled[key] = run
        return run

    # -------------------------------------------------------------- gallery
    # Template-bank programs for the gallery tier (tmr_tpu/serve/gallery):
    # a STANDING pattern set of N registered exemplar sets matched against
    # a stream frame with ONE backbone pass, then the matcher/heads/decode
    # tail batched over N*k rows and a union NMS PER ENTRY — the
    # multi-pattern generalization of _get_multi_fn. Entry i's slice
    # traces the same op sequence as predict_multi_exemplar on that
    # entry's exemplars, which is what keeps the fused gallery arm
    # bitwise-identical to the N-loop (tests/test_gallery.py pins it;
    # the same batch-invariance caveat as test_serve applies under the
    # forced-8-device CPU conftest). N pads to an N_BUCKETS rung with
    # ``n_real`` masking exactly like the k ladder.

    def _gallery_tail(self, heads, n_bucket: int, k_bucket: int,
                      refine: bool, scales=None):
        """The ONE traced tail of the gallery programs: heads over
        ``n_bucket * k_bucket`` exemplar rows against one frame's
        features, per-entry row masking, per-entry union NMS. Shared by
        the fused (:meth:`_get_gallery_fn`) and heads-split
        (:meth:`_get_gallery_heads_fn`) builders so the two arms can
        never drift — the split arm differs only in where the features
        come from (the documented heads-path ULP exception)."""

        def tail(params, refiner_params, feat, exemplars, k_real, n_real,
                 image_hw):
            # feat (1, h, w, C); exemplars (n_bucket, k_bucket, 4);
            # k_real (n_bucket,) int32; n_real () int32
            head_params = {n: v for n, v in params.items()
                           if n != "backbone"}
            rows = n_bucket * k_bucket
            out = heads.apply(
                self._variables(head_params, scales),
                jnp.repeat(feat, rows, axis=0),
                exemplars.reshape(rows, 1, 4),
            )
            dets = self._decode(out, exemplars.reshape(rows, 4))
            row_ok = jnp.arange(k_bucket)[None, :] < k_real[:, None]
            entry_ok = (jnp.arange(n_bucket) < n_real)[:, None]
            dets["valid"] = dets["valid"] & (
                (row_ok & entry_ok).reshape(-1)[:, None]
            )
            merged = {
                name: dets[name].reshape(
                    (n_bucket, -1) + dets[name].shape[2:]
                )
                for name in ("boxes", "scores", "refs", "valid")
            }
            feature = (jnp.repeat(feat, n_bucket, axis=0) if refine
                       else feat)
            return self._refine_nms(merged, feature, image_hw,
                                    refiner_params, refine)

        return tail

    def _get_gallery_fn(self, capacity: int, n_bucket: int, k_bucket: int,
                        donate: bool = False):
        """The FUSED gallery program: frame image in, backbone ONCE,
        then :meth:`_gallery_tail` over the bank — the cold-frame arm
        whose per-entry results are bitwise the N-loop of
        ``predict_multi_exemplar``. image (1, S, S, 3); exemplars
        (n_bucket, k_bucket, 4); k_real (n_bucket,); n_real () int32.
        Returns fixed-slot dets with leading dim n_bucket (entry
        order)."""
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity, n_bucket, k_bucket = (
            int(capacity), int(n_bucket), int(k_bucket)
        )
        st = self._storage_state()
        key = ("gallery", capacity, n_bucket, k_bucket, refine, donate) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        heads = model.clone(backbone=_PassthroughBackbone())
        tail = self._gallery_tail(
            heads, n_bucket, k_bucket, refine,
            scales=st.scales if st is not None else None,
        )
        jit = (
            functools.partial(jax.jit, donate_argnums=(2,)) if donate
            else jax.jit
        )

        @jit
        def run_gallery(params, refiner_params, image, exemplars, k_real,
                        n_real):
            feat = model.backbone.apply(
                {"params": params["backbone"]}, image
            )
            if isinstance(feat, (list, tuple)):
                if len(feat) != 1:
                    raise NotImplementedError(
                        "gallery inference supports single-level "
                        "backbones only (every shipped backbone is)"
                    )
                feat = feat[0]
            return tail(params, refiner_params, feat, exemplars, k_real,
                        n_real, (image.shape[1], image.shape[2]))

        bucket = {"capacity": capacity, "n_bucket": n_bucket,
                  "k_bucket": k_bucket}
        run = self._storage_entry(track_devtime(
            track_compile(run_gallery, "gallery", key, bucket=bucket,
                          batch_arg=2),
            "gallery", key, bucket=bucket,
        ), st)
        self._compiled[key] = run
        return run

    def _get_gallery_heads_fn(self, capacity: int, n_bucket: int,
                              k_bucket: int, image_size: int):
        """Gallery tail on PRECOMPUTED features (the feature-cache /
        prefilter arm): features (1, h, w, C) from
        :meth:`_get_backbone_fn`. Same tail as the fused program —
        compiled as its own XLA program, so the heads-path last-ULP
        exception applies (cold gallery traffic stays on the fused
        bitwise arm)."""
        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity, n_bucket, k_bucket, image_size = (
            int(capacity), int(n_bucket), int(k_bucket), int(image_size)
        )
        st = self._storage_state()
        key = ("gallery_heads", capacity, n_bucket, k_bucket, image_size,
               refine) + ((st.digest,) if st is not None else ())
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        heads = model.clone(backbone=_PassthroughBackbone())
        tail = self._gallery_tail(
            heads, n_bucket, k_bucket, refine,
            scales=st.scales if st is not None else None,
        )

        @jax.jit
        def run_gallery_heads(params, refiner_params, features, exemplars,
                              k_real, n_real):
            return tail(params, refiner_params, features, exemplars,
                        k_real, n_real, (image_size, image_size))

        bucket = {"capacity": capacity, "n_bucket": n_bucket,
                  "k_bucket": k_bucket, "image_size": image_size}
        run = self._storage_entry(track_devtime(
            track_compile(run_gallery_heads, "gallery_heads", key,
                          bucket=bucket, batch_arg=2),
            "gallery_heads", key, bucket=bucket,
        ), st)
        self._compiled[key] = run
        return run

    def _get_gallery_prefilter_fn(self, n_bucket: int, k_bucket: int):
        """Coarse prefilter program: channel-pooled low-res correlation
        score per bank entry (ops/xcorr.coarse_prefilter_scores) on the
        frame's backbone features — the cheap ranking stage that decides
        which entries earn the full match+decode. Parameter-free; one
        compiled entry per (n_bucket, k_bucket)."""
        from tmr_tpu.ops.xcorr import coarse_prefilter_scores

        n_bucket, k_bucket = int(n_bucket), int(k_bucket)
        key = ("gallery_prefilter", n_bucket, k_bucket)
        if key in self._compiled:
            return self._compiled[key]

        @jax.jit
        def run_gallery_prefilter(features, exemplars, k_real, n_real):
            return coarse_prefilter_scores(features, exemplars, k_real,
                                           n_real)

        bucket = {"n_bucket": n_bucket, "k_bucket": k_bucket}
        run = track_devtime(
            track_compile(run_gallery_prefilter, "gallery_prefilter", key,
                          bucket=bucket, batch_arg=0),
            "gallery_prefilter", key, bucket=bucket,
        )
        self._compiled[key] = run
        return run

    def predict_gallery(self, image, exemplars, k_real, n_real=None,
                        features=None, image_size=None) -> dict:
        """Match a bank of N exemplar sets against ONE frame: image
        (1, S, S, 3); exemplars (N, k_bucket, 4) pre-padded to one k
        rung; k_real (N,) real row counts; ``n_real`` marks how many
        leading entries are real (the rest are rung padding). With
        ``features`` ((1, h, w, C) from :meth:`_get_backbone_fn`, plus
        ``image_size``) the encoder is skipped — the feature-cache arm.
        Returns fixed-slot dets with leading dim = the padded N rung;
        rows past ``n_real`` are fully masked."""
        if self.params is None:
            raise RuntimeError("call init_params() or load params first")
        exemplars = np.asarray(exemplars, np.float32)
        if exemplars.ndim != 3 or exemplars.shape[-1] != 4:
            raise ValueError(
                f"expected (N, k_bucket, 4) exemplars, got "
                f"{exemplars.shape}"
            )
        n = int(n_real) if n_real is not None else exemplars.shape[0]
        if not 1 <= n <= exemplars.shape[0]:
            raise ValueError(
                f"n_real={n} out of range for {exemplars.shape[0]} "
                "bank entries"
            )
        k_real = np.asarray(k_real, np.int32).reshape(-1)
        if k_real.shape[0] != exemplars.shape[0]:
            raise ValueError("k_real must have one count per entry")
        k_bucket = int(exemplars.shape[1])
        if not all(1 <= int(k) <= k_bucket for k in k_real[:n]):
            raise ValueError(
                f"k_real rows must lie in [1, {k_bucket}]"
            )
        n_bucket = int(next((b for b in self.N_BUCKETS if b >= n), n))
        if exemplars.shape[0] < n_bucket:
            pad = n_bucket - exemplars.shape[0]
            exemplars = np.concatenate(
                [exemplars, np.tile(exemplars[-1:], (pad, 1, 1))], axis=0
            )
            k_real = np.concatenate(
                [k_real, np.ones((pad,), np.int32)]
            )
        else:
            exemplars = exemplars[:n_bucket]
            k_real = k_real[:n_bucket]
        if features is None:
            size = int(image.shape[1])
        else:
            if image_size is None:
                raise ValueError(
                    "features-arm predict_gallery needs image_size"
                )
            size = int(image_size)
        with span("predict.stage", scope="batch", rows=1,
                  program="run_gallery" if features is None
                  else "run_gallery_heads") as sp:
            rows = np.concatenate(
                [exemplars[i, :int(k_real[i])] for i in range(n)], axis=0
            )
            cap = self.pick_capacity(rows, size)
            sp.set_attr(capacity=cap)
            if features is None:
                fn = self._get_gallery_fn(cap, n_bucket, k_bucket)
                frame = jnp.asarray(image)
            else:
                fn = self._get_gallery_heads_fn(cap, n_bucket, k_bucket,
                                                size)
                frame = features
            args = (
                self.exec_params(), self.refiner_params, frame,
                jnp.asarray(exemplars), jnp.asarray(k_real),
                jnp.asarray(n, jnp.int32),
            )
        return fn(*args)

    # ------------------------------------------------------- sharded serve
    # Mesh-sharded program variants for the serving tier (serve/meshplan):
    # the same _decode/_refine_nms pipeline compiled against a MeshTarget.
    # Data-parallel targets with tp == 1 go through the shard_map path of
    # parallel/compat.compile_sharded — the per-shard trace IS the
    # unsharded program body at the local batch shape, which is what
    # keeps dp-sharded serve results bitwise-identical to the unsharded
    # engine. Targets with tp > 1 go through the pjit/GSPMD path: params
    # shard Megatron-style over the group's 'tp' axis
    # (parallel/sharding.serve_param_shardings) and XLA inserts the
    # collectives — allclose-level numerics with identical keep
    # decisions (reduction reorder; the heads-path precedent).
    # Every key embeds MeshTarget.key (axis sizes + concrete device ids),
    # so a mesh-shape change compiles a NEW entry instead of silently
    # colliding with a cached program bound to other devices.

    def _sharded_shardings(self, target):
        """(params, replicated) NamedShardings for one tp target."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from tmr_tpu.parallel.sharding import serve_param_shardings

        if self.params is None:
            raise RuntimeError(
                "sharded programs need loaded params (the in_shardings "
                "tree mirrors the real param tree)"
            )
        return (
            serve_param_shardings(self.params, target.mesh),
            NamedSharding(target.mesh, P()),
        )

    def _get_sharded_fn(self, capacity: int, target, donate: bool = False):
        """Sharded variant of :meth:`_get_fn` for one
        ``serve.meshplan.MeshTarget``: mode "dp" shards the image batch
        over the mesh's dp axis, mode "group" replicates the batch and
        shards the ViT feature dims over the group's tp axis. Call
        signature and outputs match :meth:`_get_fn` (no loss/chain
        hooks — this is the serving path)."""
        from jax.sharding import PartitionSpec as P

        from tmr_tpu.parallel.compat import compile_sharded

        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity = int(capacity)
        st = self._storage_state()
        key = ("single_sharded", capacity, refine, donate, target.key) + (
            (st.digest,) if st is not None else ()
        )
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        pipeline = self._single_pipeline(
            model, refine, scales=st.scales if st is not None else None
        )

        def body(params, refiner_params, image, exemplars):
            # the SHARED single-program body (bitwise contract); the
            # sharded program drops the loss path's model_out
            return pipeline(params, refiner_params, image, exemplars)[0]

        donate_argnums = (2,) if donate else ()
        if target.mode == "dp" and target.tp == 1:
            run = compile_sharded(
                body, target.mesh,
                in_specs=(P(), P(), P("dp"), P("dp")),
                out_specs=P("dp"),
                donate_argnums=donate_argnums,
            )
        else:
            pshard, repl = self._sharded_shardings(target)
            batch = (
                self._dp_sharding(target) if target.mode == "dp" else repl
            )
            run = compile_sharded(
                body, target.mesh,
                in_shardings=(pshard, repl, batch, batch),
                out_shardings=batch,
                donate_argnums=donate_argnums,
            )
        bucket = {"capacity": capacity, "mode": target.mode,
                  "devices": target.n_devices}
        run = track_devtime(
            track_compile(run, "single_sharded", key, bucket=bucket,
                          batch_arg=2),
            "single_sharded", key, bucket=bucket,
            devices=target.n_devices,
        )
        self._compiled[key] = run
        return run

    def _dp_sharding(self, target):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(target.mesh, P("dp"))

    def _get_sharded_multi_fn(self, capacity: int, k_bucket: int, target,
                              donate: bool = False):
        """Sharded variant of :meth:`_get_multi_batched_fn` (the batched
        union-NMS program) for one MeshTarget — same masking and merge
        semantics, batch sharded over dp / params over tp per the
        target's mode."""
        from jax.sharding import PartitionSpec as P

        from tmr_tpu.parallel.compat import compile_sharded

        refine = self.refiner is not None and getattr(
            self.cfg, "refine_box", False
        )
        capacity, k_bucket = int(capacity), int(k_bucket)
        st = self._storage_state()
        key = ("multi_sharded", capacity, k_bucket, refine, donate,
               target.key) + ((st.digest,) if st is not None else ())
        if key in self._compiled:
            return self._compiled[key]
        model = self._storage_model(
            self.model.clone(template_capacity=capacity), st
        )
        heads = model.clone(backbone=_PassthroughBackbone())
        # the SHARED batched union-NMS body (bitwise contract)
        body = self._multi_batched_pipeline(
            model, heads, k_bucket, refine,
            scales=st.scales if st is not None else None,
        )

        donate_argnums = (2,) if donate else ()
        if target.mode == "dp" and target.tp == 1:
            run = compile_sharded(
                body, target.mesh,
                in_specs=(P(), P(), P("dp"), P("dp"), P("dp")),
                out_specs=P("dp"),
                donate_argnums=donate_argnums,
            )
        else:
            pshard, repl = self._sharded_shardings(target)
            batch = (
                self._dp_sharding(target) if target.mode == "dp" else repl
            )
            run = compile_sharded(
                body, target.mesh,
                in_shardings=(pshard, repl, batch, batch, batch),
                out_shardings=batch,
                donate_argnums=donate_argnums,
            )
        bucket = {"capacity": capacity, "k_bucket": k_bucket,
                  "mode": target.mode, "devices": target.n_devices}
        run = track_devtime(
            track_compile(run, "multi_sharded", key, bucket=bucket,
                          batch_arg=2),
            "multi_sharded", key, bucket=bucket,
            devices=target.n_devices,
        )
        self._compiled[key] = run
        return run


def detections_to_numpy(dets: dict) -> list:
    """Fixed-slot device detections -> per-image ragged numpy dicts
    (the reference's pred_logits/pred_boxes/ref_points lists).

    Device-compacted detections (TMR_DECODE_TAIL=device: survivors in the
    leading ``count`` slots) take the prefix-slice fast path; the host
    form scans the validity mask. Both yield identical lists."""
    # predict.fetch waits for the device and copies back; predict.unpack
    # is the host's ragged split (obs/tracing.py: always-on batch spans)
    with span("predict.fetch", scope="batch") as sp:
        boxes = np.asarray(dets["boxes"])
        scores = np.asarray(dets["scores"])
        refs = np.asarray(dets["refs"])
        compacted = "count" in dets
        keep = np.asarray(dets["count" if compacted else "valid"])
        rows = int(boxes.shape[0])
        sp.set_attr(rows=rows)
    out = []
    with span("predict.unpack", scope="batch", rows=rows):
        for b in range(rows):
            if compacted:
                # .copy(): a prefix-slice VIEW would pin the whole padded
                # (B, max_detections, ...) batch alive for as long as the
                # caller keeps the per-image dict — the retention hazard
                # serve/engine.py's _finish documents; the host path's
                # boolean indexing below copies inherently
                n = int(keep[b])
                out.append({"boxes": boxes[b][:n].copy(),
                            "scores": scores[b][:n].copy(),
                            "refs": refs[b][:n].copy()})
            else:
                v = keep[b]
                out.append({"boxes": boxes[b][v], "scores": scores[b][v],
                            "refs": refs[b][v]})
    return out

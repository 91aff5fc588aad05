"""int8-weight / bf16-activation quantization for the inference tail.

The seed's relaxed-numerics playbook (TMR_XCORR_PRECISION=bf16) trades
rounding error for MXU passes only when a measured, decisive win justifies
it. This module extends that playbook one
tier further for the post-attention tail — the matcher correlation and the
decoder conv stacks + heads, the PR-6 MFU targets: weights are rounded to
the **int8 grid with a per-output-channel f32 scale** (symmetric,
round-to-nearest), activations stay bf16, accumulation stays f32.

Two storage tiers share the int8 grid:

- ``TMR_QUANT=int8`` alone is the IN-PROGRAM fake-quant formulation —
  the quantize-dequantize round trip runs next to each matmul on the
  full-precision params the program receives, pinning the int8 NUMERICS
  exactly without shrinking HBM weight traffic.
- ``TMR_QUANT_STORAGE=int8`` additionally makes the storage real: the
  decoder/head weight leaves are quantized OFFLINE once per checkpoint
  (:func:`quantize_tree`, digest-cached) and the compiled programs
  receive the int8 arrays themselves — HBM weight bytes for those leaves
  genuinely drop 4x. The default in-program formulation dequantizes each
  int8 operand adjacent to its matmul with the SAME per-tap
  per-output-channel scales the fake path computes, so stored output is
  **bitwise identical** to the admitted fake-quant path — an equality
  pin (tier "storage" of the oracle, :func:`quant_storage_ok`), not a
  tolerance. ``TMR_QUANT_KERNEL`` selects faster matmul arms (both-
  operand int8 ``dot_general``/Pallas MXU kernels) behind their own
  tolerance gates; see ops/fused_heads.py.

Election contract (the TMR_XCORR_PRECISION pattern, one tier deeper):

- ``TMR_QUANT=off`` (default) — exact path, knob inert.
- ``TMR_QUANT=int8`` — explicit request; refused by the tiered oracle gate
  with a recorded ``gate_probe/v1`` cause + FormulationFallbackWarning,
  falling back to the exact path.
- ``TMR_QUANT=auto`` — autotune-elected: exported as int8 only when the
  on-device sweep measures a decisive win AND the tiered oracle passes at
  the production geometry (utils/autotune.py pick_quant).

Tolerance tiers (``quant_ok``): tier "weights" pins the quantization
round-trip itself (per-channel int8 reconstruction error is bounded by
construction: <= scale/2 per element, i.e. ~0.4% of the channel max);
tier "output" pins the end-to-end tail output against the unquantized
oracle at the geometry about to run. Both must pass for the gate to
admit the path; each refusal records which tier failed.
"""

from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

#: legal TMR_QUANT values (autotune + config registry import this)
QUANT_MODES = ("off", "int8", "auto")

#: legal TMR_QUANT_STORAGE values (the offline-quantized param tree)
STORAGE_MODES = ("off", "int8")

#: legal TMR_QUANT_KERNEL values — which matmul formulation consumes the
#: quantized operands (ops/fused_heads.py / ops/xcorr.py read it at
#: trace time): "auto"/"dequant" = int8 operand dequantized adjacent to
#: the f32-accumulated matmul (the bitwise equality-pinned arm);
#: "int8dot" = BOTH operands int8 through dot_general/conv with
#: preferred_element_type=int32 and the per-channel dequant fused into
#: the f32 epilogue (dynamic activation quantization — tolerance-gated);
#: "pallas" = the Mosaic int8 MXU kernel (ops/pallas_int8.py), falling
#: back to int8dot then dequant where Mosaic refuses.
QUANT_KERNELS = ("auto", "dequant", "int8dot", "pallas")

#: tier tolerances (max relative error): the weight round-trip is a pure
#: rounding bound (int8 symmetric grid -> half-step of 1/127 of the
#: channel max); the output tier allows the accumulated effect through
#: one conv stack + head at bf16 activations. Measured slack over the
#: analytic bounds, not guesses — see tests/test_quant.py.
WEIGHT_TIER_REL = 1.0 / 127.0
OUTPUT_TIER_REL = 5e-2


def quant_mode() -> str:
    """Resolve TMR_QUANT at trace time (autotune exports the elected
    winner through the same env knob, the TMR_XCORR_PRECISION
    mechanism). "auto" without an autotune election means off: quantized
    numerics must never be the accidental default."""
    mode = os.environ.get("TMR_QUANT", "off")
    if mode not in QUANT_MODES:
        raise ValueError(
            f"TMR_QUANT={mode!r}: expected " + "|".join(QUANT_MODES)
        )
    return "off" if mode == "auto" else mode


def quant_storage_mode() -> str:
    """Resolve TMR_QUANT_STORAGE (off|int8). "int8" is only meaningful on
    top of an admitted TMR_QUANT=int8 path — the admission logic lives in
    :func:`stored_params_for` (Predictor-side) so a refusal carries a
    recorded cause instead of silently running f32."""
    mode = os.environ.get("TMR_QUANT_STORAGE", "off")
    if mode not in STORAGE_MODES:
        raise ValueError(
            f"TMR_QUANT_STORAGE={mode!r}: expected " + "|".join(STORAGE_MODES)
        )
    return mode


def quant_kernel() -> str:
    """Resolve TMR_QUANT_KERNEL at trace time ("auto" -> "dequant", the
    equality-pinned arm — faster int8-operand arms are opt-in or
    autotune-elected because they change numerics)."""
    k = os.environ.get("TMR_QUANT_KERNEL", "auto")
    if k not in QUANT_KERNELS:
        raise ValueError(
            f"TMR_QUANT_KERNEL={k!r}: expected " + "|".join(QUANT_KERNELS)
        )
    return "dequant" if k == "auto" else k


def quantize_int8(w: jnp.ndarray, axis=-1
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization, scales shared over the reduced
    ``axis`` (int or tuple of ints) and distinct over the kept axes.

    Decoder/head WEIGHTS reduce over their input axes so each OUTPUT
    channel gets its own scale (fused_heads._maybe_quant axis=0, the
    weights-tier grouping quant_ok bounds); the dynamic template bank
    reduces over the tap axis for one scale per (image, channel).

    Returns (q int8 same shape, scale f32 with the reduced axes kept as
    1). scale = amax/127 per group; all-zero groups quantize to scale 1
    so dequantization is exact (all-zero) instead of 0/0.
    """
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    # an explicit reciprocal MULTIPLY, not amax / 127: XLA's jit-time
    # algebraic simplifier rewrites divide-by-constant into multiply by
    # reciprocal, so a division here would make in-program (fake) scales
    # differ at the last ULP from offline (stored) scales computed
    # eagerly — breaking the storage tier's bitwise equality pin. One
    # multiply is the same op eager and jitted.
    scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / 127.0), 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray,
               dtype=jnp.bfloat16) -> jnp.ndarray:
    """int8 + per-group scale -> ``dtype`` operand for the matmul,
    emitted adjacent to the consuming dot_general so XLA fuses it into
    the operand read instead of materializing a dequantized copy."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def fake_quant(w: jnp.ndarray, axis=-1,
               dtype=jnp.bfloat16) -> jnp.ndarray:
    """Quantize-dequantize in one step: the value the quantized program
    actually multiplies by. The oracle gates compare THIS against the
    exact weights, so the pinned error is the error inference pays.

    Straight-through gradient: the rounding is wrapped so d(out)/d(w) is
    identity instead of zero. Inference (the only elected consumer —
    main.py scrubs TMR_QUANT for training) never differentiates this,
    but a stray grad trace through a quantized program must degrade to
    QAT semantics, not silently dead weights. Forward value is bitwise
    ``dequantize(quantize_int8(w))`` (the +0 identity folds away)."""
    q, s = quantize_int8(w, axis=axis)
    deq = jax.lax.stop_gradient(dequantize(q, s, dtype=dtype))
    wc = w.astype(dtype)
    return deq + (wc - jax.lax.stop_gradient(wc))


def _refused(gate: str, reason: str, cause: str, config: dict,
             exception=None) -> bool:
    from tmr_tpu.diagnostics import gate_refused

    return gate_refused(gate, reason, cause, config=config,
                        exception=exception)


_OK_CACHE: dict = {}


def quant_ok(h: int, w: int, c_in: int, c: int,
             num_layers: int = 1, kernel_size: int = 3) -> bool:
    """Tolerance-tiered oracle gate for the int8 decoder/head path at one
    geometry. Runs the two tiers on synthetic weights at the shapes about
    to trace:

    - tier "weights": per-channel int8 round-trip of a (k, k, c_in, c)
      kernel must stay inside WEIGHT_TIER_REL of the channel max — a
      construction bound; failing it means the quantizer itself is broken
      (grid asymmetry, scale underflow), not that the model is sensitive.
    - tier "output": the fused tail (ops/fused_heads) run with
      fake-quantized weights must stay inside OUTPUT_TIER_REL of its
      exact-weight output on random activations.

    Pure XLA both sides, so the gate is backend-agnostic; any exception
    or tier failure records a gate_probe/v1 cause and refuses.

    Scope: both tiers run on SYNTHETIC N(0, 0.01) weights at the real
    geometry — they pin the formulation and the quantizer, not the
    trained checkpoint's weight distribution (outlier-heavy channels can
    amplify output-tier error beyond what iid weights show). Accuracy on
    real weights is the eval harness's job; the election contract is
    gate + measured decisive win, with quality regression checked by the
    operator before exporting TMR_QUANT=int8 into production.
    """
    cfg = {"H": h, "W": w, "C_in": c_in, "C": c,
           "num_layers": num_layers, "kernel_size": kernel_size}
    key = (h, w, c_in, c, num_layers, kernel_size)
    if key in _OK_CACHE:
        return _OK_CACHE[key]
    import numpy as np

    ok = False
    try:
        with jax.ensure_compile_time_eval():
            rng = np.random.default_rng(0)
            k = kernel_size
            kern = jnp.asarray(
                rng.standard_normal((k, k, c_in, c)) * 0.01, jnp.float32
            )
            # tier "weights": reconstruction inside the grid bound, at
            # the production grouping — one scale per OUTPUT channel
            # (reduce over the k, k, c_in input axes), the same grouping
            # _maybe_quant applies per 2D tap (axis=0 there)
            rec = fake_quant(kern, axis=(0, 1, 2), dtype=jnp.float32)
            amax = jnp.max(jnp.abs(kern), axis=(0, 1, 2))
            err = jnp.max(
                jnp.abs(rec - kern) / jnp.maximum(amax, 1e-12)[None, None,
                                                               None, :]
            )
            if not bool(err <= WEIGHT_TIER_REL):
                _refused(
                    "quant_ok", f"weights tier: rel err {float(err):.4g} > "
                    f"{WEIGHT_TIER_REL:.4g}", "forward-mismatch",
                    {**cfg, "tier": "weights"},
                )
                _OK_CACHE[key] = False
                return False

            # tier "output": end-to-end tail error at this geometry. The
            # stacks are channel-preserving past layer 0 (only the first
            # kernel sees c_in), matching fused_decoder_heads' contract.
            from tmr_tpu.ops.fused_heads import fused_decoder_heads

            x = jnp.asarray(
                rng.standard_normal((1, h, w, c_in)), jnp.bfloat16
            )

            def stack():
                return [jnp.asarray(
                    rng.standard_normal((k, k, c_in if i == 0 else c, c))
                    * 0.01, jnp.float32,
                ) for i in range(num_layers)]

            wo = stack()
            wb = stack()
            bo = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            bb = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            w1 = jnp.asarray(rng.standard_normal((1, 1, c, 1)) * 0.01,
                             jnp.float32)
            w4 = jnp.asarray(rng.standard_normal((1, 1, c, 4)) * 0.01,
                             jnp.float32)
            b1 = jnp.zeros((1,), jnp.float32)
            b4 = jnp.zeros((4,), jnp.float32)

            def run(quant):
                return fused_decoder_heads(
                    x, list(zip(wo, bo)), list(zip(wb, bb)),
                    (w1, b1), (w4, b4), dtype=jnp.bfloat16, quant=quant,
                )

            o_exact, r_exact = run(False)
            o_q, r_q = run(True)
            scale = max(
                float(jnp.max(jnp.abs(o_exact.astype(jnp.float32)))),
                float(jnp.max(jnp.abs(r_exact.astype(jnp.float32)))), 1e-6,
            )
            rel = max(
                float(jnp.max(jnp.abs(
                    o_q.astype(jnp.float32) - o_exact.astype(jnp.float32)
                ))),
                float(jnp.max(jnp.abs(
                    r_q.astype(jnp.float32) - r_exact.astype(jnp.float32)
                ))),
            ) / scale
            ok = rel < OUTPUT_TIER_REL
            if not ok:
                _refused(
                    "quant_ok", f"output tier: rel err {rel:.4g} >= "
                    f"{OUTPUT_TIER_REL}", "forward-mismatch",
                    {**cfg, "tier": "output"},
                )
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        _refused("quant_ok", f"{type(e).__name__}: {e}", "exception",
                 cfg, exception=type(e).__name__)
        ok = False
    _OK_CACHE[key] = ok
    return ok


def quant_xcorr_ok(c: int, h: int, w: int, t: int,
                   kernel: str = "dequant") -> bool:
    """Output-tier oracle gate for the int8-template correlation at one
    geometry: the quantized matcher must stay inside OUTPUT_TIER_REL of
    the exact HIGHEST-precision correlation on random data. The template
    is runtime data (extracted from the feature map), so this pins the
    dynamic-quantization error path, not a fixed weight round trip.

    ``kernel="dequant"`` (the TMR_QUANT arm): int8-grid template
    dequantized to bf16, bf16 feature, f32 accumulation.
    ``kernel="int8dot"`` (the TMR_QUANT_KERNEL arm): BOTH operands on
    the int8 grid through an integer conv (int32 accumulation) with the
    per-(image, channel) dequant in the f32 epilogue — extra feature-
    quantization rounding, same tolerance.
    """
    cfg = {"C": c, "H": h, "W": w, "T": t, "kernel": kernel}
    key = ("xcorr", c, h, w, t, kernel)
    if key in _OK_CACHE:
        return _OK_CACHE[key]
    import numpy as np

    from jax import lax

    ok = False
    try:
        with jax.ensure_compile_time_eval():
            rng = np.random.default_rng(0)
            f = jnp.asarray(rng.standard_normal((1, c, h, w)), jnp.float32)
            tm = jnp.asarray(rng.standard_normal((1, c, t, t)), jnp.float32)
            want = np.asarray(lax.conv_general_dilated(
                f.reshape(1, c, h, w), tm.reshape(c, 1, t, t),
                window_strides=(1, 1),
                padding=[(t // 2, t // 2), (t // 2, t // 2)],
                feature_group_count=c,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=lax.Precision.HIGHEST,
            ))
            if kernel == "int8dot":
                from tmr_tpu.ops.xcorr import _xcorr_int8dot

                got = np.asarray(_xcorr_int8dot(f, tm))
            else:
                tq = fake_quant(tm.reshape(1, c, t * t), axis=-1,
                                dtype=jnp.bfloat16).reshape(1, c, t, t)
                got = np.asarray(lax.conv_general_dilated(
                    f.astype(jnp.bfloat16).reshape(1, c, h, w),
                    tq.reshape(c, 1, t, t),
                    window_strides=(1, 1),
                    padding=[(t // 2, t // 2), (t // 2, t // 2)],
                    feature_group_count=c,
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    preferred_element_type=jnp.float32,
                ))
            rel = float(np.abs(got - want).max() / (np.abs(want).max() + 1e-6))
            ok = rel < OUTPUT_TIER_REL
            if not ok:
                _refused(
                    "quant_xcorr_ok", f"output tier ({kernel}): rel err "
                    f"{rel:.4g} >= {OUTPUT_TIER_REL}", "forward-mismatch",
                    cfg,
                )
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        _refused("quant_xcorr_ok", f"{type(e).__name__}: {e}", "exception",
                 cfg, exception=type(e).__name__)
        ok = False
    _OK_CACHE[key] = ok
    return ok


def quantize_template(template: jnp.ndarray,
                      dtype=jnp.bfloat16) -> jnp.ndarray:
    """Dynamic int8 round trip of a (B, C, T, T) template bank, scaled
    per (image, channel) — the matcher-side TMR_QUANT arm. Returned in
    ``dtype`` ready for the correlation's multiply."""
    b, c, t, _ = template.shape
    return fake_quant(
        template.reshape(b, c, t * t), axis=-1, dtype=dtype
    ).reshape(b, c, t, t)


def quantize_int8_template(template: jnp.ndarray
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """True-int8 flavor of :func:`quantize_template` for the int8dot /
    Pallas correlation arms: (q int8 (B, C, T, T), scale f32
    (B, C, 1, 1)) — same per-(image, channel) grid and scales as the
    fake-quant arm, operands left on the int8 grid for an
    int8 x int8 -> int32 correlation."""
    b, c, t, _ = template.shape
    q, s = quantize_int8(template.reshape(b, c, t * t), axis=-1)
    return q.reshape(b, c, t, t), s.reshape(b, c, 1, 1)


# --------------------------------------------------------------------------
# offline-quantized param trees (TMR_QUANT_STORAGE=int8)
# --------------------------------------------------------------------------

#: param-tree paths eligible for int8 storage: the decoder conv stacks
#: and the two 1x1 heads — exactly the weights the fused formulation
#: (ops/fused_heads.py) round-trips through the int8 grid in-program.
#: Biases, norms, the matcher scale, input_proj and the whole backbone
#: stay f32. Each entry is (module-name regex, sub-path regex applied to
#: "sub/modules/leaf").
import re as _re

QUANT_TREE_PATTERNS = (
    (_re.compile(r"decoder_[ob]_\d+$"), _re.compile(r"conv_\d+/kernel$")),
    (_re.compile(r"(objectness|ltrbs)_head_\d+$"),
     _re.compile(r"conv/kernel$")),
)


def _eligible(path: Tuple[str, ...]) -> bool:
    """True when the params path (tuple of keys, leaf name last) is a
    storable decoder/head conv kernel."""
    if len(path) < 2:
        return False
    sub = "/".join(path[1:])
    return any(
        mod.search(path[0]) and rest.search(sub)
        for mod, rest in QUANT_TREE_PATTERNS
    )


class QuantizedParams:
    """One checkpoint's offline-quantized param tree.

    ``tree`` — the ORIGINAL param tree with every eligible kernel leaf
    replaced by its int8 quantization (same structure, same shapes: the
    compiled programs receive this, so HBM weight bytes for those leaves
    are 1/4 of f32). ``scales`` — a sparse tree holding only the
    quantized paths, each leaf the per-tap per-output-channel f32 scale
    (shape (k, k, 1, C_out)); passed to ``model.apply`` as the
    ``quant_scales`` collection and closed over by the compiled program
    (tiny — ~C_out floats per tap). ``digest`` — sha256 over the
    eligible leaves' bytes; programs key their compile cache on it so a
    checkpoint swap can never silently reuse stale scales.
    """

    def __init__(self, tree, scales, digest: str, paths: tuple,
                 weight_bytes: int, f32_weight_bytes: int):
        self.tree = tree
        self.scales = scales
        self.digest = digest
        self.paths = paths
        self.weight_bytes = weight_bytes
        self.f32_weight_bytes = f32_weight_bytes

    def stamp(self) -> dict:
        """Provenance record for stats()/health()/serve_report."""
        return {
            "mode": "int8",
            "storage": "int8",
            "digest": self.digest[:16],
            "quantized_leaves": len(self.paths),
            "weight_bytes": self.weight_bytes,
            "f32_weight_bytes": self.f32_weight_bytes,
        }


def _tree_digest(leaves: list) -> str:
    """sha256 over the eligible leaves' path + shape + bytes — the
    checkpoint identity the stored-tree cache keys on."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        h.update("/".join(path).encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


#: digest -> {"/".join(path): (q int8, scale f32)} — quantization runs
#: once per checkpoint per process; a second Predictor over the same
#: weights assembles from this cache (tests pin the hit).
_STORED_CACHE: dict = {}
_STORED_LOCK = None  # lazily a threading.Lock (import-light module)


def _stored_lock():
    global _STORED_LOCK
    if _STORED_LOCK is None:
        import threading

        _STORED_LOCK = threading.Lock()
    return _STORED_LOCK


def quantize_tree(params) -> QuantizedParams:
    """Materialize the int8 storage tree for one param tree.

    Every eligible 4D conv kernel (see :data:`QUANT_TREE_PATTERNS`)
    quantizes with ``axis=2`` — one scale per (tap, output channel),
    elementwise identical to the per-tap ``axis=0`` grouping the
    in-program fake-quant path applies (fused_heads._maybe_quant), which
    is what makes the stored output bitwise-equal to the fake path.
    Results are cached process-wide by checkpoint digest.
    """
    import numpy as np

    flat = _flatten_with_paths(params)
    eligible = [(p, v) for p, v in flat if _eligible(p)]
    if not eligible:
        raise ValueError(
            "quantize_tree: no storable decoder/head kernels in this "
            "param tree (box_reg-ablated or non-MatchingNet params?)"
        )
    digest = _tree_digest(eligible)
    with _stored_lock():
        cached = _STORED_CACHE.get(digest)
    if cached is None:
        cached = {}
        for path, leaf in eligible:
            q, s = quantize_int8(jnp.asarray(leaf), axis=2)
            cached["/".join(path)] = (q, s)
        with _stored_lock():
            _STORED_CACHE.setdefault(digest, cached)
    qtree = _replace_leaves(
        params, {p: cached["/".join(p)][0] for p, _ in eligible}
    )
    scales = _build_tree(
        {p: cached["/".join(p)][1] for p, _ in eligible}
    )
    weight_bytes = sum(
        int(np.prod(np.asarray(v).shape)) for _, v in eligible
    )  # int8: one byte per element
    return QuantizedParams(
        qtree, scales, digest, tuple("/".join(p) for p, _ in eligible),
        weight_bytes, 4 * weight_bytes,
    )


def _flatten_with_paths(tree, prefix=()):
    out = []
    if isinstance(tree, dict) or hasattr(tree, "items"):
        for k in sorted(tree):
            out.extend(_flatten_with_paths(tree[k], prefix + (str(k),)))
    else:
        out.append((prefix, tree))
    return out


def _replace_leaves(tree, repl: dict, prefix=()):
    if isinstance(tree, dict) or hasattr(tree, "items"):
        return {
            k: _replace_leaves(tree[k], repl, prefix + (str(k),))
            for k in tree
        }
    return repl.get(prefix, tree)


def _build_tree(leaves: dict):
    out: dict = {}
    for path, val in leaves.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return out


def quant_storage_ok(h: int, w: int, c_in: int, c: int,
                     num_layers: int = 1, kernel_size: int = 3) -> bool:
    """Tier "storage" of the quant oracle: the stored-int8 tail (offline
    int8 kernels + scales, dequantized adjacent to each matmul) must be
    **bitwise identical** to the admitted fake-quant tail at this
    geometry — same grid, same scales, so this is an equality pin, not a
    tolerance. Any mismatch or exception refuses with a recorded
    gate_probe/v1 cause (tier "storage")."""
    cfg = {"H": h, "W": w, "C_in": c_in, "C": c,
           "num_layers": num_layers, "kernel_size": kernel_size,
           "tier": "storage"}
    key = ("storage", h, w, c_in, c, num_layers, kernel_size)
    if key in _OK_CACHE:
        return _OK_CACHE[key]
    import numpy as np

    ok = False
    try:
        with jax.ensure_compile_time_eval():
            from tmr_tpu.ops.fused_heads import fused_decoder_heads

            rng = np.random.default_rng(0)
            k = kernel_size
            x = jnp.asarray(
                rng.standard_normal((1, h, w, c_in)), jnp.bfloat16
            )

            def stack():
                return [jnp.asarray(
                    rng.standard_normal((k, k, c_in if i == 0 else c, c))
                    * 0.01, jnp.float32,
                ) for i in range(num_layers)]

            wo, wb = stack(), stack()
            bo = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            bb = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            w1 = jnp.asarray(rng.standard_normal((1, 1, c, 1)) * 0.01,
                             jnp.float32)
            w4 = jnp.asarray(rng.standard_normal((1, 1, c, 4)) * 0.01,
                             jnp.float32)
            b1 = jnp.zeros((1,), jnp.float32)
            b4 = jnp.zeros((4,), jnp.float32)

            fake_o, fake_r = fused_decoder_heads(
                x, list(zip(wo, bo)), list(zip(wb, bb)),
                (w1, b1), (w4, b4), dtype=jnp.bfloat16, quant=True,
            )

            def store(ws):
                return [quantize_int8(wi, axis=2) for wi in ws]

            qo, qb = store(wo), store(wb)
            q1, s1 = quantize_int8(w1, axis=2)
            q4, s4 = quantize_int8(w4, axis=2)
            st_o, st_r = fused_decoder_heads(
                x,
                [(q, b_, s) for (q, s), b_ in zip(qo, bo)],
                [(q, b_, s) for (q, s), b_ in zip(qb, bb)],
                (q1, b1, s1), (q4, b4, s4),
                dtype=jnp.bfloat16, quant="stored",
            )
            ok = bool(jnp.array_equal(fake_o, st_o)) and bool(
                jnp.array_equal(fake_r, st_r)
            )
            if not ok:
                do = float(jnp.max(jnp.abs(
                    st_o.astype(jnp.float32) - fake_o.astype(jnp.float32)
                )))
                dr = float(jnp.max(jnp.abs(
                    st_r.astype(jnp.float32) - fake_r.astype(jnp.float32)
                )))
                _refused(
                    "quant_storage_ok",
                    f"storage tier: stored != fake bitwise (max abs diff "
                    f"obj {do:.3g}, reg {dr:.3g})", "forward-mismatch",
                    cfg,
                )
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        _refused("quant_storage_ok", f"{type(e).__name__}: {e}",
                 "exception", cfg, exception=type(e).__name__)
        ok = False
    _OK_CACHE[key] = ok
    return ok


def quant_int8dot_ok(h: int, w: int, c_in: int, c: int,
                     num_layers: int = 1, kernel_size: int = 3) -> bool:
    """Tier "int8dot" of the quant oracle: the both-operand-int8
    contraction (stored int8 weights + dynamically quantized activation,
    int32 accumulation, per-channel dequant in the f32 epilogue) must
    stay inside OUTPUT_TIER_REL of the EXACT tail at this geometry — a
    tolerance tier, because the activation quantization is rounding the
    bitwise-pinned arms never pay."""
    cfg = {"H": h, "W": w, "C_in": c_in, "C": c,
           "num_layers": num_layers, "kernel_size": kernel_size,
           "tier": "int8dot"}
    key = ("int8dot", h, w, c_in, c, num_layers, kernel_size)
    if key in _OK_CACHE:
        return _OK_CACHE[key]
    import numpy as np

    ok = False
    try:
        with jax.ensure_compile_time_eval():
            from tmr_tpu.ops.fused_heads import fused_decoder_heads

            rng = np.random.default_rng(0)
            k = kernel_size
            x = jnp.asarray(
                rng.standard_normal((1, h, w, c_in)), jnp.bfloat16
            )

            def stack():
                return [jnp.asarray(
                    rng.standard_normal((k, k, c_in if i == 0 else c, c))
                    * 0.01, jnp.float32,
                ) for i in range(num_layers)]

            wo, wb = stack(), stack()
            bo = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            bb = [jnp.zeros((c,), jnp.float32) for _ in range(num_layers)]
            w1 = jnp.asarray(rng.standard_normal((1, 1, c, 1)) * 0.01,
                             jnp.float32)
            w4 = jnp.asarray(rng.standard_normal((1, 1, c, 4)) * 0.01,
                             jnp.float32)
            b1 = jnp.zeros((1,), jnp.float32)
            b4 = jnp.zeros((4,), jnp.float32)

            o_e, r_e = fused_decoder_heads(
                x, list(zip(wo, bo)), list(zip(wb, bb)),
                (w1, b1), (w4, b4), dtype=jnp.bfloat16, quant=False,
            )

            def store(ws):
                return [quantize_int8(wi, axis=2) for wi in ws]

            qo, qb = store(wo), store(wb)
            q1, s1 = quantize_int8(w1, axis=2)
            q4, s4 = quantize_int8(w4, axis=2)
            o_q, r_q = fused_decoder_heads(
                x,
                [(q, b_, s) for (q, s), b_ in zip(qo, bo)],
                [(q, b_, s) for (q, s), b_ in zip(qb, bb)],
                (q1, b1, s1), (q4, b4, s4),
                dtype=jnp.bfloat16, quant="stored", kernel_arm="int8dot",
            )
            scale = max(
                float(jnp.max(jnp.abs(o_e.astype(jnp.float32)))),
                float(jnp.max(jnp.abs(r_e.astype(jnp.float32)))), 1e-6,
            )
            rel = max(
                float(jnp.max(jnp.abs(
                    o_q.astype(jnp.float32) - o_e.astype(jnp.float32)
                ))),
                float(jnp.max(jnp.abs(
                    r_q.astype(jnp.float32) - r_e.astype(jnp.float32)
                ))),
            ) / scale
            ok = rel < OUTPUT_TIER_REL
            if not ok:
                _refused(
                    "quant_int8dot_ok", f"int8dot tier: rel err "
                    f"{rel:.4g} >= {OUTPUT_TIER_REL}", "forward-mismatch",
                    cfg,
                )
    except Exception as e:
        if os.environ.get("TMR_GATE_DEBUG"):
            import traceback

            traceback.print_exc()
        _refused("quant_int8dot_ok", f"{type(e).__name__}: {e}",
                 "exception", cfg, exception=type(e).__name__)
        ok = False
    _OK_CACHE[key] = ok
    return ok


def stored_params_for(params, h: int, w: int, c_in: int, c: int,
                      num_layers: int, kernel_size: int,
                      dtype_name: str = "bfloat16",
                      box_reg: bool = True):
    """Predictor-side admission + materialization of the stored tree.

    Returns a :class:`QuantizedParams` when TMR_QUANT_STORAGE=int8 is
    admitted at this model geometry, else None — every refusal records a
    gate_probe/v1 cause AND warns (FormulationFallbackWarning, env var
    TMR_QUANT_STORAGE) so autotune sweeps annotate mislabeled timings.
    Admission requires, in order: TMR_QUANT=int8 (storage rides the
    admitted fake-quant path), a two-stack model (box_reg), no explicit
    TMR_DECODER_IMPL=xla pin (int8 leaves cannot run the module stack),
    and the fused/quant/storage oracle gates at the geometry.
    """
    import warnings

    from tmr_tpu.diagnostics import FormulationFallbackWarning
    from tmr_tpu.ops.fused_heads import fused_heads_ok

    if quant_storage_mode() != "int8":
        return None

    def refuse(reason: str, cause: str) -> None:
        _refused("quant_storage_ok", reason, cause,
                 {"H": h, "W": w, "C_in": c_in, "C": c, "tier": "storage"})
        warnings.warn(FormulationFallbackWarning(
            "TMR_QUANT_STORAGE",
            f"TMR_QUANT_STORAGE=int8: {reason}; running without int8 "
            "storage"
        ))

    if quant_mode() != "int8":
        refuse("TMR_QUANT=int8 not set (storage rides the admitted "
               "fake-quant path)", "kill-switch")
        return None
    if not box_reg:
        refuse("box_reg=False: the stored tail covers the two-stack "
               "formulation only", "unsupported-shape")
        return None
    if os.environ.get("TMR_DECODER_IMPL") == "xla":
        refuse("TMR_DECODER_IMPL=xla pinned: int8 leaves cannot run the "
               "XLA module stack", "kill-switch")
        return None
    if not fused_heads_ok(h, w, c_in, c, num_layers, kernel_size,
                          dtype_name):
        refuse("fused_heads_ok refused at this geometry", "forward-mismatch")
        return None
    if not quant_ok(h, w, c_in, c, num_layers, kernel_size):
        refuse("quant_ok refused at this geometry", "forward-mismatch")
        return None
    if not quant_storage_ok(h, w, c_in, c, num_layers, kernel_size):
        refuse("quant_storage_ok equality pin refused at this geometry",
               "forward-mismatch")
        return None
    return quantize_tree(params)

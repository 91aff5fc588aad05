"""Program-tier audit: the bucketed production programs traced to
jaxprs and checked structurally.

Four invariants, each cheap because everything here is TRACE-ONLY
(``jax.make_jaxpr`` over ``jax.eval_shape``-derived abstract params —
no compile, no execute, no device memory):

- **no-S²** — every attention formulation that claims streaming/tiled
  semantics must never materialize the (B, H, S, S) score tensor or the
  broadcast rel-pos bias: largest intermediate anywhere in the traced
  attention jaxpr stays below S*S elements, for every formulation a
  global block can take.
- **no-f64** — no equation output anywhere in a production program may
  be float64/complex128: on TPU a silent f64 upcast runs in emulation,
  on CPU it silently doubles bandwidth, and either way the oracle pins
  never blessed those numerics.
- **quant-widen** — inside the quantized path (TMR_QUANT=int8), no
  ``convert_element_type`` may widen beyond 32-bit floats: the int8
  dequant arithmetic is pinned at f32 accumulation, and a stray f64
  dequant would both break the quant_ok bound and destroy the win.
- **transfer-guard** — ``device_put`` equations per program are pinned
  to the expected count (zero: one means someone put a mid-program host
  hop into a hot path) and host
  callbacks (``pure_callback``/``io_callback``/``debug_callback``) must
  be ZERO — the rtt_floor regression mode. The device_put pin is
  per-platform (CPU constant staging differs from TPU), resolved
  baseline.transfer_guard[platform][program] first, then the in-code
  defaults.

``audit_production_programs`` is the entry point scripts/analyze.py,
gate_probe.py, and bench.py share; ``audit_jaxpr`` is the reusable
single-jaxpr predicate the fixture tests drive directly.
"""

from __future__ import annotations

import math
import os
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the global-attention formulations (ops/pallas_attn.global_formulation's
#: answers), each contractually bound to < S*S intermediates
NO_S2_ATTN_IMPLS = ("blockwise", "flash", "packed")

#: expected trace-time ``device_put`` count per production program: none.
#: JAX 0.9 embeds the numpy constants a trace stages (the ViT rel-pos
#: tables, norm stats) as literals, so any ``device_put`` equation is a
#: host hop somebody wrote into the program. Override per platform via
#: analysis_baseline.json ``transfer_guard`` or per call via
#: ``transfer_pins``.
DEFAULT_TRANSFER_PINS: Dict[str, int] = {
    "match_heads": 0,
    "match_heads_dp": 0,
    "backbone": 0,
    "heads_only": 0,
    "nms_topk": 0,
}

#: the three trace-time gate knobs whose cross product defines the
#: audited gate states (the PR 6 surface)
GATE_KNOBS = ("TMR_DECODER_IMPL", "TMR_QUANT", "TMR_DECODE_TAIL")

#: the full 2x2x2 sweep test coverage pins
ALL_GATE_STATES: Tuple[Dict[str, str], ...] = tuple(
    {"TMR_DECODER_IMPL": di, "TMR_QUANT": q, "TMR_DECODE_TAIL": dt}
    for di in ("xla", "fused")
    for q in ("off", "int8")
    for dt in ("host", "device")
)


# --------------------------------------------------------------------------
# jaxpr predicates
# --------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    # params may hold a jaxpr directly (scan/pjit 'jaxpr'), or a
    # tuple/list of them (cond/switch 'branches') — missing the latter
    # would blind every invariant inside conditional branches
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def iter_eqns(jaxpr):
    """Every equation in a jaxpr, sub-jaxprs (scan/pjit/pallas bodies)
    included, depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from iter_eqns(inner)


def jaxpr_stats(jaxpr) -> dict:
    """The structural facts every audit rule reads, in one walk:
    largest intermediate (elements), f64/complex128 equation count,
    widening convert_element_type count (target float wider than 32
    bits), device_put count, host-callback count."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    stats = {
        "max_intermediate_elems": 0,
        "f64_eqns": 0,
        "widening_converts": 0,
        "device_put": 0,
        "callbacks": 0,
    }
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "device_put":
            stats["device_put"] += 1
        elif "callback" in name:
            stats["callbacks"] += 1
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None:
                continue
            shape = getattr(aval, "shape", None)
            if shape is not None:
                stats["max_intermediate_elems"] = max(
                    stats["max_intermediate_elems"],
                    int(math.prod(shape)),
                )
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) in ("float64",
                                                    "complex128"):
                stats["f64_eqns"] += 1
                if name == "convert_element_type":
                    stats["widening_converts"] += 1
    return stats


def int8_reach_stats(jaxpr) -> dict:
    """Prove stored int8 weights actually FEED the matmuls (the
    TMR_QUANT_STORAGE audit): walk the jaxpr tainting every value
    transitively derived from an int8 program input (or int8 constant),
    and count the ``dot_general`` equations consuming a tainted or
    directly-int8 operand. The storage contract is that the program's
    int8 invars reach the dots through in-program widening only — a
    tree upconverted to f32 BEFORE the program boundary would show
    ``int8_invars == 0`` here even though the numerics still pass the
    equality pin (that is exactly the silent failure this rule exists
    to catch: the bytes would never have moved).

    Taint propagation is deliberately over-approximate (any equation
    with a tainted input taints all its outputs); sub-jaxprs map taint
    positionally where the invar lists line up (pjit) and fall back to
    whole-body tainting elsewhere (scan/cond) — over-taint can only
    produce a false PASS for a program with int8 inputs feeding nothing,
    which ``int8_invars`` plus the dot counts make visible."""
    from jax.extend.core import Literal

    top = getattr(jaxpr, "jaxpr", jaxpr)
    stats = {"int8_invars": 0, "dot_eqns": 0, "int8_fed_dots": 0,
             "int8_operand_dots": 0, "conv_eqns": 0,
             "int8_fed_convs": 0}

    def is_int8(v):
        dtype = getattr(getattr(v, "aval", None), "dtype", None)
        return dtype is not None and str(dtype) == "int8"

    def walk(jx, seed) -> bool:
        """Returns True when any outvar of ``jx`` ends tainted."""
        tainted = set(seed)
        for eqn in jx.eqns:
            name = eqn.primitive.name
            real_ins = [v for v in eqn.invars
                        if not isinstance(v, Literal)]
            any_t = any(v in tainted for v in real_ins)
            direct = any(is_int8(v) for v in real_ins)
            if name == "dot_general":
                stats["dot_eqns"] += 1
                if direct:
                    stats["int8_operand_dots"] += 1
                if any_t or direct:
                    stats["int8_fed_dots"] += 1
            elif name == "conv_general_dilated":
                stats["conv_eqns"] += 1
                if any_t or direct:
                    stats["int8_fed_convs"] += 1
            inner_tainted = False
            for val in eqn.params.values():
                items = val if isinstance(val, (tuple, list)) else (val,)
                for item in items:
                    inner = getattr(item, "jaxpr", item)
                    if not hasattr(inner, "eqns"):
                        continue
                    iseed = set(
                        v for v in getattr(inner, "constvars", ())
                        if is_int8(v)
                    )
                    if len(inner.invars) == len(eqn.invars):
                        for outer, iv in zip(eqn.invars, inner.invars):
                            if not isinstance(outer, Literal) and (
                                outer in tainted or is_int8(outer)
                            ):
                                iseed.add(iv)
                    elif any_t or direct:
                        iseed.update(inner.invars)
                    if walk(inner, iseed):
                        inner_tainted = True
            if any_t or direct or inner_tainted:
                tainted.update(
                    v for v in eqn.outvars if not isinstance(v, Literal)
                )
        return any(v in tainted for v in jx.outvars
                   if not isinstance(v, Literal))

    seed = set()
    for v in top.invars:
        if is_int8(v):
            stats["int8_invars"] += 1
            seed.add(v)
    seed.update(v for v in getattr(top, "constvars", ()) if is_int8(v))
    walk(top, seed)
    return stats


def audit_jaxpr(
    jaxpr,
    name: str,
    s2_bound: Optional[int] = None,
    quant: bool = False,
    transfer_pin: Optional[int] = None,
) -> dict:
    """Audit one traced program. Returns a record with the measured
    stats, a ``problems`` list (empty == clean), and ``ok``.

    ``s2_bound``: when set, max intermediate must stay strictly below it
    (the no-S² rule — pass S*S for an attention trace, omit for full
    programs whose legitimate tensors dwarf the reduced-geometry S²).
    ``quant``: apply the quant-widen rule (widening converts must be 0).
    ``transfer_pin``: expected device_put count (None = unpinned);
    callbacks must always be 0."""
    stats = jaxpr_stats(jaxpr)
    problems: List[str] = []
    if s2_bound is not None and stats["max_intermediate_elems"] >= s2_bound:
        problems.append(
            f"{name}: materializes a {stats['max_intermediate_elems']}-"
            f"element intermediate (bound S^2 = {s2_bound})"
        )
    if stats["f64_eqns"]:
        problems.append(
            f"{name}: {stats['f64_eqns']} float64/complex128 equation(s) "
            "in a production program"
        )
    if quant and stats["widening_converts"]:
        problems.append(
            f"{name}: {stats['widening_converts']} widening "
            "convert_element_type(s) beyond f32 inside the quantized path"
        )
    if stats["callbacks"]:
        problems.append(
            f"{name}: {stats['callbacks']} host callback(s) mid-program — "
            "the rtt_floor regression mode; hot paths must stay on device"
        )
    if transfer_pin is not None and stats["device_put"] != transfer_pin:
        problems.append(
            f"{name}: {stats['device_put']} device_put equation(s), "
            f"pinned {transfer_pin} for this platform — a new one means a "
            "host hop snuck into the program (update the per-platform pin "
            "in analysis_baseline.json transfer_guard only for an "
            "understood constant-staging change)"
        )
    return {"name": name, **stats, "s2_bound": s2_bound,
            "transfer_pin": transfer_pin, "quant": quant,
            "problems": problems, "ok": not problems}


# --------------------------------------------------------------------------
# attention-impl audit (PR 1's no-S² assert, generalized)
# --------------------------------------------------------------------------


def _attention_impl_fns() -> Dict[str, callable]:
    from tmr_tpu.models.vit import blockwise_decomposed_attention
    from tmr_tpu.ops.flash_attn import flash_decomposed_attention
    from tmr_tpu.ops.pallas_attn import _packed_global_on_heads

    return {
        "packed": _packed_global_on_heads,
        "blockwise": blockwise_decomposed_attention,
        "flash": flash_decomposed_attention,
    }


def audit_attention_impls(
    grids: Sequence[Tuple[int, int]] = ((64, 64),),
    head_dim: int = 64,
    impls: Optional[Iterable[str]] = None,
) -> dict:
    """Trace every global-attention formulation at the given grids and
    apply the no-S² bound to each. Trace-only —
    the production 64x64 grid costs ~0.1 s per impl on CPU."""
    import jax
    import jax.numpy as jnp

    fns = _attention_impl_fns()
    wanted = list(impls) if impls is not None else sorted(fns)
    out: Dict[str, dict] = {}
    ok = True
    for gh, gw in grids:
        S = gh * gw
        q = jax.ShapeDtypeStruct((1, 2, S, head_dim), jnp.bfloat16)
        rh = jax.ShapeDtypeStruct((gh, gh, head_dim), jnp.float32)
        rw = jax.ShapeDtypeStruct((gw, gw, head_dim), jnp.float32)
        for name in wanted:
            fn = fns[name]
            label = f"attn:{name}@{gh}x{gw}"
            bound = S * S
            try:
                jaxpr = jax.make_jaxpr(
                    lambda a, b, c, d, e, _f=fn: _f(
                        a, b, c, d, e, (gh, gw), head_dim**-0.5
                    )
                )(q, q, q, rh, rw)
            except Exception as e:  # an impl that cannot trace here is
                out[label] = {"name": label, "ok": True,  # not audited
                              "skipped": f"{type(e).__name__}: {e}"}
                continue
            rec = audit_jaxpr(jaxpr, label, s2_bound=bound)
            out[label] = rec
            ok = ok and rec["ok"]
    return {"grids": [list(g) for g in grids], "head_dim": head_dim,
            "impls": out, "ok": ok}


# --------------------------------------------------------------------------
# production-program audit
# --------------------------------------------------------------------------


def _platform() -> str:
    import jax

    return jax.default_backend()


def current_gate_state() -> Dict[str, str]:
    return {
        "TMR_DECODER_IMPL": os.environ.get("TMR_DECODER_IMPL", "auto"),
        "TMR_QUANT": os.environ.get("TMR_QUANT", "off"),
        "TMR_DECODE_TAIL": os.environ.get("TMR_DECODE_TAIL", "host"),
        "TMR_QUANT_STORAGE": os.environ.get("TMR_QUANT_STORAGE", "off"),
    }


def audit_storage_program(
    image_size: int = 32,
    emb_dim: int = 16,
    max_detections: int = 32,
    backbone: str = "resnet50_layer1",
) -> dict:
    """The stored-int8 program audited for REAL int8 reach: under
    TMR_QUANT_STORAGE=int8 (caller's env) a tiny-geometry Predictor is
    given real params, the stored tree is materialized through the full
    admission path (quant.stored_params_for), and the traced fused
    program is checked for (a) int8 invars — the program boundary
    actually receives int8 arrays, no silent upconvert — and (b) those
    invars feeding the decoder/head ``dot_general`` equations
    (:func:`int8_reach_stats`), plus the standard no-f64 / quant-widen /
    no-callback rules. Real (tiny) init instead of eval_shape because
    the stored tree's scales are concrete trace constants; ~1 s on CPU.
    """
    import jax
    import jax.numpy as jnp

    from tmr_tpu.inference import Predictor

    cfg = _audit_cfg(image_size, emb_dim, max_detections, backbone)
    pred = Predictor(cfg)
    pred.init_params(seed=0, image_size=image_size)
    problems: List[str] = []
    st = pred._storage_state()
    if st is None:
        from tmr_tpu.diagnostics import gate_refusals

        problems.append(
            "storage: TMR_QUANT_STORAGE=int8 was not admitted for the "
            "audit predictor (see recorded quant_storage_ok causes: "
            f"{[r['message'] for r in gate_refusals()[-3:]]})"
        )
        return {"name": "match_heads_stored", "ok": False,
                "problems": problems}
    cap = int(cfg.template_buckets[0])
    img = jax.ShapeDtypeStruct((1, image_size, image_size, 3),
                               jnp.float32)
    ex = jax.ShapeDtypeStruct((1, 1, 4), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        jaxpr = jax.make_jaxpr(pred._get_fn(cap))(st.tree, None, img, ex)
    rec = audit_jaxpr(jaxpr, "match_heads_stored", quant=True,
                      transfer_pin=None)
    reach = int8_reach_stats(jaxpr)
    rec.update(reach)
    k = int(cfg.decoder_kernel_size)
    # one conv = k^2 tap dots, + the block-diagonal head dot; every one
    # of them must be fed from an int8 invar
    min_dots = k * k + 1
    if reach["int8_invars"] < len(st.paths):
        problems.append(
            f"storage: program receives {reach['int8_invars']} int8 "
            f"invars but the stored tree holds {len(st.paths)} int8 "
            "leaves — something upconverted the tree at the boundary"
        )
    if reach["int8_fed_dots"] < min_dots:
        problems.append(
            f"storage: only {reach['int8_fed_dots']} dot_general "
            f"equation(s) fed from int8 inputs (expected >= {min_dots}: "
            f"{k}x{k} taps + the block-diagonal head)"
        )
    problems.extend(rec["problems"])
    rec["problems"] = problems
    rec["ok"] = not problems
    rec["stored_leaves"] = len(st.paths)
    rec["digest"] = st.digest[:16]
    return rec


def _audit_cfg(image_size: int, emb_dim: Optional[int],
               max_detections: int, backbone: str):
    from tmr_tpu.config import preset

    kw = dict(backbone=backbone, image_size=image_size,
              compute_dtype="float32", batch_size=1,
              max_detections=max_detections)
    if emb_dim is not None:
        kw["emb_dim"] = emb_dim
    return preset("TMR_FSCD147", **kw)


def _transfer_pin(baseline, platform: str, program: str,
                  overrides: Optional[Dict[str, int]] = None
                  ) -> Optional[int]:
    if overrides is not None:
        return overrides.get(program)
    if baseline is not None:
        pin = baseline.transfer_pin(platform, program)
        if pin is not None:
            return int(pin.get("device_put", 0)) if isinstance(
                pin, dict
            ) else int(pin)
    return DEFAULT_TRANSFER_PINS.get(program)


def _trace_programs(pred, params, image_size: int, batch: int,
                    programs: Sequence[str]) -> Dict[str, object]:
    """Trace the requested production programs under the CURRENT env
    knobs; returns {name: ClosedJaxpr}. Every trace is abstract —
    ShapeDtypeStruct inputs, eval_shape params."""
    import jax
    import jax.numpy as jnp

    img1 = jax.ShapeDtypeStruct((1, image_size, image_size, 3),
                                jnp.float32)
    ex1 = jax.ShapeDtypeStruct((1, 1, 4), jnp.float32)
    imgB = jax.ShapeDtypeStruct((batch, image_size, image_size, 3),
                                jnp.float32)
    exB = jax.ShapeDtypeStruct((batch, 1, 4), jnp.float32)
    cap = int(pred.cfg.template_buckets[0])
    out: Dict[str, object] = {}
    with warnings.catch_warnings():
        # a pinned-but-refused formulation warns FormulationFallback —
        # the audit then audits the fallback, which is what will run
        warnings.simplefilter("ignore")
        if "match_heads" in programs:
            out["match_heads"] = jax.make_jaxpr(pred._get_fn(cap))(
                params, None, img1, ex1
            )
        if "backbone" in programs or "heads_only" in programs:
            bb = pred._get_backbone_fn()
            if "backbone" in programs:
                out["backbone"] = jax.make_jaxpr(bb)(params, imgB)
            if "heads_only" in programs:
                feat = jax.eval_shape(bb, params, imgB)
                out["heads_only"] = jax.make_jaxpr(
                    pred._get_heads_fn(cap, image_size)
                )(params, None, feat, exB)
        if "nms_topk" in programs:
            from tmr_tpu.ops.pallas_nms import nms_topk

            boxes = jax.ShapeDtypeStruct((batch, 64, 4), jnp.float32)
            scores = jax.ShapeDtypeStruct((batch, 64), jnp.float32)
            valid = jax.ShapeDtypeStruct((batch, 64), jnp.bool_)
            out["nms_topk"] = jax.make_jaxpr(
                lambda b, s, v: nms_topk(b, s, 0.5, valid=v, k=32)
            )(boxes, scores, valid)
        if "match_heads_dp" in programs:
            # the mesh-sharded serving variant (shard_map over dp, the
            # bitwise-exact fan-out path): trace-only like everything
            # here — the shard_map in_specs path needs no real params.
            # Needs >= 2 local devices for a dp-2 mesh; a single-device
            # runtime records a skip instead of failing the audit (the
            # forced-8-device test conftest is where the pin is load-
            # bearing).
            if len(jax.devices()) >= 2:
                from tmr_tpu.serve.meshplan import MeshPlan

                plan = MeshPlan("dp2", devices=jax.devices())
                dp_batch = max(2, batch + (batch % 2))
                img_dp = jax.ShapeDtypeStruct(
                    (dp_batch, image_size, image_size, 3), jnp.float32
                )
                ex_dp = jax.ShapeDtypeStruct((dp_batch, 1, 4),
                                             jnp.float32)
                out["match_heads_dp"] = jax.make_jaxpr(
                    pred._get_sharded_fn(cap, plan.dp_target)
                )(params, None, img_dp, ex_dp)
    return out


def audit_production_programs(
    baseline=None,
    image_size: int = 64,
    emb_dim: Optional[int] = None,
    max_detections: int = 64,
    batch: int = 2,
    backbone: str = "sam_vit_b",
    transfer_pins: Optional[Dict[str, int]] = None,
    gate_states: Optional[Sequence[Dict[str, str]]] = None,
    programs: Sequence[str] = ("match_heads", "match_heads_dp",
                               "backbone", "heads_only", "nms_topk"),
    attention_grids: Sequence[Tuple[int, int]] = ((64, 64),),
    include_attention: bool = True,
    record_refusals: bool = False,
) -> dict:
    """The full program-tier audit record (the ``program_audit`` section
    of analysis_report/v1).

    ``gate_states``: list of env-knob dicts to sweep (each audits the
    knob-dependent programs; the FIRST state audits everything
    requested). None = audit once under the ambient env — what bench.py
    wants after autotune exported its winners. ``record_refusals``: on a
    failing program, record a structured ``gate_probe/v1`` cause via
    diagnostics.gate_refused — the same contract the kernel gates keep,
    so an autotune-elected path that fails the audit travels with WHY.
    """
    platform = _platform()
    cfg = _audit_cfg(image_size, emb_dim, max_detections, backbone)

    import jax
    import jax.numpy as jnp

    from tmr_tpu.inference import Predictor

    pred = Predictor(cfg)
    params = jax.eval_shape(
        lambda k: pred.model.init(
            k,
            jnp.zeros((1, image_size, image_size, 3), jnp.float32),
            jnp.zeros((1, 1, 4), jnp.float32),
        ),
        jax.random.key(0),
    )["params"]

    states = list(gate_states) if gate_states is not None else [None]
    state_records: List[dict] = []
    problems: List[str] = []
    saved = {k: os.environ.get(k) for k in GATE_KNOBS}
    try:
        for i, state in enumerate(states):
            if state is not None:
                for k in GATE_KNOBS:
                    if state.get(k) is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = state[k]
                pred._compiled.clear()  # knobs are read at trace time
            wanted = (
                programs if i == 0
                else [p for p in programs
                      if p in ("match_heads", "heads_only")]
            )
            quant = os.environ.get("TMR_QUANT", "off") == "int8"
            jaxprs = _trace_programs(pred, params, image_size, batch,
                                     wanted)
            recs = []
            for name, jaxpr in jaxprs.items():
                rec = audit_jaxpr(
                    jaxpr, name, quant=quant,
                    transfer_pin=_transfer_pin(baseline, platform, name,
                                               transfer_pins),
                )
                recs.append(rec)
                problems.extend(rec["problems"])
                if record_refusals and not rec["ok"]:
                    from tmr_tpu.diagnostics import gate_refused

                    gate_refused(
                        "program_audit", "; ".join(rec["problems"]),
                        "forward-mismatch",
                        config={"program": name, "platform": platform,
                                **current_gate_state()},
                    )
            state_records.append({
                "gate_state": current_gate_state(),
                "programs": recs,
                "ok": all(r["ok"] for r in recs),
            })
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if gate_states is not None:
            pred._compiled.clear()

    attention = None
    if include_attention:
        attention = audit_attention_impls(grids=attention_grids)
        problems.extend(
            p for rec in attention["impls"].values()
            for p in rec.get("problems", ())
        )
        if record_refusals and not attention["ok"]:
            from tmr_tpu.diagnostics import gate_refused

            gate_refused(
                "program_audit",
                "attention no-S^2 audit failed",
                "forward-mismatch",
                config={"program": "attention", "platform": platform},
            )

    # storage audit: when the ambient env elects TMR_QUANT_STORAGE=int8
    # (autotune export / explicit pin), prove the int8 leaves reach the
    # matmuls with real (tiny) params — the states sweep above traces
    # abstract eval_shape params, which cannot exercise the stored tree
    storage = None
    if os.environ.get("TMR_QUANT_STORAGE", "off") == "int8":
        try:
            storage = audit_storage_program()
        except Exception as e:
            storage = {"name": "match_heads_stored", "ok": False,
                       "problems": [
                           f"storage audit raised {type(e).__name__}: {e}"
                       ]}
        problems.extend(storage["problems"])
        if record_refusals and not storage["ok"]:
            from tmr_tpu.diagnostics import gate_refused

            gate_refused(
                "program_audit", "; ".join(storage["problems"]),
                "forward-mismatch",
                config={"program": "match_heads_stored",
                        "platform": platform, **current_gate_state()},
            )

    return {
        "platform": platform,
        "geometry": {"image_size": image_size,
                     "emb_dim": emb_dim or cfg.emb_dim,
                     "batch": batch},
        "states": state_records,
        "attention": attention,
        "storage": storage,
        "problems": problems,
        "ok": not problems,
    }

"""True int8 storage (TMR_QUANT_STORAGE, ops/quant.quantize_tree):
offline-quantized param trees, the bitwise stored-vs-fake equality
contract end-to-end through Predictor, the digest cache, the int8-reach
program audit, the devtime weight-bytes accounting, and the serve-layer
quant provenance stamp."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tmr_tpu.diagnostics import drain_gate_refusals
from tmr_tpu.ops import quant as q

TINY = dict(backbone="resnet50_layer1", image_size=64, emb_dim=16,
            compute_dtype="bfloat16", batch_size=1, max_detections=64)


def _tiny_cfg(**over):
    from tmr_tpu.config import preset

    return preset("TMR_FSCD147", **{**TINY, **over})


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in ("TMR_QUANT", "TMR_QUANT_STORAGE", "TMR_QUANT_KERNEL",
              "TMR_DECODER_IMPL", "TMR_NO_FUSED_HEADS",
              "TMR_NO_PALLAS_INT8"):
        monkeypatch.delenv(k, raising=False)
    q._OK_CACHE.clear()
    drain_gate_refusals()
    yield
    q._OK_CACHE.clear()
    drain_gate_refusals()


def _mk_tree(rng, c=8):
    z = lambda *s: jnp.zeros(s, jnp.float32)
    kern = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.05,
                                  jnp.float32)
    return {
        "backbone": {"conv": {"kernel": kern(3, 3, 3, c),
                              "bias": z(c)}},
        "input_proj_0": {"kernel": kern(1, 1, c, c), "bias": z(c)},
        "decoder_o_0": {"conv_0": {"kernel": kern(3, 3, c, c),
                                   "bias": z(c)}},
        "decoder_b_0": {"conv_0": {"kernel": kern(3, 3, c, c),
                                   "bias": z(c)}},
        "objectness_head_0": {"conv": {"kernel": kern(1, 1, c, 1),
                                       "bias": z(1)}},
        "ltrbs_head_0": {"conv": {"kernel": kern(1, 1, c, 4),
                                  "bias": z(4)}},
    }


# ------------------------------------------------------- quantize_tree


def test_quantize_tree_structure_dtypes_and_scales():
    """int8 leaves exactly at the decoder/head kernel paths, per-tap
    per-output-channel scales, everything else untouched."""
    rng = np.random.default_rng(0)
    tree = _mk_tree(rng)
    qp = q.quantize_tree(tree)
    assert sorted(qp.paths) == [
        "decoder_b_0/conv_0/kernel", "decoder_o_0/conv_0/kernel",
        "ltrbs_head_0/conv/kernel", "objectness_head_0/conv/kernel",
    ]
    assert qp.tree["decoder_o_0"]["conv_0"]["kernel"].dtype == jnp.int8
    assert qp.tree["ltrbs_head_0"]["conv"]["kernel"].dtype == jnp.int8
    # untouched leaves ride through as-is (same objects)
    assert qp.tree["backbone"]["conv"]["kernel"] is \
        tree["backbone"]["conv"]["kernel"]
    assert qp.tree["input_proj_0"]["kernel"].dtype == jnp.float32
    assert qp.tree["decoder_o_0"]["conv_0"]["bias"].dtype == jnp.float32
    # per-tap per-output-channel scales: (k, k, 1, C_out)
    assert qp.scales["decoder_o_0"]["conv_0"]["kernel"].shape == \
        (3, 3, 1, 8)
    assert qp.scales["ltrbs_head_0"]["conv"]["kernel"].shape == \
        (1, 1, 1, 4)
    assert "backbone" not in qp.scales
    # int8 bytes are exactly 1/4 the f32 bytes of the same leaves
    assert qp.f32_weight_bytes == 4 * qp.weight_bytes


def test_quantize_tree_round_trip_matches_per_tap_fake_quant():
    """axis=2 offline quantization is elementwise the per-tap axis=0
    grouping the in-program fake path applies — the bitwise contract's
    foundation."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) * 0.05, jnp.float32)
    qw, s = q.quantize_int8(w, axis=2)
    for dy in range(3):
        for dx in range(3):
            q2, s2 = q.quantize_int8(w[dy, dx], axis=0)
            np.testing.assert_array_equal(np.asarray(qw[dy, dx]),
                                          np.asarray(q2))
            np.testing.assert_array_equal(np.asarray(s[dy, dx]),
                                          np.asarray(s2))
            np.testing.assert_array_equal(
                np.asarray(q.fake_quant(w[dy, dx], axis=0,
                                        dtype=jnp.float32)),
                np.asarray(q.dequantize(qw[dy, dx], s[dy, dx],
                                        jnp.float32)),
            )


def test_quantize_tree_digest_cache_hit_skips_requantization(monkeypatch):
    """Same weight bytes (different array objects) -> same digest -> the
    cached int8 leaves are reused, quantize_int8 never runs again."""
    rng = np.random.default_rng(2)
    tree = _mk_tree(rng)
    qp1 = q.quantize_tree(tree)
    calls = []
    real = q.quantize_int8
    monkeypatch.setattr(
        q, "quantize_int8", lambda *a, **k: calls.append(1) or real(*a, **k)
    )
    copy = jax.tree.map(lambda x: jnp.array(np.asarray(x)), tree)
    qp2 = q.quantize_tree(copy)
    assert qp2.digest == qp1.digest
    assert calls == []  # digest hit: no re-quantization
    assert qp2.tree["decoder_o_0"]["conv_0"]["kernel"] is \
        qp1.tree["decoder_o_0"]["conv_0"]["kernel"]
    # different weights -> different digest, fresh quantization
    tree3 = _mk_tree(np.random.default_rng(3))
    qp3 = q.quantize_tree(tree3)
    assert qp3.digest != qp1.digest
    assert calls  # re-quantized


def test_quantize_tree_refuses_non_matching_tree():
    with pytest.raises(ValueError, match="no storable"):
        q.quantize_tree({"backbone": {"w": jnp.zeros((2, 2))}})


# ------------------------------------------------------- gates / modes


def test_storage_and_kernel_mode_validation(monkeypatch):
    assert q.quant_storage_mode() == "off"
    assert q.quant_kernel() == "dequant"  # auto resolves to the pin
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    assert q.quant_storage_mode() == "int8"
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int4")
    with pytest.raises(ValueError, match="TMR_QUANT_STORAGE"):
        q.quant_storage_mode()
    monkeypatch.setenv("TMR_QUANT_KERNEL", "int8dot")
    assert q.quant_kernel() == "int8dot"
    monkeypatch.setenv("TMR_QUANT_KERNEL", "fp8")
    with pytest.raises(ValueError, match="TMR_QUANT_KERNEL"):
        q.quant_kernel()


def test_quant_storage_ok_equality_pin_small_geometry():
    assert q.quant_storage_ok(8, 8, 16, 16, num_layers=2, kernel_size=3)
    assert drain_gate_refusals() == []


def test_quant_storage_ok_refusal_records_storage_tier(monkeypatch):
    """Perturb the offline scales (axis=2 path only): stored != fake ->
    the equality pin refuses with tier 'storage' recorded and caches the
    verdict."""
    real = q.quantize_int8

    def skewed(w, axis=-1):
        qq, s = real(w, axis=axis)
        if axis == 2:  # the offline grouping only
            s = s * 1.5
        return qq, s

    monkeypatch.setattr(q, "quantize_int8", skewed)
    assert not q.quant_storage_ok(8, 8, 16, 16)
    causes = drain_gate_refusals()
    assert causes and causes[-1]["gate"] == "quant_storage_ok"
    assert causes[-1]["config"]["tier"] == "storage"
    assert not q.quant_storage_ok(8, 8, 16, 16)  # cached
    assert drain_gate_refusals() == []


def test_quant_int8dot_ok_small_geometry():
    assert q.quant_int8dot_ok(8, 8, 16, 16)
    assert drain_gate_refusals() == []


def test_quant_xcorr_int8dot_tier():
    assert q.quant_xcorr_ok(8, 12, 12, 5, kernel="int8dot")
    assert drain_gate_refusals() == []


def test_stored_params_for_admission_refusals(monkeypatch):
    """Every admission refusal returns None with a recorded cause AND a
    FormulationFallbackWarning naming TMR_QUANT_STORAGE."""
    from tmr_tpu.diagnostics import FormulationFallbackWarning

    rng = np.random.default_rng(4)
    tree = _mk_tree(rng)
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    # TMR_QUANT unset: storage rides the admitted fake-quant path only
    with pytest.warns(FormulationFallbackWarning):
        assert q.stored_params_for(tree, 8, 8, 16, 16, 1, 3) is None
    assert drain_gate_refusals()[-1]["gate"] == "quant_storage_ok"
    monkeypatch.setenv("TMR_QUANT", "int8")
    # explicit xla pin: int8 leaves cannot run the module stack
    monkeypatch.setenv("TMR_DECODER_IMPL", "xla")
    with pytest.warns(FormulationFallbackWarning):
        assert q.stored_params_for(tree, 8, 8, 16, 16, 1, 3) is None
    monkeypatch.delenv("TMR_DECODER_IMPL")
    # single-stack model
    with pytest.warns(FormulationFallbackWarning):
        assert q.stored_params_for(tree, 8, 8, 16, 16, 1, 3,
                                   box_reg=False) is None
    # admitted: a real QuantizedParams
    qp = q.stored_params_for(tree, 8, 8, 16, 16, 1, 3)
    assert qp is not None and len(qp.paths) == 4


# -------------------------------------------------- Predictor end-to-end


@pytest.fixture(scope="module")
def tiny_pred():
    from tmr_tpu.inference import Predictor

    cfg = _tiny_cfg()
    pred = Predictor(cfg)
    pred.init_params(seed=0, image_size=64)
    return pred


def _inputs():
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((1, 64, 64, 3)), jnp.float32)
    ex = jnp.asarray([[[0.4, 0.4, 0.6, 0.6]]], jnp.float32)
    return img, ex


def test_predictor_stored_bitwise_vs_fake_reduced(tiny_pred, monkeypatch):
    """The acceptance pin at the reduced CPU geometry: the full fused
    program with a stored int8 tree is bitwise-identical to the admitted
    fake-quant program — single AND batched-multi paths."""
    from tmr_tpu.inference import Predictor

    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_QUANT", "int8")
    img, ex = _inputs()
    fake = tiny_pred(img, ex)
    fake_multi = tiny_pred.predict_multi_exemplar(
        img, np.asarray([[0.4, 0.4, 0.6, 0.6], [0.3, 0.3, 0.5, 0.5]],
                        np.float32),
    )
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    pred2 = Predictor(tiny_pred.cfg, params=tiny_pred.params)
    st = pred2._storage_state()
    assert st is not None, "storage must be admitted at tiny geometry"
    assert pred2.exec_params() is st.tree
    stored = pred2(img, ex)
    for k in ("boxes", "scores", "refs", "valid"):
        np.testing.assert_array_equal(np.asarray(fake[k]),
                                      np.asarray(stored[k]), err_msg=k)
    stored_multi = pred2.predict_multi_exemplar(
        img, np.asarray([[0.4, 0.4, 0.6, 0.6], [0.3, 0.3, 0.5, 0.5]],
                        np.float32),
    )
    for k in ("boxes", "scores", "refs", "valid"):
        np.testing.assert_array_equal(
            np.asarray(fake_multi[k]), np.asarray(stored_multi[k]),
            err_msg=f"multi:{k}",
        )
    # program keys carry the checkpoint digest (stale-scale protection)
    assert any(st.digest in map(str, key) for key in pred2._compiled)
    # provenance stamp
    stamp = pred2.quant_stamp()
    assert stamp["mode"] == "int8" and stamp["storage"] == "int8"
    assert stamp["f32_weight_bytes"] == 4 * stamp["weight_bytes"]


def test_predictor_storage_off_without_quant(tiny_pred, monkeypatch):
    """TMR_QUANT_STORAGE alone (no TMR_QUANT=int8) must refuse and run
    the exact path — never silently quantize."""
    from tmr_tpu.inference import Predictor

    img, ex = _inputs()
    # fresh Predictor for the exact reference: the env knobs are read at
    # trace time, so tiny_pred's cached programs belong to other states
    exact = Predictor(tiny_pred.cfg, params=tiny_pred.params)(img, ex)
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    with pytest.warns(Warning):
        pred2 = Predictor(tiny_pred.cfg, params=tiny_pred.params)
        assert pred2._storage_state() is None
        got = pred2(img, ex)
    for k in ("boxes", "scores"):
        np.testing.assert_array_equal(np.asarray(exact[k]),
                                      np.asarray(got[k]))
    assert pred2.quant_stamp() is None


def test_second_predictor_hits_digest_cache(tiny_pred, monkeypatch):
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_QUANT", "int8")
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    from tmr_tpu.inference import Predictor

    p1 = Predictor(tiny_pred.cfg, params=tiny_pred.params)
    st1 = p1._storage_state()
    assert st1 is not None
    calls = []
    real = q.quantize_int8
    monkeypatch.setattr(
        q, "quantize_int8", lambda *a, **k: calls.append(1) or real(*a, **k)
    )
    p2 = Predictor(
        tiny_pred.cfg,
        params=jax.tree.map(lambda x: jnp.array(np.asarray(x)),
                            tiny_pred.params),
    )
    st2 = p2._storage_state()
    assert st2 is not None and st2.digest == st1.digest
    assert calls == []  # no re-quantization on the second Predictor


# ---------------------------------------------- accounting + audit


def test_mfu_report_weight_bytes_halved_and_roofline_flip():
    """The acceptance accounting pin: per-program weight bytes from the
    devtime table drop >= 2x (4x for the quantized leaves) when the
    program receives the int8 tree, cost_analysis() bytes drop with
    them, and a formerly memory-bound program's roofline verdict flips
    to compute at the same shape."""
    from tmr_tpu.obs import devtime, flight

    flight.configure(enabled=True)
    devtime.reset()
    try:
        rng = np.random.default_rng(0)
        K = N = 512
        # M rows against a (K, N) weight: about 2M flops per weight
        # element, over 4+2+2 bytes of it stored f32 (read, bf16 copy
        # written, copy read: XLA:CPU does not fuse the convert into the
        # dot) and 1+2+2 stored int8 — intensity ~M/4 against ~2M/5.
        # The CPU row's ridge is 10 flops/byte, so 25 <= M < 40 puts the
        # two storages on opposite sides of it.
        M = 32
        w = jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.float32)
        x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)

        @jax.jit
        def f32_prog(params, x):
            return jax.lax.dot_general(
                x, params["w"].astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        qw, s = q.quantize_int8(w, axis=0)

        @jax.jit
        def int8_prog(params, x):
            op = q.dequantize(params["w"], params["s"], jnp.bfloat16)
            return jax.lax.dot_general(
                x, op, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        wf = devtime.track_devtime(f32_prog, "heads", ("f32",))
        wi = devtime.track_devtime(int8_prog, "heads", ("int8",))
        for _ in range(2):
            jax.block_until_ready(wf({"w": w}, x))
            jax.block_until_ready(wi({"w": qw, "s": s}, x))
        doc = devtime.mfu_report()
        from tmr_tpu.diagnostics import validate_mfu_report

        assert validate_mfu_report(doc) == []
        pf = next(p for p in doc["programs"] if "f32" in p["key"])
        pi = next(p for p in doc["programs"] if "int8" in p["key"])
        assert not pf["int8_weights"] and pi["int8_weights"]
        assert pf["weight_bytes"] >= 2 * pi["weight_bytes"]
        # cost_analysis bytes move with the storage, enough to flip the
        # roofline verdict of this memory-bound shape
        assert pf["cost_source"] == "xla" and pi["cost_source"] == "xla"
        assert pi["bytes_per_call"] < pf["bytes_per_call"]
        assert (pi["arithmetic_intensity"]
                >= 1.4 * pf["arithmetic_intensity"])  # 8/5 less x and out
        assert pf["bound"] == "memory"
        assert pi["bound"] == "compute"
    finally:
        flight.configure(enabled=False)
        devtime.reset()


def test_storage_audit_proves_int8_reach(monkeypatch):
    """The program audit's storage rule: int8 leaves arrive as program
    invars AND feed the decoder/head dot_generals."""
    monkeypatch.setenv("TMR_QUANT", "int8")
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    from tmr_tpu.analysis.program_audit import audit_storage_program

    rec = audit_storage_program(image_size=32, emb_dim=16,
                                backbone="resnet50_layer1",
                                max_detections=32)
    assert rec["ok"], rec["problems"]
    assert rec["int8_invars"] == rec["stored_leaves"] == 4
    assert rec["int8_fed_dots"] >= 10  # 3x3 taps + block-diagonal head
    assert rec["widening_converts"] == 0  # quant-widen still holds


def test_int8_reach_stats_detects_upconverted_tree():
    """A program handed an f32 tree (the silent-upconvert failure mode)
    shows zero int8 invars — the exact signal the audit keys on."""
    from tmr_tpu.analysis.program_audit import int8_reach_stats

    @jax.jit
    def prog(w, x):
        return x @ w

    w8 = jnp.ones((4, 4), jnp.int8)
    x = jnp.ones((2, 4), jnp.float32)
    good = int8_reach_stats(
        jax.make_jaxpr(lambda w, x: prog(w.astype(jnp.float32) * 0.1, x))(
            w8, x
        )
    )
    assert good["int8_invars"] == 1 and good["int8_fed_dots"] >= 1
    bad = int8_reach_stats(
        jax.make_jaxpr(prog)(jnp.ones((4, 4), jnp.float32), x)
    )
    assert bad["int8_invars"] == 0 and bad["int8_fed_dots"] == 0


# ----------------------------------------------------- serve provenance


def test_serve_engine_carries_quant_stamp(tiny_pred, monkeypatch):
    """stats()/health() carry the quant stamp under storage mode, the
    health document still validates, and the default-off engine keeps
    its byte-identical shape (no 'quant' key)."""
    from tmr_tpu.diagnostics import validate_health_report
    from tmr_tpu.serve.engine import ServeEngine

    eng = ServeEngine(tiny_pred, batch=1, exemplar_cache=0,
                      feature_cache=0)
    try:
        assert "quant" not in eng.stats()
        assert "quant" not in eng.health()
    finally:
        eng.close(timeout=5)
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_QUANT", "int8")
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    from tmr_tpu.inference import Predictor

    pred2 = Predictor(tiny_pred.cfg, params=tiny_pred.params)
    eng2 = ServeEngine(pred2, batch=1, exemplar_cache=0, feature_cache=0)
    try:
        stats = eng2.stats()
        assert stats["quant"]["storage"] == "int8"
        assert stats["quant"]["mode"] == "int8"
        health = eng2.health()
        assert health["quant"]["digest"]
        assert validate_health_report(health) == []
    finally:
        eng2.close(timeout=5)


def test_quant_attachment_validator_rejects_bad_stamp():
    from tmr_tpu.diagnostics import _validate_quant_attachment

    assert _validate_quant_attachment({}) == []
    ok = {"quant": {"mode": "int8", "storage": "int8", "digest": "ab",
                    "quantized_leaves": 4, "weight_bytes": 10,
                    "f32_weight_bytes": 40}}
    assert _validate_quant_attachment(ok) == []
    bad = {"quant": {"mode": "fp4", "storage": "int8"}}
    problems = _validate_quant_attachment(bad)
    assert any("mode" in p for p in problems)
    assert any("digest" in p for p in problems)


# ------------------------------------------------------- training scrub


def test_training_scrub_strips_storage_knobs():
    """main.py's training invariant: stored-int8 trees are
    inference-only — both quant knobs scrub before a training trace."""
    import main as main_mod

    env = {"TMR_QUANT": "int8", "TMR_QUANT_STORAGE": "int8",
           "TMR_DECODER_IMPL": "fused"}
    scrubbed = main_mod.scrub_training_env(env)
    assert sorted(scrubbed) == ["TMR_QUANT", "TMR_QUANT_STORAGE"]
    assert env["TMR_QUANT"] == "off"
    assert env["TMR_QUANT_STORAGE"] == "off"
    assert env["TMR_DECODER_IMPL"] == "fused"  # gradient-valid, kept
    assert main_mod.scrub_training_env({"TMR_QUANT": "off"}) == []


def test_training_step_params_never_int8(tiny_pred, monkeypatch):
    """Even with the storage knobs exported (pre-scrub worst case), the
    training side's param tree holds no int8 leaf — storage lives only
    inside Predictor program builds."""
    monkeypatch.setenv("TMR_QUANT", "int8")
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    from tmr_tpu.train.state import create_train_state

    state = create_train_state(
        tiny_pred.model, _tiny_cfg(), jax.random.key(0),
        jnp.zeros((1, 64, 64, 3), jnp.float32),
        jnp.array([[[0.4, 0.4, 0.6, 0.6]]], jnp.float32),
    )
    dtypes = {str(x.dtype) for x in jax.tree.leaves(state.params)}
    assert "int8" not in dtypes


# ------------------------------------------------ pallas int8 kernel


def test_pallas_int8_matmul_interpret_matches_xla():
    from tmr_tpu.ops.pallas_int8 import int8_matmul

    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-127, 128, (200, 300)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (300, 70)), jnp.int8)
    sx = jnp.asarray(rng.random((200, 1)) * 0.01 + 1e-4, jnp.float32)
    sw = jnp.asarray(rng.random((1, 70)) * 0.01 + 1e-4, jnp.float32)
    got = np.asarray(int8_matmul(xq, wq, sx, sw, interpret=True))
    want = np.asarray(
        jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32
                            ).astype(jnp.float32) * (sx * sw)
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_pallas_int8_gate_refuses_off_tpu_with_cause():
    from tmr_tpu.ops import pallas_int8 as pi8

    pi8.pallas_int8_ok.cache_clear()
    assert not pi8.pallas_int8_ok()
    causes = drain_gate_refusals()
    assert causes and causes[-1]["gate"] == "pallas_int8_ok"
    assert causes[-1]["cause"] in ("backend", "exception")


def test_stored_int8dot_arm_within_tolerance(monkeypatch):
    """TMR_QUANT_KERNEL=int8dot through the jitted stage program: int8
    operands both sides, inside the output tier of the fake path."""
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_QUANT", "int8")
    from tmr_tpu.utils.stage_bench import build_decoder_tail_step

    step_f, inp = build_decoder_tail_step(1, 8, 16, 1, 3, "float32",
                                          seed=7)
    (of, bf), _ = step_f(inp[0], jnp.zeros((), jnp.float32))
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    monkeypatch.setenv("TMR_QUANT_KERNEL", "int8dot")
    step_i, inp2 = build_decoder_tail_step(1, 8, 16, 1, 3, "float32",
                                           seed=7)
    (oi, bi), _ = step_i(inp2[0], jnp.zeros((), jnp.float32))
    scale = float(jnp.max(jnp.abs(of))) + 1e-9
    rel = float(jnp.max(jnp.abs(oi - of))) / scale
    assert 0 < rel < q.OUTPUT_TIER_REL


@pytest.mark.slow
def test_quant_storage_bitwise_production_geometry(monkeypatch):
    """The production pin: the jitted decoder-tail stage at the real
    128^2 x 1024 geometry (emb 512, fusion) — stored int8 tree bitwise
    the fake-quant program."""
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_QUANT", "int8")
    from tmr_tpu.utils.stage_bench import build_decoder_tail_step

    step_f, inp = build_decoder_tail_step(1, 128, 1024, 1, 3, "float32")
    (of, bf), _ = step_f(inp[0], jnp.zeros((), jnp.float32))
    monkeypatch.setenv("TMR_QUANT_STORAGE", "int8")
    step_s, inp2 = build_decoder_tail_step(1, 128, 1024, 1, 3, "float32")
    (os_, bs), _ = step_s(inp2[0], jnp.zeros((), jnp.float32))
    assert bool(jnp.array_equal(of, os_))
    assert bool(jnp.array_equal(bf, bs))
    # and the equality-tier gate itself admits the production geometry
    assert q.quant_storage_ok(128, 128, 1024, 1024, 1, 3)

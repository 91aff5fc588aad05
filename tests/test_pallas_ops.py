"""Pallas TPU kernels vs their pure-XLA oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmr_tpu.ops.nms import nms_keep_mask
from tmr_tpu.ops.pallas_nms import nms_keep_mask_pallas


def rand_boxes(n, seed, spread=1.0):
    rng = np.random.default_rng(seed)
    cx, cy = rng.uniform(0, spread, (2, n))
    w, h = rng.uniform(0.02, 0.3, (2, n))
    boxes = np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)
    scores = rng.uniform(0, 1, n)
    return jnp.asarray(boxes, jnp.float32), jnp.asarray(scores, jnp.float32)


@pytest.mark.parametrize("n,seed,thr", [(64, 0, 0.5), (128, 1, 0.3),
                                        (256, 2, 0.7), (128, 3, 0.15)])
@pytest.mark.slow
def test_pallas_nms_matches_xla(n, seed, thr):
    boxes, scores = rand_boxes(n, seed, spread=0.6)  # dense -> many overlaps
    want = nms_keep_mask(boxes, scores, thr)
    got = nms_keep_mask_pallas(boxes, scores, thr, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_pallas_nms_valid_mask():
    boxes, scores = rand_boxes(96, 4, spread=0.4)
    valid = jnp.asarray(np.random.default_rng(5).uniform(0, 1, 96) > 0.3)
    want = nms_keep_mask(boxes, scores, 0.5, valid=valid)
    got = nms_keep_mask_pallas(boxes, scores, 0.5, valid=valid,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.any(np.asarray(got) & ~np.asarray(valid))


def test_pallas_nms_identical_boxes():
    """Identical boxes + tied scores must keep exactly one."""
    boxes = jnp.tile(jnp.array([[0.1, 0.1, 0.3, 0.3]], jnp.float32), (8, 1))
    scores = jnp.full((8,), 0.7, jnp.float32)
    got = nms_keep_mask_pallas(boxes, scores, 0.5, interpret=True)
    assert int(np.asarray(got).sum()) == 1


def test_pallas_nms_all_invalid():
    boxes, scores = rand_boxes(32, 6)
    valid = jnp.zeros((32,), bool)
    got = nms_keep_mask_pallas(boxes, scores, 0.5, valid=valid,
                               interpret=True)
    assert int(np.asarray(got).sum()) == 0


@pytest.mark.slow
def test_batched_nms_backend_parity():
    """postprocess.batched_nms gives identical results on both backends
    (vmap over the pallas kernel included)."""
    from tmr_tpu.ops.postprocess import batched_nms

    B, N = 3, 64
    boxes = jnp.stack([rand_boxes(N, 10 + i, spread=0.5)[0] for i in range(B)])
    scores = jnp.stack([rand_boxes(N, 20 + i)[1] for i in range(B)])
    valid = scores > 0.2
    dets = {"boxes": boxes, "scores": jnp.where(valid, scores, 0.0),
            "refs": jnp.zeros((B, N, 2)), "valid": valid}
    out_x = batched_nms(dets, 0.4, backend="xla")
    out_p = batched_nms(dets, 0.4, backend="pallas")
    np.testing.assert_array_equal(np.asarray(out_p["valid"]),
                                  np.asarray(out_x["valid"]))
    np.testing.assert_allclose(np.asarray(out_p["scores"]),
                               np.asarray(out_x["scores"]))


@pytest.mark.parametrize("n", [150, 2000])
@pytest.mark.slow
def test_pallas_nms_non_lane_aligned(n):
    """N not a multiple of 128 (the eval default 2000 isn't either after
    padding semantics changed): the wrapper pads rows to a lane multiple with
    valid=0 and slices back; decisions must still match the XLA fixpoint."""
    boxes, scores = rand_boxes(n, 7, spread=0.5)
    valid = jnp.asarray(np.random.default_rng(8).uniform(0, 1, n) > 0.25)
    want = nms_keep_mask(boxes, scores, 0.5, valid=valid)
    got = nms_keep_mask_pallas(boxes, scores, 0.5, valid=valid,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_compiled_selfcheck_gates_auto_backend():
    """'auto' on TPU must route through pallas_nms_compiled_ok(); off-TPU it
    must never touch the compiled path. On a real TPU this test additionally
    exercises the compiled kernel itself."""
    from tmr_tpu.ops.pallas_nms import pallas_nms_compiled_ok

    if jax.default_backend() == "tpu":
        assert pallas_nms_compiled_ok(), (
            "compiled Pallas NMS disagrees with the XLA fixpoint on TPU"
        )
    else:
        # cheap sanity: the self-check is exception-safe and returns a bool
        assert pallas_nms_compiled_ok() in (True, False)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs a real TPU")
@pytest.mark.parametrize("n,seed,thr", [(256, 11, 0.5), (1100, 12, 0.3)])
def test_pallas_nms_compiled_matches_xla_on_tpu(n, seed, thr):
    boxes, scores = rand_boxes(n, seed, spread=0.5)
    want = nms_keep_mask(boxes, scores, thr)
    got = nms_keep_mask_pallas(boxes, scores, thr, interpret=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_nms_suppression_chain():
    """A chain a>b>c where a suppresses b and b would suppress c but is
    itself suppressed -> c survives (the sequential-greedy subtlety)."""
    boxes = jnp.array(
        [
            [0.00, 0.0, 0.40, 1.0],   # a (top score)
            [0.25, 0.0, 0.65, 1.0],   # b: IoU(a,b) = .15/.65 ~ .231 -> gone
            [0.50, 0.0, 0.90, 1.0],   # c: IoU(a,c) = 0; IoU(b,c) ~ .231
        ],                            #    but b is dead -> c survives
        jnp.float32,
    )
    scores = jnp.array([0.9, 0.8, 0.7], jnp.float32)
    got = np.asarray(nms_keep_mask_pallas(boxes, scores, 0.2,
                                          interpret=True))
    want = np.asarray(nms_keep_mask(boxes, scores, 0.2))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, [True, False, True])


# ---- pallas depthwise correlation (ops/pallas_xcorr.py) --------------------
@pytest.mark.slow
def test_pallas_xcorr_matches_conv_path():
    """The Pallas correlation kernel (interpret mode on CPU) must equal the
    HIGHEST-precision grouped-conv lowering on identical inputs, across
    channel counts that do and don't divide the channel block."""
    from jax import lax

    from tmr_tpu.ops.pallas_xcorr import xcorr_pallas

    rng = np.random.default_rng(3)
    for B, C, H, W, T in ((2, 8, 24, 20, 5), (1, 3, 16, 16, 7)):
        f = jnp.asarray(rng.standard_normal((B, C, H, W)), jnp.float32)
        t = jnp.asarray(rng.standard_normal((B, C, T, T)), jnp.float32)
        got = np.asarray(xcorr_pallas(f, t, interpret=True))
        want = np.asarray(
            lax.conv_general_dilated(
                f.reshape(1, B * C, H, W),
                t.reshape(B * C, 1, T, T),
                window_strides=(1, 1),
                padding=[(T // 2, T // 2), (T // 2, T // 2)],
                feature_group_count=B * C,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=lax.Precision.HIGHEST,
            ).reshape(B, C, H, W)
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pallas_xcorr_dispatch_falls_back_off_tpu(monkeypatch):
    """TMR_XCORR_IMPL=pallas off-TPU: the self-check refuses (no TPU), the
    dispatcher silently falls back to the conv path, results exact."""
    from tmr_tpu.ops import xcorr as xc

    rng = np.random.default_rng(4)
    B, C, H, W, cap = 2, 4, 20, 20, 9
    feat = rng.standard_normal((B, C, H, W)).astype(np.float32)
    tmpl = np.zeros((B, C, cap, cap), np.float32)
    tmpl[:, :, 2:7, 3:6] = rng.standard_normal((B, C, 5, 3))
    thw = jnp.array([[5, 3], [5, 3]], jnp.int32)

    monkeypatch.delenv("TMR_XCORR_IMPL", raising=False)
    want = np.asarray(
        xc.cross_correlation(jnp.array(feat), jnp.array(tmpl), thw)
    )
    monkeypatch.setenv("TMR_XCORR_IMPL", "pallas")
    got = np.asarray(
        xc.cross_correlation(jnp.array(feat), jnp.array(tmpl), thw)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pallas_xcorr_ok_gates(monkeypatch):
    from tmr_tpu.ops import pallas_xcorr as px

    # capacity beyond the unroll cap always refuses, even on TPU
    assert not px.pallas_xcorr_ok(8, 64, 64, px.MAX_UNROLL_T + 2)
    # force-disable env wins regardless of backend
    monkeypatch.setenv("TMR_NO_PALLAS_XCORR", "1")
    assert not px.pallas_xcorr_ok(8, 64, 64, 17)


def test_gate_refusal_records_structured_cause(monkeypatch):
    """Every kernel-gate refusal must leave a machine-readable cause in
    the diagnostics registry: category, exception class when one was
    swallowed, the gate's tile/geometry config, and the device kind —
    exercised end-to-end here via a FORCED refusal (the kill-switch) and
    the organic off-TPU backend refusal."""
    from tmr_tpu.diagnostics import drain_gate_refusals
    from tmr_tpu.ops import flash_attn, pallas_attn

    drain_gate_refusals()

    # forced refusal: the kill-switch env, fresh cache entry
    flash_attn.flash_attention_ok.cache_clear()
    monkeypatch.setenv("TMR_NO_FLASH_ATTN", "1")
    assert flash_attn.flash_attention_ok(16, 8, 8) is False
    recs = drain_gate_refusals()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["schema"] == "gate_probe/v1"
    assert rec["gate"] == "flash_attention_ok"
    assert rec["cause"] == "kill-switch"
    assert rec["config"]["gh"] == 16 and rec["config"]["head_dim"] == 8
    assert rec["device_kind"]  # resolved from the live backend
    monkeypatch.delenv("TMR_NO_FLASH_ATTN")

    # organic refusal off-TPU: the require_tpu gates record "backend",
    # with the geometry the verdict keys on in the cause record
    pallas_attn.packed_global_ok.cache_clear()
    assert pallas_attn.packed_global_ok(16, 16, 64, 2) is False
    recs = drain_gate_refusals()
    assert [r["cause"] for r in recs] == ["backend"]
    assert recs[0]["gate"] == "packed_global_ok"
    assert recs[0]["config"]["gh"] == 16
    assert recs[0]["config"]["H"] == 2

    # the xcorr gate follows the same schema (its own config vocabulary)
    from tmr_tpu.ops import pallas_xcorr as px

    monkeypatch.setenv("TMR_NO_PALLAS_XCORR", "1")
    assert px.pallas_xcorr_ok(8, 64, 64, 17) is False
    recs = drain_gate_refusals()
    assert recs and recs[-1]["gate"] == "pallas_xcorr_ok"
    assert recs[-1]["cause"] == "kill-switch"
    assert recs[-1]["config"] == {"C": 8, "H": 64, "W": 64, "T": 17}


def test_pallas_xcorr_big_bucket_falls_back_to_fft(monkeypatch):
    """TMR_XCORR_IMPL=pallas with a >threshold capacity must fall back to
    the FFT path (a direct conv at T in the 100s is the O(H^2 T^2 C)
    blowup FFT_CAPACITY_THRESHOLD exists to prevent), not the conv path."""
    from tmr_tpu.ops import xcorr as xc

    B, C, H, W, cap = 1, 2, 16, 16, 67
    assert cap > xc.FFT_CAPACITY_THRESHOLD
    feat = jnp.asarray(
        np.random.default_rng(0).standard_normal((B, C, H, W)), jnp.float32
    )
    tmpl = jnp.zeros((B, C, cap, cap), jnp.float32)
    tmpl = tmpl.at[:, :, cap // 2, cap // 2].set(1.0)
    thw = jnp.array([[1, 1]], jnp.int32)
    monkeypatch.setenv("TMR_XCORR_IMPL", "pallas")
    got = xc.cross_correlation(feat, tmpl, thw)
    # identity template through FFT: equal up to FFT rounding, and the
    # nonzero rounding proves the FFT path ran (a conv would be exact)
    np.testing.assert_allclose(np.asarray(got), np.asarray(feat), atol=1e-4)
    assert abs(np.asarray(got) - np.asarray(feat)).max() > 0


# ------------------------------------------- nms_topk padded-output tail
def _batched_rand(b, n, seed, spread=0.6):
    boxes = jnp.stack([rand_boxes(n, seed + i, spread)[0]
                       for i in range(b)])
    scores = jnp.stack([rand_boxes(n, seed + i, spread)[1]
                        for i in range(b)])
    return boxes, scores


def _topk_reference(boxes, scores, thr, valid, k):
    """Per-image numpy reference: XLA keep mask -> survivors sorted by
    (-score, slot) -> compacted into k padded slots."""
    from tmr_tpu.ops.pallas_nms import nms_topk  # noqa: F401  (under test)

    out = {"count": [], "boxes": [], "scores": [], "index": []}
    for i in range(scores.shape[0]):
        keep = np.asarray(nms_keep_mask(boxes[i], scores[i], thr,
                                        valid=valid[i]))
        idx = np.nonzero(keep)[0]
        idx = idx[np.lexsort((idx, -np.asarray(scores[i])[idx]))][:k]
        n = len(idx)
        bx = np.zeros((k, 4), np.float32)
        sc = np.zeros((k,), np.float32)
        ix = np.full((k,), -1, np.int64)
        bx[:n] = np.asarray(boxes[i])[idx]
        sc[:n] = np.asarray(scores[i])[idx]
        ix[:n] = idx
        out["count"].append(n)
        out["boxes"].append(bx)
        out["scores"].append(sc)
        out["index"].append(ix)
    return {k_: np.stack(v) for k_, v in out.items()}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("k", [4, 16, 64])
def test_nms_topk_matches_reference(backend, k):
    """Batched/padded semantics against the per-image float32 reference,
    on both backends (pallas in interpret mode — the satellite's
    interpret-parity requirement)."""
    from tmr_tpu.ops.pallas_nms import nms_topk

    boxes, scores = _batched_rand(3, 64, seed=10)
    valid = jnp.ones(scores.shape, bool)
    got = nms_topk(boxes, scores, 0.4, valid=valid, k=k,
                   backend=backend, interpret=True)
    want = _topk_reference(boxes, scores, 0.4, valid, k)
    np.testing.assert_array_equal(np.asarray(got["count"]), want["count"])
    np.testing.assert_array_equal(np.asarray(got["index"]), want["index"])
    np.testing.assert_array_equal(np.asarray(got["boxes"]), want["boxes"])
    np.testing.assert_array_equal(np.asarray(got["scores"]),
                                  want["scores"])


def test_nms_topk_degenerate_boxes():
    """Zero-area and inverted boxes must not poison the IoU math: they
    survive as their own detections (IoU 0 against everything) and the
    output stays finite."""
    from tmr_tpu.ops.pallas_nms import nms_topk

    boxes = jnp.asarray([[[0.1, 0.1, 0.1, 0.1],     # zero-area point
                          [0.5, 0.5, 0.4, 0.4],     # inverted
                          [0.2, 0.2, 0.4, 0.4]]], jnp.float32)
    scores = jnp.asarray([[0.9, 0.8, 0.7]], jnp.float32)
    out = nms_topk(boxes, scores, 0.5, backend="xla")
    assert int(out["count"][0]) == 3
    assert np.isfinite(np.asarray(out["boxes"])).all()
    np.testing.assert_array_equal(np.asarray(out["index"][0]), [0, 1, 2])


def test_nms_topk_all_suppressed_to_one():
    """N copies of one box: exactly the top scorer survives; the other
    slots are zeroed with index -1."""
    from tmr_tpu.ops.pallas_nms import nms_topk

    boxes = jnp.tile(jnp.asarray([[[0.2, 0.2, 0.6, 0.6]]], jnp.float32),
                     (1, 8, 1))
    scores = jnp.asarray([[0.1, 0.3, 0.95, 0.2, 0.5, 0.4, 0.6, 0.7]],
                         jnp.float32)
    out = nms_topk(boxes, scores, 0.5, backend="xla", k=8)
    assert int(out["count"][0]) == 1
    assert int(out["index"][0][0]) == 2
    assert float(out["scores"][0][0]) == pytest.approx(0.95)
    assert (np.asarray(out["index"][0][1:]) == -1).all()
    assert (np.asarray(out["scores"][0][1:]) == 0).all()
    assert (np.asarray(out["boxes"][0][1:]) == 0).all()


def test_nms_topk_k_beyond_valid_count_pads():
    """k larger than the input (and than the survivor count) pads: count
    reports the real survivors, slots past it are zero/-1."""
    from tmr_tpu.ops.pallas_nms import nms_topk

    boxes, scores = _batched_rand(1, 6, seed=20, spread=4.0)  # sparse
    valid = jnp.asarray([[True, True, True, False, False, False]])
    out = nms_topk(boxes, scores, 0.5, valid=valid, k=10, backend="xla")
    n = int(out["count"][0])
    assert n <= 3
    assert out["boxes"].shape == (1, 10, 4)
    assert (np.asarray(out["index"][0][n:]) == -1).all()
    assert (np.asarray(out["scores"][0][n:]) == 0).all()
    # the surviving prefix is score-descending
    sc = np.asarray(out["scores"][0][:n])
    assert (np.diff(sc) <= 0).all()


def test_nms_topk_empty_valid():
    from tmr_tpu.ops.pallas_nms import nms_topk

    boxes, scores = _batched_rand(2, 8, seed=30)
    valid = jnp.zeros(scores.shape, bool)
    out = nms_topk(boxes, scores, 0.5, valid=valid, backend="xla")
    assert (np.asarray(out["count"]) == 0).all()
    assert (np.asarray(out["index"]) == -1).all()
    assert (np.asarray(out["boxes"]) == 0).all()

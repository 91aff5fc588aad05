"""The Xing4.0 trunk as the detector's backbone (``models/lm_trunk.py`` with
a low-rank query, YaRN rotary and hyper-connection streams; ``ops/rope.py``,
``ops/hyper_conn.py``) against its plain reference
(``benchmarks/reference_xing_trunk.py``), at tiny widths on the CPU: hidden
64, 2 heads of 16 + 8 / 16, query rank 24, 4 streams, 8 experts all held, 2
a token, the stage's 6 layers (one dense, five with experts), 64 px images.
"""

import math

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_xing_trunk as ref
from tmr_tpu import obs
from tmr_tpu.models.lm_trunk import (TRUNK_CONFIGS, GatedMLP, MLAMixer,
                                     RMSNorm, TrunkLayer, build_lm_trunk)
from tmr_tpu.ops import hyper_conn, rope

TINY = "xing4_tiny"
SIZE = 64
ROPE = dict(TRUNK_CONFIGS["xing4_a4b_stage6"]["rope"])
#: bfloat16 against float32, of the maps' range; the readings are in its test
BF16_TOLERANCE = 0.02

# the stage's pattern at the tests' widths, under a name of the tests' own
TRUNK_CONFIGS[TINY] = dict(
    hidden=64, layers=(("mla", "dense"),) + (("mla", "moe"),) * 5,
    num_heads=2, qk_nope_dim=16, qk_pe_dim=8, v_dim=16, kv_rank=24,
    q_rank=24, dense_width=96, expert_width=32, num_experts=8,
    experts_held=8, top_k=2, routed_scale=2.0, norm_eps=1e-6, rope=ROPE,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0))


# ------------------------------------------------------ hyper-connections
def test_sinkhorn_gives_rows_and_columns_that_sum_to_one():
    """Logits as the cell's (order 1 about twice the identity), 4,096
    tokens. The last half-sweep normalises the columns: 1 to float32
    rounding for every token. The rows after the published 20 sweeps: within
    1e-4 for the median token (read 1.5e-6); the slowest tokens are still a
    per cent or two off (read 0.017, limit 0.05), which is the published
    arithmetic and not a fault; 300 sweeps bring every row within 1e-4."""
    logits = jax.random.normal(jax.random.key(0), (4, 4, 4096)) \
        + 2.0 * jnp.eye(4)[:, :, None]
    m = np.asarray(hyper_conn.sinkhorn(logits, 20, 1e-6))
    assert (m > 0).all()
    np.testing.assert_allclose(m.sum(0), 1.0, atol=1e-5)
    off = np.abs(m.sum(1) - 1.0).max(0)  # a token's worst row
    assert np.median(off) < 1e-4 and off.max() < 0.05
    one = np.asarray(hyper_conn.sinkhorn(logits, 1, 1e-6))
    assert np.median(np.abs(one.sum(1) - 1.0).max(0)) > 0.05
    many = np.asarray(hyper_conn.sinkhorn(logits, 300, 1e-6))
    np.testing.assert_allclose(many.sum(1), 1.0, atol=1e-4)


def _hc_params(n, c, seed=0, bias=100.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return dict(
        phi=jax.random.normal(ks[0], (n * c, 2 * n + n * n)) * (n * c) ** -0.5,
        alpha=jnp.asarray([1.0, 0.7, 1.3]),
        b_pre=0.3 * jax.random.normal(ks[1], (n,)),
        b_post=0.3 * jax.random.normal(ks[2], (n,)),
        # one entry far over the clamp, one far under it
        b_res=(2.0 * jnp.eye(n) + 0.3 * jax.random.normal(ks[3], (n, n))
               ).at[0, 1].set(bias).at[2, 0].set(-bias))


def _loop_over_tokens(x, p, f, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                      norm_eps=1e-6):
    """numpy, float64, one token at a time: x (n, T, C) -> (n, T, C)."""
    x = np.asarray(x, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    n, t, c = x.shape
    out = np.empty_like(x)
    for tok in range(t):
        streams = x[:, tok]
        v = streams.reshape(-1)
        v = v / math.sqrt((v * v).mean() + norm_eps)
        h = v @ p["phi"]
        sig = lambda z: 1.0 / (1.0 + np.exp(-z))
        h_pre = sig(p["alpha"][0] * h[:n] + p["b_pre"])
        h_post = 2.0 * sig(p["alpha"][1] * h[n:2 * n] + p["b_post"])
        m = np.exp(np.clip(p["alpha"][2] * h[2 * n:].reshape(n, n)
                           + p["b_res"], *clamp))
        for _ in range(iters):
            m = m / (m.sum(1, keepdims=True) + eps)
            m = m / (m.sum(0, keepdims=True) + eps)
        y = f(h_pre @ streams)
        out[:, tok] = m @ streams + h_post[:, None] * y[None, :]
    return out


def test_a_hyper_connected_sub_layer_equals_a_loop_over_tokens():
    """Against float64 numpy one token at a time, to 2e-5 of values of
    order 1 (float32 sums over 64 products). Two biases lie far beyond the
    clamp: with it the float32 result is finite and the loop's; without it
    exp(100) overflows float32 and the sweeps divide infinities."""
    n, c = 4, 16
    x = jax.random.normal(jax.random.key(1), (n, 2, 5, c))
    p = _hc_params(n, c)
    f = lambda h: np.tanh(h) * 1.5
    h_pre, h_post, h_res = hyper_conn.coefficients(
        x, p["phi"], p["alpha"], p["b_pre"], p["b_post"], p["b_res"], 20,
        1e-6, (-30.0, 30.0), 1e-6)
    assert h_pre.shape == (n, 2, 5) and h_res.shape == (n, n, 2, 5)
    assert h_pre.dtype == h_res.dtype == jnp.float32
    y = jnp.tanh(hyper_conn.pre_mix(x, h_pre, jnp.float32)) * 1.5
    got = hyper_conn.post_mix(x, y, h_post, h_res)
    want = _loop_over_tokens(x.reshape(n, 10, c), p, f).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    unclamped = hyper_conn.coefficients(
        x, p["phi"], p["alpha"], p["b_pre"], p["b_post"], p["b_res"], 20,
        1e-6, (-1e9, 1e9), 1e-6)[2]
    assert not np.isfinite(np.asarray(unclamped)).all()


def test_one_stream_is_not_the_plain_add():
    """n = 1: H_res is 1 after a sweep, but H_pre and H_post still weigh."""
    x = jax.random.normal(jax.random.key(2), (1, 6, 16))
    p = _hc_params(1, 16, bias=0.0)
    h_pre, h_post, h_res = hyper_conn.coefficients(
        x, p["phi"], p["alpha"], p["b_pre"], p["b_post"], p["b_res"], 20,
        1e-6, (-30.0, 30.0), 1e-6)
    np.testing.assert_allclose(h_res, 1.0, atol=1e-5)
    assert np.abs(np.asarray(h_pre) - 1.0).min() > 0.05
    assert np.abs(np.asarray(h_post) - 1.0).max() > 0.05


def _layer(hc_mult):
    z = dict(TRUNK_CONFIGS[TINY], hc_mult=hc_mult, kda_head_dim=None,
             conv_size=None, expert_offset=0)
    return TrunkLayer("mla", "dense", z, jnp.float32), z


def test_without_streams_the_layer_is_the_plain_pre_norm_add_bit_for_bit():
    """``hc_mult=0``: ``x + attn(norm1(x))`` then ``x + ffn(norm2(x))`` of
    the layer's own sub-modules, equal to the last bit, and no
    hyper-connection leaf."""
    layer, z = _layer(0)
    x = jax.random.normal(jax.random.key(3), (2, 16, 64))
    params = layer.init(jax.random.key(4), x)["params"]
    assert set(params) == {"attn", "ffn", "norm1", "norm2"}
    got = layer.apply({"params": params}, x)
    norm = lambda name, t: RMSNorm(z["norm_eps"]).apply(
        {"params": params[name]}, t)
    attn = MLAMixer(z["num_heads"], z["qk_nope_dim"], z["qk_pe_dim"],
                    z["v_dim"], z["kv_rank"], z["q_rank"], z["rope"],
                    z["norm_eps"])
    x1 = x + attn.apply({"params": params["attn"]}, norm("norm1", x))
    want = x1 + GatedMLP(z["dense_width"]).apply(
        {"params": params["ffn"]}, norm("norm2", x1))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    streams, _ = _layer(4)
    assert {"hc_attn", "hc_ffn"} <= set(streams.init(
        jax.random.key(4), jnp.broadcast_to(x, (4,) + x.shape))["params"])


# ----------------------------------------------------------------- rotary
def test_yarn_inv_freq_equals_its_closed_form():
    """At the published numbers: d 64, theta 10000, factor 64, window 4096,
    beta 32 / 1: the ramp runs from pair 10 to pair 23."""
    got = rope.yarn_inv_freq(64, 10000, 64, 4096, 32, 1)
    i = np.arange(32)
    f = 10000.0 ** (-2.0 * i / 64)
    r = lambda beta: 64 * math.log(4096 / (2 * math.pi * beta)) / (
        2 * math.log(10000))
    assert (math.floor(r(32)), math.ceil(r(1))) == (10, 23)
    ramp = np.clip((i - 10) / 13.0, 0.0, 1.0)
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 64 * ramp, rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        got, ref.yarn_inv_freq(ROPE, 64, ROPE["theta"]), rtol=1e-6)
    assert abs(rope.yarn_mscale(64, 1) - 1.4159) < 1e-4


def test_at_factor_one_rope_scores_depend_on_the_distance_alone():
    """q . k after the rotation is a function of the difference of the two
    positions: equal at (3, 10) and (40, 47) to float32 rounding of sums of
    8 products of order 1 (1e-4), and not equal at another distance."""
    inv_freq = rope.yarn_inv_freq(16, 10000, 1, 4096, 32, 1)
    np.testing.assert_allclose(
        inv_freq, 10000.0 ** (-2.0 * np.arange(8) / 16), rtol=1e-6)
    q, k = jax.random.normal(jax.random.key(5), (2, 1, 1, 16))
    at = lambda t, pos: rope.rotate(jnp.broadcast_to(t, (1, 64, 16)),
                                    jnp.arange(64), inv_freq)[0, pos]
    score = lambda a, b: float(at(q, a) @ at(k, b))
    assert abs(score(10, 3) - score(47, 40)) < 1e-4
    assert abs(score(10, 3) - score(10, 4)) > 1e-3
    np.testing.assert_allclose(at(q, 0), q[0, 0], atol=1e-7)  # position 0


def test_mla_with_a_low_rank_query_and_rotary_equals_a_masked_softmax():
    """``MLAMixer`` against the reference's masked softmax a head with the
    rotation as complex numbers: 2e-5 of outputs of order 1 (float32)."""
    z = TRUNK_CONFIGS[TINY]
    mixer = MLAMixer(z["num_heads"], z["qk_nope_dim"], z["qk_pe_dim"],
                     z["v_dim"], z["kv_rank"], z["q_rank"], z["rope"],
                     z["norm_eps"], param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(6), (2, 48, 64))
    params = mixer.init(jax.random.key(7), x)["params"]
    assert {"q_a", "q_a_norm", "q_b"} <= set(params)
    assert "q_proj" not in params
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    got = mixer.apply({"params": params}, x)
    as_pairs = tuple(sorted(ROPE.items()))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want = ref._mla(x[b], flat, z["num_heads"], z["qk_nope_dim"],
                            z["v_dim"], z["norm_eps"], as_pairs, None)
            np.testing.assert_allclose(got[b], want, atol=2e-5)
    # the rotation matters: without it the outputs differ
    nope = MLAMixer(z["num_heads"], z["qk_nope_dim"], z["qk_pe_dim"],
                    z["v_dim"], z["kv_rank"], z["q_rank"], None,
                    z["norm_eps"], param_dtype=jnp.float32)
    assert np.abs(np.asarray(nope.apply({"params": params}, x) - got)
                  ).max() > 1e-3


# ------------------------------------------------------ the whole detector
def _tiny_weights(seed=0, compute="float32"):
    """A Predictor on the tiny trunk with seeded weights that leave no leaf
    at its initial constant (the alphas of order 1, so that the
    token-dependent part of every coefficient shows), and the same weights
    as the reference's flat dict."""
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    pred = Predictor(preset("TMR_FSCD147", backbone=TINY, image_size=SIZE,
                            emb_dim=32, compute_dtype=compute))
    params = pred.init_params(seed, image_size=SIZE)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    key = jax.random.key(seed + 1)
    for i, (path, leaf) in enumerate(sorted(flat.items())):
        if path.endswith(("bias", "weight", "b_pre", "b_post", "b_res")):
            noise = 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                            leaf.shape)
            flat[path] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
        if path.endswith("alpha"):
            flat[path] = jnp.asarray([1.0, 0.8, 1.2], leaf.dtype)
        if path.endswith("b_res"):  # from 4 I to 2 I: H_res well mixed
            flat[path] = (flat[path].astype(jnp.float32)
                          - 2.0 * jnp.eye(4)).astype(leaf.dtype)
    flat["objectness_head_0/conv/bias"] = jnp.full((1,), 0.3)
    pred.params = flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})
    return pred, flat


def _model_dict():
    z = TRUNK_CONFIGS[TINY]
    return dict(
        patch_size=16, feature_upsample=True, fusion=True,
        decoder_num_layer=1, layers=[list(l) for l in z["layers"]],
        num_heads=z["num_heads"], qk_nope_head_dim=z["qk_nope_dim"],
        v_head_dim=z["v_dim"], num_experts_per_token=z["top_k"],
        routed_scaling_factor=z["routed_scale"], expert_offset=0,
        rms_norm_eps=z["norm_eps"], hc_mult=z["hc_mult"],
        hc_sinkhorn_iters=z["hc_sinkhorn_iters"], hc_eps=z["hc_eps"],
        mhc_h_res_clamp_min=z["hc_clamp"][0],
        mhc_h_res_clamp_max=z["hc_clamp"][1],
        rope_scaling={k: v for k, v in ROPE.items() if k != "theta"},
        rope_theta=ROPE["theta"])


def _inputs(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((rows, SIZE, SIZE, 3)).astype(np.float32)
    exemplars = np.asarray([[[0.2, 0.2, 0.5, 0.5]], [[0.1, 0.3, 0.4, 0.6]]],
                           np.float32)[:rows]
    return images, exemplars


@pytest.fixture(scope="module")
def tiny_f32():
    return _tiny_weights()


def _gaps(pred, flat, quant=None, routing=None):
    """Widest |program - reference| of the objectness logits and the
    regressions over two images, of the reference's range; ``quant`` puts
    the control in the program's place."""
    images, exemplars = _inputs()
    out = pred.model.apply({"params": pred.params}, jnp.asarray(images),
                           jnp.asarray(exemplars))
    gaps = []
    for b in range(len(images)):
        obj, reg = ref.forward_dense(flat, images[b], exemplars[b, 0],
                                     _model_dict(), routing=routing)
        if quant:
            got_obj, got_reg = ref.forward_dense(
                flat, images[b], exemplars[b, 0], _model_dict(), quant=quant)
        else:
            got_obj = np.asarray(out["objectness"][0][b])
            got_reg = np.asarray(out["regressions"][0][b])
        gaps.append((np.abs(got_obj - obj).max() / np.abs(obj).max(),
                     np.abs(got_reg - reg).max() / np.abs(reg).max()))
    return np.max(gaps, axis=0)


def test_detector_in_float32_equals_the_reference_tightly(tiny_f32):
    """1e-4 of the maps' range: float32 on both sides, other orders of
    summation (read 1.4e-7)."""
    pred, flat = tiny_f32
    assert max(_gaps(pred, flat)) < 1e-4


def test_detector_in_bfloat16_is_within_a_tolerance_the_fp8_control_breaks(
        tiny_f32):
    """bfloat16 compute on the same leaves against the float32 reference,
    and the reference at fp8, the nearest precision below, in the
    program's place: the tolerance, 0.02 of the maps' range, lies between
    the two readings (0.0063 and 0.052 on these weights; on two other seeds
    a routing tie that bfloat16 breaks otherwise reads up to 0.030, which
    is the benchmark cell's to judge, where the reference follows ties)."""
    _, flat = tiny_f32
    pred16, _ = _tiny_weights(compute="bfloat16")
    bf16, fp8 = max(_gaps(pred16, flat)), max(_gaps(pred16, flat, "fp8"))
    assert bf16 < BF16_TOLERANCE, bf16
    assert fp8 > 2 * BF16_TOLERANCE and fp8 > 2 * bf16, (bf16, fp8)


def test_through_predictor_call_with_counters_and_compile_span(tiny_f32):
    from tmr_tpu.inference import ROUTING_TABLE_KEY, detections_to_numpy

    pred, flat = tiny_f32
    pred.invalidate_compiled()
    obs.clear()
    images, exemplars = _inputs()
    before = obs.get_registry().counters("trunk.")
    dets = pred(images, exemplars)
    served = detections_to_numpy(dets)
    assert len(served) == 2 and all(len(s["scores"]) for s in served)
    after = obs.get_registry().counters("trunk.")
    gained = {k: after[k] - before.get(k, 0) for k in after}
    assert gained["hc.xla"] == 12  # two sub-layers a layer
    assert gained["mla.blocked_xla_rope"] == 6
    assert gained["moe.tokens"] == 2 * 16 * 5  # images, tokens, layers
    assert gained["moe.pairs_here"] == 2 * 16 * 5 * 2  # every expert held
    # in float32 the program chooses the experts the reference chooses
    routing = []
    _gaps(pred, flat, routing=routing)
    table = np.asarray(dets[ROUTING_TABLE_KEY])
    assert table.shape == (5, 2 * 16, 2)
    mine = np.sort(table.reshape(5, 2, 16, 2), -1)
    for b in range(2):
        for layer in range(5):
            own = np.sort(routing[5 * b + layer]["own"], -1)
            assert (mine[layer, b] == own).all(), (b, layer)
    attrs = [r for r in obs.spans() if r["name"] == "compile"][-1]["attrs"]
    assert attrs["trunk_hc"] == "xla x12"
    assert attrs["trunk_mla"] == "blocked_xla_rope x6"
    assert attrs["trunk_moe"] == "ragged_dot x5"  # gmm on a TPU in bfloat16
    assert attrs["trunk_pairs"] == "xla_gather x5"  # row_dma there
    assert attrs["experts_held"] == 8
    assert "trunk_kda" not in attrs


def test_scopes_name_what_the_new_metrics_match(tiny_f32):
    import re

    pred, _ = tiny_f32
    images, exemplars = _inputs()
    text = jax.jit(pred.model.apply).lower(
        {"params": pred.params}, jnp.asarray(images),
        jnp.asarray(exemplars)).as_text(debug_info=True)
    for scope in ("backbone/layers_0/hc_attn/coeff/",
                  "backbone/layers_0/hc_attn/mix/",
                  "backbone/layers_5/hc_ffn/coeff/",
                  "backbone/layers_5/hc_ffn/mix/",
                  "backbone/layers_0/attn/rope/",
                  "backbone/layers_3/attn/softmax/",
                  "backbone/layers_0/attn/q_a/",
                  "backbone/layers_1/ffn/router/",
                  "backbone/layers_1/ffn/dispatch/",
                  "backbone/layers_1/ffn/experts/",
                  "backbone/layers_1/ffn/shared/"):
        assert scope in text, scope
    # the sub-layers keep their own scopes beside the hyper-connection's:
    # trunk.mla.ms and trunk.ffn.ms match what they matched
    assert not re.search(r"hc_(attn|ffn)/[a-z_/]*(softmax|experts)/", text)


def test_the_registry_names_the_stage_and_a_trunk_needs_no_kda_size():
    """``build_backbone`` finds the published stage by name; its sizes are
    the configuration file's; a trunk with no ``kda`` layer states neither
    ``kda_head_dim`` nor ``conv_size``."""
    import json
    import os

    from benchmarks.drivers.offline_predict_xing_trunk import _trunk_sizes

    z = TRUNK_CONFIGS["xing4_a4b_stage6"]
    assert "kda_head_dim" not in z and "conv_size" not in z
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "xing4_fscd147.json")) as f:
        assert _trunk_sizes(json.load(f)["model"]) == z
    shapes = jax.eval_shape(build_lm_trunk("xing4_a4b_stage6").init,
                            jax.random.key(0), jnp.zeros((1, 64, 64, 3)))
    layers = shapes["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    # the issue's arithmetic: 128.2 M the dense layer, 745.0 M an expert one
    assert round(count(layers["layers_0"]) / 1e6, 1) == 128.2
    assert round(count(layers["layers_1"]) / 1e6, 1) == 745.0
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(layers["layers_1"]))

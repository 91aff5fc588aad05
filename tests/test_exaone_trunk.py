"""The K-EXAONE trunk as the detector's backbone (``models/lm_trunk.py`` with
q/k-normed grouped-query attention, three layers under a causal sliding
window with rotary to one full layer without, a norm after each sub-layer
and none ahead of it, sigmoid-routed experts with a selection bias and a
shared expert) against its plain reference
(``benchmarks/reference_exaone_trunk.py``), at tiny widths on the CPU:
hidden 64, 8 query heads on 1 key-value head of 16 (groups of 8, as the
published 64 on 8), a window of 8 patches, 16 experts of which 2 are held
(the cell's share of 8), 4 a token, the cell's five layers (dense, then
sliding, sliding, full, sliding with experts), 128 px images (64 patches).
"""

import re

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_exaone_trunk as ref
from benchmarks.reference_lm_trunk import _moe_ffn
from tmr_tpu import obs
from tmr_tpu.models.lm_trunk import (TRUNK_CONFIGS, GatedMLP, GQAMixer,
                                     MoEFFN, RMSNorm, build_lm_trunk)

TINY = "k_exaone_tiny_tests"
SIZE = 128
LAYERS = (("swa", "dense"), ("swa", "moe"), ("swa", "moe"), ("gqa", "moe"),
          ("swa", "moe"))

# the published pattern at the tests' widths, under a name of the tests' own
TRUNK_CONFIGS[TINY] = dict(
    hidden=64, layers=LAYERS, num_heads=8, kv_heads=1, head_dim=16,
    attn_scale=0.25, qk_norm=True, sliding_window=8,
    sliding_rope_theta=100.0, post_norms=True, dense_width=96,
    expert_width=32, num_experts=16, experts_held=2, expert_offset=0,
    top_k=4, routed_scale=2.5)


def _flat(params) -> dict:
    return {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}


def _tree(flat: dict) -> dict:
    return flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})


def _normed_weights(flat: dict, seed: int) -> dict:
    """q/k norms about 1.75, as the cell draws them."""
    for i, name in enumerate(sorted(n for n in flat
                                    if n.endswith(("q_norm/weight",
                                                   "k_norm/weight")))):
        flat[name] = 1.75 + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), flat[name].shape)
    return flat


# ---------------------------------------------------------- attention
def test_the_band_is_the_published_sliding_mask():
    mask = np.asarray(ref.band(12, 4))
    want = np.array([[i - 4 < j <= i for j in range(12)] for i in range(12)])
    np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(np.asarray(ref.band(6, None)),
                                  np.tril(np.ones((6, 6), bool)))
    np.testing.assert_array_equal(np.asarray(ref.band(6, 6)),
                                  np.tril(np.ones((6, 6), bool)))


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_the_mixer_equals_the_references_masked_softmax(kind):
    """8 query heads on 1 key-value head of 16, q/k norms; sliding: a window
    of 8 and rotary; full: every earlier key and no rotary (NoPE). 40
    tokens: the mixer against the reference's masked softmax a head. A
    window ignored, a window one wider, and the rotary on the full layer
    each give another answer."""
    sliding = kind == "sliding"
    window, theta = (8, 100.0) if sliding else (None, None)
    mixer = GQAMixer(8, 1, 16, 0.25, qk_norm=True, rope_theta=theta,
                     window=window, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 40, 64))
    flat = _normed_weights(_flat(mixer.init(jax.random.key(2), x)["params"]),
                           3)
    tree = _tree(flat)
    got = mixer.apply({"params": tree}, x)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            np.testing.assert_allclose(
                got[b], ref._attention(x[b], flat, 8, 1, theta, window, 1e-5,
                                       None), atol=2e-5)
    others = ([dict(window=None), dict(window=9)] if sliding
              else [dict(rope_theta=100.0)])
    for change in others:
        other = mixer.clone(**change).apply({"params": tree}, x)
        assert float(jnp.abs(other - got).max()) > 1e-2, change


# -------------------------------------------------------------- the layer
def test_eight_shares_add_up_to_the_uncut_reference_layer():
    """A sliding layer with experts, 16 of them held 2 a share as the eight
    chips that share a layer hold them: the attention and its post-norm
    computed once, each share's expert layer gives the shared expert plus
    its own experts' part, and the eight routed parts with the shared
    expert counted once, post-normed and added, are what the reference
    gives for the layer with all 16 held (2e-5 of outputs of order 1). One
    share alone is not the layer."""
    z = TRUNK_CONFIGS[TINY]
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))
    attn = GQAMixer(8, 1, 16, 0.25, qk_norm=True, rope_theta=100.0, window=8,
                    param_dtype=jnp.float32)
    a_params = _normed_weights(
        _flat(attn.init(jax.random.key(1), x)["params"]), 2)
    whole = MoEFFN(16, 16, 0, 4, 2.5, 32, param_dtype=jnp.float32)
    params = whole.init(jax.random.key(3), x)["params"]
    assert set(params) == {"router", "experts", "shared"}
    norm = RMSNorm(1e-5, jnp.float32)
    post = {"weight": 1.0 + 0.1 * jax.random.normal(jax.random.key(4),
                                                    (64,))}

    x1 = x + norm.apply({"params": post}, attn.apply(
        {"params": _tree(a_params)}, x))
    shared = GatedMLP(32, param_dtype=jnp.float32).apply(
        {"params": params["shared"]}, x1)
    parts = []
    for offset in range(0, 16, 2):
        share = MoEFFN(16, 2, offset, 4, 2.5, 32, param_dtype=jnp.float32)
        cut = dict(params, experts={
            name: leaf[offset:offset + 2]
            for name, leaf in params["experts"].items()})
        parts.append(np.asarray(share.apply({"params": cut}, x1) - shared))
    got = x1 + norm.apply({"params": post}, shared + sum(parts))

    flat = {f"layers_0/attn/{k}": v for k, v in a_params.items()}
    flat.update({f"layers_0/ffn/{k}": v for k, v in _flat(params).items()})
    flat["layers_0/post_attn_norm/weight"] = post["weight"]
    flat["layers_0/post_ffn_norm/weight"] = post["weight"]
    flat["final_norm/weight"] = jnp.ones((64,))
    model = dict(layers=[["swa", "moe"]], num_heads=8, num_key_value_heads=1,
                 rope_theta=100.0, sliding_window=8, rms_norm_eps=1e-5,
                 num_experts_per_token=4, routed_scaling_factor=2.5,
                 expert_offset=0)
    final = lambda t: t * jax.lax.rsqrt(
        jnp.mean(t * t, -1, keepdims=True) + 1e-5)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.trunk(flat, x[b], model))
                         for b in range(2)])
    np.testing.assert_allclose(final(got), want, atol=2e-5)
    one = x1 + norm.apply({"params": post}, shared + parts[0])
    assert np.abs(np.asarray(final(one)) - want).max() > 1e-2
    assert z["routed_scale"] == 2.5


def test_the_router_is_the_sigmoid_one_with_bias_scale_and_shared_expert():
    """The expert layer's leaves are a sigmoid router's with a selection
    bias, the held experts' and one shared expert of an expert's width; it
    equals the reference's at the published scale of 2.5, which a scale of
    1 does not."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))
    layer = MoEFFN(16, 16, 0, 4, 2.5, 32, param_dtype=jnp.float32)
    params = layer.init(jax.random.key(1), x)["params"]
    assert set(params["router"]) == {"kernel", "bias"}
    assert params["shared"]["gate"]["kernel"].shape == (64, 32)
    flat = _flat(params)
    got = layer.apply({"params": params}, x)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(_moe_ffn(x[b], flat, 4, 0, 2.5, None)[0])
                         for b in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-5)
    unscaled = layer.clone(scale=1.0).apply({"params": params}, x)
    assert float(jnp.abs(unscaled - got).max()) > 1e-2


# ------------------------------------------------------ the whole detector
def _tiny_weights(seed=0):
    """A Predictor on the tiny trunk with seeded weights that leave no leaf
    at its initial constant (q/k norms near 1.75, as the cell draws them),
    and the same weights as the reference's flat dict."""
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    pred = Predictor(preset("TMR_FSCD147", backbone=TINY, image_size=SIZE,
                            emb_dim=32, compute_dtype="float32"))
    flat = _flat(pred.init_params(seed, image_size=SIZE))
    key = jax.random.key(seed + 1)
    for i, (path, leaf) in enumerate(sorted(flat.items())):
        if path.endswith(("bias", "weight")):
            mean = 1.75 if path.endswith(("q_norm/weight",
                                          "k_norm/weight")) else 0.0
            noise = mean + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)
            flat[path] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
    flat["objectness_head_0/conv/bias"] = jnp.full((1,), 0.3)
    # the benchmark's gain on the objectness head: else the map is its bias
    # to two digits
    flat["objectness_head_0/conv/kernel"] *= 9.0
    pred.params = _tree(flat)
    return pred, flat


def _model_dict(**change):
    z = TRUNK_CONFIGS[TINY]
    return dict(
        patch_size=16, feature_upsample=True, fusion=True,
        decoder_num_layer=1, layers=[list(l) for l in z["layers"]],
        num_heads=z["num_heads"], num_key_value_heads=z["kv_heads"],
        rope_theta=z["sliding_rope_theta"],
        sliding_window=z["sliding_window"],
        num_experts_per_token=z["top_k"], expert_offset=0,
        routed_scaling_factor=z["routed_scale"], rms_norm_eps=1e-5,
        **change)


def _inputs(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((rows, SIZE, SIZE, 3)).astype(np.float32)
    exemplars = np.asarray([[[0.2, 0.2, 0.5, 0.5]], [[0.1, 0.3, 0.4, 0.6]]],
                           np.float32)[:rows]
    return images, exemplars


@pytest.fixture(scope="module")
def tiny_f32():
    return _tiny_weights()


def _gaps(pred, flat, routing=None, model=None):
    """Widest |program - reference| of the objectness logits and the
    regressions over two images, of the reference's range."""
    images, exemplars = _inputs()
    out = pred.model.apply({"params": pred.params}, jnp.asarray(images),
                           jnp.asarray(exemplars))
    gaps = []
    for b in range(len(images)):
        obj, reg = ref.forward_dense(flat, images[b], exemplars[b, 0],
                                     model or _model_dict(), routing=routing)
        got_obj = np.asarray(out["objectness"][0][b])
        got_reg = np.asarray(out["regressions"][0][b])
        gaps.append((np.abs(got_obj - obj).max() / np.abs(obj).max(),
                     np.abs(got_reg - reg).max() / np.abs(reg).max()))
    return np.max(gaps, axis=0)


def test_detector_in_float32_equals_the_reference_tightly(tiny_f32):
    """1e-4 of the maps' range: float32 on both sides, other orders of
    summation; the reference with a window one wider is another
    detector."""
    pred, flat = tiny_f32
    assert max(_gaps(pred, flat)) < 1e-4
    assert max(_gaps(pred, flat, model=_model_dict() | dict(
        sliding_window=9))) > 1e-2


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
    assert gained["swa.blocked_xla_rope_w8"] == 4
    assert gained["gqa.blocked_xla"] == 1
    assert gained["moe.ragged_dot"] == 4
    assert gained["experts_held"] == 4 * 2
    assert gained["moe.tokens"] == 2 * 64 * 4  # images, tokens, layers
    # in float32 the program chooses the experts the reference chooses
    routing = []
    _gaps(pred, flat, routing=routing)
    table = np.asarray(dets[ROUTING_TABLE_KEY])
    assert table.shape == (4, 2 * 64, 4)
    mine = np.sort(table.reshape(4, 2, 64, 4), -1)
    for b in range(2):
        for layer in range(4):
            own = np.sort(routing[4 * b + layer]["own"], -1)
            assert (mine[layer, b] == own).all(), (b, layer)
    attrs = [r for r in obs.spans() if r["name"] == "compile"][-1]["attrs"]
    assert attrs["trunk_swa"] == "blocked_xla_rope_w8 x4"
    assert attrs["trunk_gqa"] == "blocked_xla x1"
    assert attrs["trunk_moe"] == "ragged_dot x4"
    assert attrs["trunk_pairs"] == "xla_gather x4"
    assert attrs["experts_held"] == 2
    assert not {"trunk_kda", "trunk_mla", "trunk_ssm", "trunk_hc",
                "trunk_short_conv"} & set(attrs)


def test_scopes_name_what_the_new_metrics_match(tiny_f32):
    """The sliding layers' attention under ``layers_{0,1,2,4}/attn/`` with
    ``softmax/`` and ``rope/`` inside (``trunk.swa.ms``,
    ``swa_attn_roofline``), the full layer's under ``layers_3/attn/`` with
    no ``rope/``; the post-norms under their own names and no pre-norm."""
    pred, _ = tiny_f32
    images, exemplars = _inputs()
    text = jax.jit(pred.model.apply).lower(
        {"params": pred.params}, jnp.asarray(images),
        jnp.asarray(exemplars)).as_text(debug_info=True)
    for layer in range(5):
        scopes = ["attn/q_norm/", "attn/k_norm/", "attn/softmax/",
                  "post_attn_norm/", "post_ffn_norm/"]
        scopes += ["attn/rope/"] if layer != 3 else []
        scopes += ["ffn/router/", "ffn/dispatch/", "ffn/experts/",
                   "ffn/shared/"] if layer else ["ffn/gate/"]
        for scope in scopes:
            assert f"backbone/layers_{layer}/{scope}" in text, (layer, scope)
    assert "backbone/layers_3/attn/rope/" not in text
    assert not re.search(r"layers_\d+/norm[12]/", text)


def test_the_registry_names_the_share_at_the_published_widths():
    """``build_backbone`` finds the published share by name; its sizes are
    the configuration file's; ``jax.eval_shape`` pins the file's
    ``cut_arithmetic``: 113,246,208 parameters of attention projections a
    layer (and 256 of q/k norms), 37,748,736 an expert, 452,997,376 the
    dense layer, 755,773,824 an expert layer with 16 of 128 experts held,
    3,476,098,816 the five layers with the final norm, all the layers'
    leaves bfloat16; a trunk states the sizes of its own kinds of layer
    only."""
    import json
    import os

    from benchmarks.drivers.offline_predict_exaone_trunk import _trunk_sizes

    z = TRUNK_CONFIGS["k_exaone_236b_a23b_share8"]
    assert not {"kda_head_dim", "qk_nope_dim", "kv_rank", "ssm_heads",
                "hc_mult", "residual_multiplier", "conv_size", "rope_theta",
                "diffusion_block", "router", "router_eps",
                "shared_width"} & set(z)
    assert z["layers"] == LAYERS
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kexaone_fscd147.json")) as f:
        config = json.load(f)
    assert _trunk_sizes(config["model"]) == z
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    arithmetic = config["cut_arithmetic"]
    shapes = jax.eval_shape(
        build_lm_trunk("k_exaone_236b_a23b_share8").init, jax.random.key(0),
        jnp.zeros((1, 64, 64, 3)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    attn = shapes["layers_1"]["attn"]
    projections = count({k: v for k, v in attn.items()
                         if k.endswith("_proj")})
    assert projections == arithmetic["attention_projections"] == 113246208
    assert count(attn) == arithmetic["attention_with_qk_norms"] == 113246464
    assert count(shapes["layers_1"]["ffn"]["router"]) == 6144 * 128 + 128
    expert = count(shapes["layers_1"]["ffn"]["experts"]) // 16
    assert expert == count(shapes["layers_1"]["ffn"]["shared"]) \
        == arithmetic["expert"] == 37748736
    assert count(shapes["layers_0"]) == arithmetic["dense_layer"] \
        == 452997376
    assert all(count(shapes[f"layers_{i}"]) == arithmetic[
        "expert_layer_held"] == 755773824 for i in range(1, 5))
    layers = sum(count(shapes[f"layers_{i}"]) for i in range(5))
    assert layers + count(shapes["final_norm"]) == arithmetic[
        "five_layers_with_final_norm"] == 3476098816
    assert layers + count(shapes["final_norm"]) + count(
        shapes["patch_embed"]) == arithmetic[
            "trunk_held_with_patch_embed_and_final_norm"]
    assert all(l.dtype == jnp.bfloat16 for i in range(5)
               for l in jax.tree.leaves(shapes[f"layers_{i}"]))
    assert set(shapes["layers_0"]) == {"attn", "ffn", "post_attn_norm",
                                       "post_ffn_norm"}


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number the published config gives is the file's, but for the
    four ``reduced`` keys, which hold this chip's share and name their
    published values."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kexaone_fscd147.json")) as f:
        config = json.load(f)
    published = config["published"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
                5, 16, 0, 0)
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"],
            published["num_nextn_predict_layers"]) == (48, 128, 153600, 1)
    assert config["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["moe_intermediate_size"],
            config["intermediate_size"], config["num_experts_per_tok"],
            config["routed_scaling_factor"]) == (
                6144, 64, 8, 128, 128, 2048, 18432, 8, 2.5)


@pytest.mark.parametrize("name", ["kimi_linear_a3b_share2",
                                  "xing4_a4b_stage6",
                                  "granite4_h_small_share2",
                                  "lfm2_24b_a2b_share8",
                                  "sdar_30b_a3b_share8"])
def test_the_registered_trunks_keep_pre_norms_and_no_window(name):
    """The window and the post-norms default to none: the five trunks
    registered before them state neither and build as they were."""
    z = TRUNK_CONFIGS[name]
    assert not {"sliding_window", "sliding_rope_theta", "post_norms"} & set(z)
    model = build_lm_trunk(name)
    assert model.sliding_window is None and model.post_norms is False


def test_the_kernel_path_runs_every_sliding_layer_through_the_kernel(
        monkeypatch):
    """With the gate's yes, in bfloat16 at heads of 128 (tiny everything
    else, 1,024 patches: two query blocks of 512), every sliding layer
    counts ``trunk.swa.causal_kernel_qknorm_rope_w128`` and the full layer
    ``trunk.gqa.causal_kernel_qknorm`` (no rotary), and the lowered text
    carries the kernel's call under each layer's ``attn/softmax/`` and
    nothing under ``rope/`` or the q/k norms."""
    from tmr_tpu.obs.compile import _trunk_attrs
    from tmr_tpu.ops import causal_attn

    monkeypatch.setattr(causal_attn, "gqa_kernel_ok", lambda *a: True)
    model = build_lm_trunk("k_exaone_236b_a23b_share8", dtype=jnp.bfloat16,
                           hidden=64, num_heads=16, kv_heads=2,
                           dense_width=64, expert_width=32, num_experts=4,
                           experts_held=4, top_k=2)
    image = jax.ShapeDtypeStruct((1, 512, 512, 3), jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.key(0), image)
    counts = lambda: obs.get_registry().counters("trunk.")
    before = counts()
    text = jax.jit(model.apply).lower(params, image).as_text(debug_info=True)
    attrs = _trunk_attrs(before, counts())
    assert attrs["trunk_swa"] == "causal_kernel_qknorm_rope_w128 x4"
    assert attrs["trunk_gqa"] == "causal_kernel_qknorm x1"
    call = r"layers_(\d+)/attn/softmax/[^\"]*_gqa_kernel_fwd_impl"
    assert sorted({int(n) for n in re.findall(call, text)}) == [0, 1, 2, 3,
                                                                 4]
    assert not re.search(r"layers_\d+/attn/(rope|q_norm|k_norm)/", text)

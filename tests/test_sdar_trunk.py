"""The SDAR-MoE trunk as the detector's backbone (``models/lm_trunk.py`` with
grouped-query attention under block diffusion's block-causal mask, per-head
q/k norms and the default rotary in every layer, routed-only expert layers
chosen by the largest logits) against its plain reference
(``benchmarks/reference_sdar_trunk.py``), at tiny widths on the CPU: hidden
64, 8 query heads on 1 key-value head of 16 (groups of 8, as the published
32 on 4), diffusion blocks of 4, 16 experts of which 2 are held (the cell's
share of 8), 4 a token, four layers, 64 px images (16 patches: 4 blocks).
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sdar_trunk as ref
from tmr_tpu import obs
from tmr_tpu.models.lm_trunk import (TRUNK_CONFIGS, GQAMixer, MoEFFN,
                                     build_lm_trunk)
from tmr_tpu.ops import moe

TINY = "sdar_tiny"
SIZE = 64

# the published pattern at the tests' widths, under a name of the tests' own
TRUNK_CONFIGS[TINY] = dict(
    hidden=64, layers=(("gqa", "moe"),) * 4, num_heads=8, kv_heads=1,
    head_dim=16, attn_scale=0.25, qk_norm=True, rope_theta=100.0,
    diffusion_block=4, expert_width=32, shared_width=0, num_experts=16,
    experts_held=2, expert_offset=0, top_k=4, router="softmax_topk",
    norm_eps=1e-6)


def _flat(params) -> dict:
    return {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}


def _tree(flat: dict) -> dict:
    return flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})


# ---------------------------------------------------------- attention
def test_the_block_mask_is_the_published_rule():
    mask = np.asarray(ref.block_mask(10, 4))
    want = np.array([[j // 4 <= i // 4 for j in range(10)]
                     for i in range(10)])
    np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(np.asarray(ref.block_mask(6, 1)),
                                  np.tril(np.ones((6, 6), bool)))


def test_gqa_under_the_block_mask_equals_a_masked_softmax():
    """8 query heads on 1 key-value head of 16, q/k norms, rotary, blocks
    of 4, 40 tokens: the mixer against the reference's masked softmax a
    head; the causal mask (blocks of 1) and blocks of 8 each give another
    answer."""
    mixer = GQAMixer(8, 1, 16, 0.25, qk_norm=True, rope_theta=100.0,
                     norm_eps=1e-6, diffusion_block=4,
                     param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 40, 64))
    params = mixer.init(jax.random.key(2), x)["params"]
    flat = _flat(params)
    for i, name in enumerate(("q_norm/weight", "k_norm/weight")):
        flat[name] = 1.75 + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(3), i), (16,))
    tree = _tree(flat)
    got = mixer.apply({"params": tree}, x)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            np.testing.assert_allclose(
                got[b], ref._gqa(x[b], flat, 8, 1, 100.0, 1e-6, 4, None),
                atol=2e-5)
    for n in (1, 8):
        other = mixer.clone(diffusion_block=n).apply({"params": tree}, x)
        assert float(jnp.abs(other - got).max()) > 1e-2, n


# -------------------------------------------------------------- experts
def test_the_router_weighs_as_the_published_softmax_renormalised():
    """Softmax over all the experts, the largest chosen, renormalised over
    the chosen (``norm_topk_prob``): the program's softmax over the chosen
    logits gives the same weights; the unrenormalised share does not."""
    x = jax.random.normal(jax.random.key(0), (30, 16))
    kernel = 0.1 * jax.random.normal(jax.random.key(1), (16, 12))
    idx, weights = moe.route_softmax_topk(x, kernel, 3)
    p = np.asarray(jax.nn.softmax(
        jnp.matmul(x, kernel, precision="highest"), -1))
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.argsort(-p, -1)[:, :3], -1))
    chosen = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    assert chosen.sum(-1).max() < 0.8  # the share alone is not the weights


def test_eight_shares_add_up_to_the_uncut_reference_layer():
    """Experts 0-1, 2-3, ..., 14-15 of 16 as the eight chips that share a
    layer hold them, nothing counted once (no shared expert): the eight
    parts add up to what the reference gives with all 16 held; 2e-5 of
    outputs of order 1. One share alone is not the layer."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))
    whole = MoEFFN(16, 16, 0, 4, 1.0, 32, "softmax_topk", shared_width=0,
                   param_dtype=jnp.float32)
    params = whole.init(jax.random.key(1), x)["params"]
    assert set(params) == {"router", "experts"}
    assert set(params["router"]) == {"kernel"}  # no selection bias
    flat = _flat(params)
    parts = []
    for offset in range(0, 16, 2):
        share = MoEFFN(16, 2, offset, 4, 1.0, 32, "softmax_topk",
                       shared_width=0, param_dtype=jnp.float32)
        cut = dict(params, experts={
            name: leaf[offset:offset + 2]
            for name, leaf in params["experts"].items()})
        parts.append(np.asarray(share.apply({"params": cut}, x)))
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref._moe_ffn(x[b], flat, 4, 0, None)[0])
                         for b in range(2)])
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    np.testing.assert_allclose(whole.apply({"params": params}, x), want,
                               atol=2e-5)
    assert np.abs(parts[0] - want).max() > 1e-2


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
            mean = 1.75 if path.endswith("_norm/weight") else 0.0
            noise = mean + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)
            flat[path] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
    flat["objectness_head_0/conv/bias"] = jnp.full((1,), 0.3)
    # the benchmark's gain on the objectness head: else the map is its bias
    # to two digits
    flat["objectness_head_0/conv/kernel"] *= 9.0
    pred.params = _tree(flat)
    return pred, flat


def _model_dict():
    z = TRUNK_CONFIGS[TINY]
    return dict(
        patch_size=16, feature_upsample=True, fusion=True,
        decoder_num_layer=1, layers=[list(l) for l in z["layers"]],
        num_heads=z["num_heads"], num_key_value_heads=z["kv_heads"],
        rope_theta=z["rope_theta"], diffusion_block=z["diffusion_block"],
        num_experts_per_token=z["top_k"], expert_offset=0,
        rms_norm_eps=z["norm_eps"])


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
    summation; the reference under the causal mask is another detector."""
    pred, flat = tiny_f32
    assert max(_gaps(pred, flat)) < 1e-4
    assert max(_gaps(pred, flat, model=dict(_model_dict(),
                                            diffusion_block=1))) > 1e-2


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
    assert gained["gqa.blocked_xla_rope_block4"] == 4
    assert gained.get("gqa.blocked_xla_rope", 0) == 0
    assert gained["moe.ragged_dot"] == 4
    assert gained["experts_held"] == 4 * 2
    assert gained["moe.tokens"] == 2 * 16 * 4  # images, tokens, layers
    assert 0 < gained["moe.pairs_here"] < 2 * 16 * 4 * 4  # 2 of 16 held
    # in float32 the program chooses the experts the reference chooses
    routing = []
    _gaps(pred, flat, routing=routing)
    table = np.asarray(dets[ROUTING_TABLE_KEY])
    assert table.shape == (4, 2 * 16, 4)
    mine = np.sort(table.reshape(4, 2, 16, 4), -1)
    for b in range(2):
        for layer in range(4):
            own = np.sort(routing[4 * b + layer]["own"], -1)
            assert (mine[layer, b] == own).all(), (b, layer)
    attrs = [r for r in obs.spans() if r["name"] == "compile"][-1]["attrs"]
    assert attrs["trunk_gqa"] == "blocked_xla_rope_block4 x4"
    assert attrs["trunk_moe"] == "ragged_dot x4"
    assert attrs["trunk_pairs"] == "xla_gather x4"
    assert attrs["experts_held"] == 2
    assert not {"trunk_kda", "trunk_mla", "trunk_ssm", "trunk_hc",
                "trunk_short_conv"} & set(attrs)


def test_scopes_name_what_the_new_metrics_match(tiny_f32):
    import re

    pred, _ = tiny_f32
    images, exemplars = _inputs()
    text = jax.jit(pred.model.apply).lower(
        {"params": pred.params}, jnp.asarray(images),
        jnp.asarray(exemplars)).as_text(debug_info=True)
    for layer in range(4):
        for scope in ("attn/q_norm/", "attn/k_norm/", "attn/rope/",
                      "attn/softmax/", "ffn/router/", "ffn/dispatch/",
                      "ffn/experts/"):
            assert f"backbone/layers_{layer}/{scope}" in text, (layer, scope)
    assert not re.search(r"layers_\d+/ffn/shared/", text)


def test_the_registry_names_the_share_at_the_published_widths():
    """``build_backbone`` finds the published share by name; its sizes are
    the configuration file's; ``jax.eval_shape`` pins the file's
    ``cut_arithmetic``: 18,874,624 parameters of attention and 94,638,336
    a layer with 16 of 128 experts held, 4,542.6 M for the 48 layers, all
    their leaves bfloat16; a trunk states the sizes of its own kinds of layer
    only."""
    import json
    import os

    from benchmarks.drivers.offline_predict_sdar_trunk import _trunk_sizes

    z = TRUNK_CONFIGS["sdar_30b_a3b_share8"]
    assert not {"kda_head_dim", "qk_nope_dim", "kv_rank", "ssm_heads",
                "hc_mult", "residual_multiplier", "conv_size",
                "dense_width", "router_eps"} & set(z)
    assert z["layers"] == (("gqa", "moe"),) * 48
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "sdar30b_fscd147.json")) as f:
        config = json.load(f)
    assert _trunk_sizes(config["model"]) == z
    assert config["reduced"] == ["num_experts", "vocab_size"]
    arithmetic = config["cut_arithmetic"]
    shapes = jax.eval_shape(
        build_lm_trunk("sdar_30b_a3b_share8").init, jax.random.key(0),
        jnp.zeros((1, 64, 64, 3)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    assert count(shapes["layers_0"]["attn"]) == arithmetic["attention"] \
        == 18874624
    assert count(shapes["layers_0"]["ffn"]["router"]) == 262144
    expert = count(shapes["layers_0"]["ffn"]["experts"]) // 16
    assert expert == arithmetic["expert"] == 4718592
    assert count(shapes["layers_0"]) == count(shapes["layers_47"]) \
        == arithmetic["layer_held"] == 94638336
    layers = sum(count(shapes[f"layers_{i}"]) for i in range(48))
    assert layers == arithmetic["trunk_48_layers_held"]
    assert round(layers / 1e6, 1) == 4542.6
    assert layers + count(shapes["final_norm"]) + count(
        shapes["patch_embed"]) == arithmetic[
            "trunk_held_with_patch_embed_and_final_norm"]
    assert all(l.dtype == jnp.bfloat16 for i in range(48)
               for l in jax.tree.leaves(shapes[f"layers_{i}"]))


@pytest.mark.parametrize("name", ["kimi_linear_a3b_share2",
                                  "xing4_a4b_stage6",
                                  "granite4_h_small_share2",
                                  "lfm2_24b_a2b_share8"])
def test_the_registered_trunks_keep_the_causal_mask(name):
    """The diffusion block defaults to 1, the causal mask: the four trunks
    registered before it state none and build as they were."""
    assert "diffusion_block" not in TRUNK_CONFIGS[name]
    assert build_lm_trunk(name).diffusion_block == 1

"""A language model's trunk as the detector's backbone
(``tmr_tpu/models/lm_trunk.py``, ``ops/kda.py``, ``ops/moe.py``,
``ops/causal_attn.py``) against its plain reference
(``benchmarks/reference_lm_trunk.py``), at tiny widths on the CPU: hidden
64, 2 heads x 16, 8 experts of which 4 are held, 2 a token, 5 layers in the
published pattern, 64 px images.
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_lm_trunk as ref
from tmr_tpu import obs
from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, MoEFFN, build_lm_trunk
from tmr_tpu.ops import moe
from tmr_tpu.ops.causal_attn import causal_attention_blocked
from tmr_tpu.ops import kda
from tmr_tpu.ops.kda import (causal_conv, kda_chunk_kernel, kda_chunked,
                             kda_formulation, kda_recurrent)

TINY = "kimi_linear_tiny"
SIZE = 64

# the published pattern at the tests' widths, under a name of the tests' own
# (``build_backbone`` takes a backbone by its name in the registry)
TRUNK_CONFIGS[TINY] = dict(
    hidden=64, num_heads=2, kda_head_dim=16, conv_size=4, qk_nope_dim=16,
    qk_pe_dim=8, v_dim=16, kv_rank=24, dense_width=96, expert_width=32,
    num_experts=8, experts_held=4, top_k=2, routed_scale=2.446,
    layers=(("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
            ("kda", "moe")))


# ------------------------------------------------------------------ KDA
def _kda_inputs(seq, decay, seed=0, b=2, h=2, d=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, seq, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, seq, h, d)))
    v = jax.random.normal(ks[2], (b, seq, h, d))
    # log-decays spread around log(decay), one a head and channel
    g = jnp.log(decay) * jnp.exp(0.3 * jax.random.normal(ks[3], (b, seq, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, seq, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("seq", [64, 128, 100, 37])
@pytest.mark.parametrize("decay", [1e-9, 0.5, 0.97, 1.0 - 1e-6])
def test_chunked_kda_equals_the_token_recurrence(seq, decay):
    """Lengths that are and are not multiples of the chunk; a decay near 0
    (the state dies in a token: exp(-G) would overflow) and near 1."""
    args = _kda_inputs(seq, decay, seed=seq)
    want = kda_recurrent(*args)
    got = kda_chunked(*args, chunk=64, sub=16)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_chunk_and_sub_block_sizes_do_not_change_the_result():
    args = _kda_inputs(96, 0.9)
    a = kda_chunked(*args, chunk=32, sub=8)
    b = kda_chunked(*args, chunk=64, sub=16)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq", [64, 128, 256])
@pytest.mark.parametrize("decay", [1e-9, 0.5, 0.97, 1.0 - 1e-6])
def test_chunk_kernel_equals_the_token_recurrence(seq, decay):
    """The Pallas kernel (in the interpreter here) at the published head
    width, two heads a grid step, a batch of two so that the state is reset
    at a sequence's first chunk, up to four chunks so that it is carried."""
    args = _kda_inputs(seq, decay, seed=seq, d=128)
    assert kda._heads_per_step(args[0].shape[2]) == 2
    want = kda_recurrent(*args)
    got = kda_chunk_kernel(*args, jnp.float32)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_chunk_kernel_takes_the_norms_on_either_side_of_the_recurrence():
    """With ``q_scale`` the kernel takes q and k unnormalised and does what
    the mixer's ``l2norm`` does; with ``out_norm`` it returns what the
    mixer's ``o_norm`` would: a chunk at a time, and differentiable."""
    _, _, v, g, beta = _kda_inputs(128, 0.9, d=128)
    q, k = (3.0 * jax.random.normal(jax.random.key(n), v.shape)
            for n in (5, 6))
    weight = 1.0 + 0.1 * jax.random.normal(jax.random.key(7), (128,))

    def plain(fn, q, k, weight):
        return kda.rms_norm(fn(kda.l2norm(q) * 128 ** -0.5, kda.l2norm(k), v,
                               g, beta), weight, 1e-5)

    def fused(q, k, weight):
        return kda_chunk_kernel(q, k, v, g, beta, jnp.float32, 128 ** -0.5,
                                (weight, 1e-5))

    np.testing.assert_allclose(fused(q, k, weight),
                               plain(kda_recurrent, q, k, weight),
                               atol=1e-4, rtol=1e-4)  # o's rms is 0.01
    grads = jax.grad(lambda *a: jnp.sum(fused(*a) ** 2),
                     argnums=(0, 1, 2))(q, k, weight)
    wants = jax.grad(lambda *a: jnp.sum(plain(kda_chunked, *a) ** 2),
                     argnums=(0, 1, 2))(q, k, weight)
    for a, b in zip(grads, wants):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_chunk_kernel_differentiates_through_the_chunked_form():
    args = _kda_inputs(128, 0.9, d=128)
    weigh = jax.random.normal(jax.random.key(9), args[2].shape)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weigh)
    got = jax.grad(loss(lambda *a: kda_chunk_kernel(*a, jnp.float32)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(kda_chunked), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("case,want", [
    ("the_cell", "chunk_kernel"), ("odd_length", "chunked_xla"),
    ("narrow_heads", "chunked_xla"), ("float32", "chunked_xla"),
    ("cpu_backend", "chunked_xla"), ("partitioned", "chunked_xla")])
def test_kda_formulation_by_what_it_observes(case, want, monkeypatch):
    """The kernel where a TPU, the type, the length and the head width
    allow it and its gate says yes; ``kda_chunked`` everywhere else, and a
    trace XLA partitions says why."""
    from tmr_tpu import diagnostics

    if case != "partitioned":  # there the gate's own wrapper answers
        monkeypatch.setattr(kda, "kda_chunk_ok", lambda dk, hb: True)
    if case != "cpu_backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq, d, dtype = {"odd_length": (4000, 128, jnp.bfloat16),
                     "narrow_heads": (4096, 16, jnp.bfloat16),
                     "float32": (4096, 128, jnp.float32)}.get(
                         case, (4096, 128, jnp.bfloat16))
    diagnostics.drain_gate_refusals()
    if case == "partitioned":
        with diagnostics.mosaic_kernels_off("a two-chip mesh"):
            got = kda_formulation(seq, d, d, dtype, 32)
        causes = {(r["gate"], r["cause"])
                  for r in diagnostics.drain_gate_refusals()}
        assert ("kda_chunk_ok", "partitioned") in causes
    else:
        got = kda_formulation(seq, d, d, dtype, 32)
        assert not diagnostics.drain_gate_refusals()
    assert got == want


def test_causal_conv_is_left_padded_and_depthwise():
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    kernel = jax.random.normal(jax.random.key(1), (4, 6))
    got = np.asarray(causal_conv(x, kernel))
    xs = np.asarray(x)
    for t in range(9):
        want = sum(np.asarray(kernel)[j] * xs[:, t - 3 + j]
                   for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(got[:, t], want, atol=1e-6)


# ------------------------------------------------------------------ MLA
@pytest.mark.parametrize("seq,block", [(70, 32), (64, 64), (48, 256)])
def test_blocked_causal_attention_equals_masked_softmax(seq, block):
    """Unequal query-key (24) and value (16) widths."""
    ks = jax.random.split(jax.random.key(seq), 3)
    q = jax.random.normal(ks[0], (2, seq, 2, 24))
    k = jax.random.normal(ks[1], (2, seq, 2, 24))
    v = jax.random.normal(ks[2], (2, seq, 2, 16))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    got = causal_attention_blocked(q, k, v, 24 ** -0.5, block=block)
    np.testing.assert_allclose(got, want, atol=2e-6)


# ------------------------------------------------------------------ MoE
def _moe_inputs(tokens=50, d=16, experts=8, width=12, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (tokens, d)),
        router=jax.random.normal(ks[1], (d, experts)),
        bias=0.5 * jax.random.normal(ks[2], (experts,)),
        gate=jax.random.normal(ks[3], (experts, d, width)),
        up=jax.random.normal(ks[4], (experts, d, width)),
        down=jax.random.normal(ks[5], (experts, width, d)))


def _share(z, idx, weights, offset, held):
    xs, sizes, here, slot = moe.dispatch(z["x"], idx, held, offset)
    cut = lambda w: w[offset:offset + held]
    ys = moe.grouped_ffn(xs, sizes, cut(z["gate"]), cut(z["up"]),
                         cut(z["down"]), jnp.float32)
    return moe.combine(ys, weights, here, slot), sizes


def _loop_over_experts(z, idx, weights):
    out = np.zeros(z["x"].shape, np.float64)
    for t in range(idx.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            h = jax.nn.silu(z["x"][t] @ z["gate"][e]) * (z["x"][t] @ z["up"][e])
            out[t] += float(weights[t, j]) * np.asarray(h @ z["down"][e])
    return out


def test_router_selects_by_score_plus_bias_and_weighs_by_score():
    z = _moe_inputs()
    idx, weights = moe.route(z["x"], z["router"], z["bias"], 2, 2.446)
    s = np.asarray(jax.nn.sigmoid(z["x"] @ z["router"]), np.float64)
    picked = np.argsort(-(s + np.asarray(z["bias"])), -1)[:, :2]
    assert (np.sort(np.asarray(idx), -1) == np.sort(picked, -1)).all()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    want = chosen / chosen.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    # the bias chooses only: without it another set may win, same weights' law
    assert not np.allclose(np.sort(np.argsort(-s, -1)[:, :2], -1),
                           np.sort(picked, -1))


@pytest.mark.parametrize("offset,held", [(0, 8), (0, 4), (4, 4), (2, 3)])
def test_expert_layer_equals_a_loop_over_its_experts(offset, held):
    z = _moe_inputs()
    idx, weights = moe.route(z["x"], z["router"], z["bias"], 2, 2.446)
    got, sizes = _share(z, idx, weights, offset, held)
    mine = (np.asarray(idx) >= offset) & (np.asarray(idx) < offset + held)
    want = _loop_over_experts(z, np.asarray(idx),
                              np.where(mine, np.asarray(weights), 0.0))
    np.testing.assert_allclose(got, want, atol=2e-4)
    counts = np.bincount(np.asarray(idx)[mine] - offset, minlength=held)
    assert (np.asarray(sizes) == counts).all()


def test_no_token_is_dropped_when_every_token_picks_the_same_expert():
    z = _moe_inputs()
    z["bias"] = jnp.asarray([0, 0, 50.0, 0, 0, 40.0, 0, 0])  # all pick 2, 5
    idx, weights = moe.route(z["x"], z["router"], z["bias"], 2, 2.446)
    assert (np.sort(np.asarray(idx), -1) == [2, 5]).all()
    got, sizes = _share(z, idx, weights, 0, 4)  # holds 2, not 5
    assert np.asarray(sizes).tolist() == [0, 0, 50, 0]
    want = _loop_over_experts(
        z, np.asarray(idx), np.where(np.asarray(idx) < 4, weights, 0.0))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert np.abs(want).min(axis=-1).max() > 0  # every token got its part


# ------------------------------------------------- the shares add up
def _tiny_weights(seed=0, compute="float32"):
    """A Predictor on the tiny trunk with seeded weights that leave no leaf
    at its initial constant, and the same weights as the reference's flat
    dict."""
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    pred = Predictor(preset("TMR_FSCD147", backbone=TINY, image_size=SIZE,
                            emb_dim=32, compute_dtype=compute))
    params = pred.init_params(seed, image_size=SIZE)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    key = jax.random.key(seed + 1)
    for i, (path, leaf) in enumerate(sorted(flat.items())):
        if path.endswith(("bias", "weight", "A_log", "dt_bias")):
            noise = 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                            leaf.shape)
            flat[path] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
    flat["objectness_head_0/conv/bias"] = jnp.full((1,), 0.3)
    pred.params = flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})
    return pred, flat


def _model_dict(**over):
    z = TRUNK_CONFIGS[TINY]
    model = dict(
        patch_size=16, feature_upsample=True, fusion=True,
        decoder_num_layer=1, layers=[list(l) for l in z["layers"]],
        num_heads=z["num_heads"], qk_nope_head_dim=z["qk_nope_dim"],
        v_head_dim=z["v_dim"], num_experts_per_token=z["top_k"],
        routed_scaling_factor=z["routed_scale"], expert_offset=0)
    model.update(over)
    return model


def test_the_shares_add_up_to_the_uncut_layer():
    """The two shares' routed parts plus the shared expert counted once
    equal the reference's layer holding all 8 experts."""
    z = TRUNK_CONFIGS[TINY]
    kw = dict(num_experts=8, top_k=2, scale=z["routed_scale"],
              width=z["expert_width"], dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 24, z["hidden"]))
    whole = MoEFFN(experts_held=8, expert_offset=0, **kw)
    params = whole.init(jax.random.key(1), x)["params"]
    params = jax.tree.map(
        lambda p: (p.astype(jnp.float32) + 0.1 * jax.random.normal(
            jax.random.key(p.size), p.shape)).astype(p.dtype), params)
    cut = lambda lo: {**params, "experts": jax.tree.map(
        lambda w: w[lo:lo + 4], params["experts"])}
    part = lambda lo: MoEFFN(experts_held=4, expert_offset=lo, **kw).apply(
        {"params": cut(lo)}, x)
    shared = whole.apply(
        {"params": {**params, "experts": jax.tree.map(
            jnp.zeros_like, params["experts"])}}, x)
    got = part(0) + part(4) - shared  # the shared expert counted once
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    want, _, _, counts, _ = ref._moe_ffn(
        x.reshape(-1, z["hidden"]), flat, 2, 0, z["routed_scale"], None)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-5)
    assert int(counts.sum()) == 2 * 48  # uncut: every pair is held


# ---------------------------------------------- the whole detector
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
    regressions over two images; ``quant`` puts the control in the
    program's place."""
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
    pred, flat = tiny_f32
    assert max(_gaps(pred, flat)) < 1e-4


def test_detector_in_bfloat16_is_within_a_tolerance_the_fp8_control_breaks(
        tiny_f32):
    """bfloat16 compute on the same leaves: within 0.015 of the maps' range
    (read 0.007); the reference at fp8, the nearest precision below, is
    outside it (read 0.033)."""
    _, flat = tiny_f32
    pred16, _ = _tiny_weights(compute="bfloat16")
    bf16, fp8 = max(_gaps(pred16, flat)), max(_gaps(pred16, flat, "fp8"))
    assert bf16 < 0.015, bf16
    assert fp8 > 0.025 and fp8 > 2 * bf16, (bf16, fp8)


def test_through_predictor_call_with_counters_and_compile_span(tiny_f32):
    from tmr_tpu.inference import (ROUTING_KEY, ROUTING_TABLE_KEY,
                                   detections_to_numpy)

    pred, flat = tiny_f32
    pred.invalidate_compiled()
    obs.clear()
    images, exemplars = _inputs()
    before = obs.get_registry().counters()
    dets = pred(images, exemplars)
    served = detections_to_numpy(dets)
    assert len(served) == 2 and all(len(s["scores"]) for s in served)
    after = obs.get_registry().counters()
    gained = {k: after[k] - before.get(k, 0) for k in (
        "trunk.moe.tokens", "trunk.moe.pairs_here", "trunk.moe.pairs_busiest")}
    # the reference's own count of the pairs held here, over both images
    routing = []
    _gaps(pred, flat, routing=routing)
    pairs = sum(int(r["counts"].sum()) for r in routing)
    assert gained["trunk.moe.pairs_here"] == pairs
    assert gained["trunk.moe.tokens"] == 2 * 16 * 4  # images, tokens, layers
    assert pairs / 4 <= gained["trunk.moe.pairs_busiest"] <= pairs
    assert np.asarray(dets[ROUTING_KEY]).tolist() == list(gained.values())
    # the program's own table of who went where: (layers, tokens, k), and in
    # float32 the experts the reference chooses by itself, image by image
    table = np.asarray(dets[ROUTING_TABLE_KEY])
    assert table.shape == (4, 2 * 16, 2)
    mine = np.sort(table.reshape(4, 2, 16, 2), -1)
    for b in range(2):
        for layer in range(4):
            own = np.sort(routing[4 * b + layer]["own"], -1)
            assert (mine[layer, b] == own).all(), (b, layer)
    fetch = [r for r in obs.spans() if r["name"] == "predict.fetch"][-1]
    assert fetch["attrs"]["trunk.moe.pairs_here"] == pairs
    compiles = [r for r in obs.spans() if r["name"] == "compile"]
    assert compiles, "the program's first call is a compile span"
    attrs = compiles[-1]["attrs"]
    assert attrs["trunk_kda"] == "chunked_xla x4"
    assert attrs["trunk_mla"] == "blocked_xla x1"
    assert attrs["trunk_moe"] == "ragged_dot x4"  # gmm on a TPU in bfloat16
    assert attrs["trunk_pairs"] == "xla_gather x4"  # row_dma there
    assert attrs["experts_held"] == 4


def test_scopes_name_the_layers_the_metrics_match(tiny_f32):
    pred, _ = tiny_f32
    images, exemplars = _inputs()
    text = jax.jit(pred.model.apply).lower(
        {"params": pred.params}, jnp.asarray(images),
        jnp.asarray(exemplars)).as_text(debug_info=True)
    for scope in ("backbone/layers_0/attn/scan/", "backbone/layers_3/attn/"
                  "softmax/", "backbone/layers_1/ffn/router/",
                  "backbone/layers_1/ffn/dispatch/",
                  "backbone/layers_1/ffn/experts/",
                  "backbone/layers_1/ffn/shared/"):
        assert scope in text, scope


def test_scopes_hold_every_operation_of_the_row_kernels(monkeypatch):
    """What ``trunk.moe_dispatch.ms`` owns on the chip: with the results
    brought to their tokens by row DMAs (here in the interpreter, at the
    narrowest trunk the kernels take) every operation of the two kernels and
    of what feeds them carries ``ffn/dispatch/`` in the compiled program's
    ``op_name``."""
    import re

    wide = dict(TRUNK_CONFIGS[TINY], hidden=256,
                layers=(("kda", "dense"), ("kda", "moe")))
    monkeypatch.setitem(TRUNK_CONFIGS, "kimi_linear_tiny_wide", wide)
    monkeypatch.setattr(moe, "pairs_formulation", lambda *a: "row_dma")
    trunk = build_lm_trunk("kimi_linear_tiny_wide", dtype=jnp.bfloat16)
    x = jnp.zeros((1, 256, 256, 3), jnp.bfloat16)  # 256 tokens, 2 a token
    params = jax.eval_shape(trunk.init, jax.random.key(0), x)
    text = jax.jit(trunk.apply).lower(params, x).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    kernels = [n for n in names if "_rows_impl" in n or "_pack_impl" in n]
    assert sum("_pack_impl" in n for n in kernels) > 5
    assert sum("_sum_rows_impl" in n for n in kernels) > 10
    assert all("layers_1/ffn/dispatch/" in n for n in kernels), kernels


def test_sam_vit_keeps_its_leaf_names_beside_the_shared_stem():
    """``patch_embed`` / ``neck_*`` are one definition for both families."""
    from tmr_tpu.models.vit import build_sam_vit

    x = jnp.zeros((1, SIZE, SIZE, 3))
    sam = jax.eval_shape(build_sam_vit("vit_b").init, jax.random.key(0), x)
    trunk = jax.eval_shape(build_lm_trunk(TINY).init, jax.random.key(0), x)
    stem = {"patch_embed", "neck_0", "neck_1", "neck_2", "neck_3"}
    assert stem <= set(sam["params"]) and stem <= set(trunk["params"])
    assert "pos_embed" in sam["params"] and "pos_embed" not in trunk["params"]
    leaves = jax.tree.leaves(trunk["params"]["layers_1"])
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    assert trunk["params"]["neck_0"]["kernel"].dtype == jnp.float32


def test_an_expert_layer_inside_a_partitioned_program_is_refused():
    """The one refusal of a mesh stands where the exchange would: every
    program XLA partitions over more than one device is traced through
    ``parallel/compat.partitioned``; on one device the same trace runs."""
    from jax.sharding import Mesh

    from tmr_tpu.parallel.compat import partitioned

    trunk = build_lm_trunk(TINY)
    x = jnp.zeros((1, SIZE, SIZE, 3))
    params = jax.eval_shape(trunk.init, jax.random.key(0), x)
    run = lambda p, x: trunk.apply(p, x)
    two = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    with pytest.raises(ValueError, match="exchange of tokens"):
        jax.eval_shape(partitioned(run, two), params, x)
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    out = jax.eval_shape(partitioned(run, one), params, x)
    assert out.shape == (1, SIZE // 16, SIZE // 16, 256)

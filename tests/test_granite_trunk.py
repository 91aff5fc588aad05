"""The Granite 4.0-H trunk as the detector's backbone (``models/lm_trunk.py``
with state-space and grouped-query mixers, the softmax-of-top-k router, a
shared MLP of its own width and scaled residuals; ``ops/ssd.py``) against
its plain reference (``benchmarks/reference_granite_trunk.py``), at tiny
widths on the CPU: hidden 64, 4 state-space heads of 16 on a state of 16,
4 query heads on 2 key-value heads of 16, 8 experts of which 4 are held, 3 a
token, two state-space layers and one attention layer, 64 px images.
"""

import functools
from unittest import mock

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite_trunk as ref
from benchmarks.reference_lm_trunk import _rms_norm
from tmr_tpu import obs
from tmr_tpu.models.lm_trunk import (TRUNK_CONFIGS, GatedRMSNorm, GQAMixer,
                                     MoEFFN, SSMMixer, build_lm_trunk)
from tmr_tpu.ops import moe, ssd
from tmr_tpu.ops.causal_attn import causal_attention_blocked
from tmr_tpu.ops.kda import causal_conv

TINY = "granite4_tiny"
SIZE = 64
#: bfloat16 against float32, of the maps' range; the readings are in its test
BF16_TOLERANCE = 0.017

# the stage's pattern at the tests' widths, under a name of the tests' own
TRUNK_CONFIGS[TINY] = dict(
    hidden=64, layers=(("ssm", "moe"),) * 2 + (("gqa", "moe"),),
    ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=1, conv_size=4,
    ssm_chunk=8, num_heads=4, kv_heads=2, head_dim=16, attn_scale=1 / 16,
    expert_width=32, shared_width=48, num_experts=8, experts_held=4, top_k=3,
    router="softmax_topk", residual_multiplier=0.22,
    embedding_multiplier=12.0)


# -------------------------------------------------------- the recurrence
def _scan_inputs(seq, decay, seed=0, groups=2):
    """``decay`` "slow": a token keeps 0.99 to 0.999 of the state, so a
    chunk's result is mostly what it was handed; "fast": 0.2 to 0.6, so it
    is mostly its own tokens'."""
    k = jax.random.split(jax.random.key(seed), 6)
    b, h, p, n = 2, 4, 8, 16
    lo, hi = (1e-3, 1e-2) if decay == "slow" else (0.5, 1.6)
    u = jax.random.normal(k[0], (b, seq, h, p))
    delta = jax.random.uniform(k[1], (b, seq, h), minval=lo, maxval=hi)
    a = jnp.ones((h,))
    bb = jax.random.normal(k[2], (b, seq, groups, n))
    cc = jax.random.normal(k[3], (b, seq, groups, n))
    d = jax.random.normal(k[4], (h,))
    return u, delta, a, bb, cc, d


@pytest.mark.parametrize("seq", [64, 70, 5])
@pytest.mark.parametrize("decay", ["slow", "fast"])
def test_ssd_chunked_equals_ssd_recurrent(seq, decay):
    """Lengths that are and are not whole chunks of 16 (and one shorter than
    a chunk), two groups of two heads: 2e-5 of outputs of order 10 (float32
    on both sides, other orders of summation)."""
    args = _scan_inputs(seq, decay)
    want = ssd.ssd_recurrent(*args)
    got = ssd.ssd_chunked(*args, chunk=16)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(scale, 1.0)
    # with slow decays late tokens read mostly the state they were handed
    if decay == "slow" and seq == 64:
        alone = ssd.ssd_chunked(*(t[:, 48:] if t.ndim > 1 else t
                                  for t in args), chunk=16)
        assert float(jnp.abs(alone - want[:, 48:]).max()) > 0.1 * scale


def test_two_chunk_sizes_give_one_result_and_the_state_is_handed_over():
    args = _scan_inputs(96, "slow", seed=1)
    a, b = (ssd.ssd_chunked(*args, chunk=c) for c in (8, 32))
    assert float(jnp.abs(a - b).max()) < 2e-5 * float(jnp.abs(a).max())
    # the fault the benchmark plants: no hand-over between chunks
    real = ssd.hand_over
    ssd.hand_over = lambda state, decay_end, local: local * 0.0
    try:
        cut = ssd.ssd_chunked(*args, chunk=8)
    finally:
        ssd.hand_over = real
    assert float(jnp.abs(cut - a).max()) > 0.1 * float(jnp.abs(a).max())
    np.testing.assert_allclose(cut[:, :8], a[:, :8], atol=1e-5)


def test_ssd_chunked_in_bfloat16_keeps_decays_and_state_in_float32():
    """bfloat16 products against the float32 recurrence on the same inputs:
    within 0.02 of the range (read 0.006); the result comes in bfloat16."""
    args = _scan_inputs(64, "slow", seed=2)
    want = ssd.ssd_recurrent(*args)
    got = ssd.ssd_chunked(*args, chunk=16, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap < 0.02 * float(jnp.abs(want).max())


# ------------------------------------------- the recurrence as one kernel
def _kernel_inputs(batch, seq, heads, decay, dtype, seed=0):
    """What ``SSMMixer`` hands the kernel, at heads of 64 on a state of
    128: the convolution's ``[u | B | C]``, ``in_proj``'s ``[z | ...]``
    (wider than z), Delta as ``_scan_inputs`` draws it, A 1, D and the
    norm's weight drawn."""
    k = jax.random.split(jax.random.key(seed), 6)
    inner, n = heads * 64, 128
    lo, hi = (1e-3, 1e-2) if decay == "slow" else (0.5, 1.6)
    xbc = jax.random.normal(k[0], (batch, seq, inner + 2 * n)).astype(dtype)
    zx = jax.random.normal(k[1], (batch, seq, 2 * inner + 2 * n + heads))
    delta = jax.random.uniform(k[2], (batch, seq, heads), minval=lo,
                               maxval=hi)
    d = jax.random.normal(k[3], (heads,))
    weight = 1.0 + 0.1 * jax.random.normal(k[4], (inner,))
    return xbc, zx.astype(dtype), delta, jnp.ones((heads,)), d, weight


@functools.partial(jax.jit, static_argnums=6)
def _recurrence_gated(xbc, zx, delta, a, d, weight, heads):
    """The token recurrence, then the gated norm, all in float32."""
    u, b, c, z = ssd._operands(xbc, zx, heads, 64)
    y = ssd.ssd_recurrent(u, delta, a, b, c, d).reshape(z.shape)
    return _rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), weight, 1e-5)


#: heads, chunk, length (no multiple of the chunk but in the second), batch
_KERNEL_CASES = [(4, 16, 40, 2, "slow"), (8, 64, 100, 1, "fast"),
                 (6, 256, 300, 1, "slow"), (4, 32, 64, 2, "fast")]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,chunk,seq,batch,decay", _KERNEL_CASES)
def test_chunk_kernel_with_its_gated_norm_equals_the_recurrence(
        heads, chunk, seq, batch, decay, dtype):
    """The kernel in the Pallas interpreter. float32: against the token
    recurrence and the gated norm, 2e-5 of the range (read 3e-6).
    bfloat16: against ``ssd_chunked`` and the norm as XLA runs them, and
    against the float32 recurrence, 0.02 of the range each (read 0.005)."""
    args = _kernel_inputs(batch, seq, heads, decay, jnp.dtype(dtype))
    got = ssd.ssd_chunk_kernel(*args, 1e-5, heads, 64, chunk)
    assert got.shape == (batch, seq, heads * 64) and got.dtype == dtype
    got = got.astype(jnp.float32)
    want = _recurrence_gated(*args, heads)
    scale = float(jnp.abs(want).max())
    if dtype == "float32":
        assert float(jnp.abs(got - want).max()) < 2e-5 * scale
        return
    xla = jax.jit(ssd.ssd_gated_chunked, static_argnums=range(6, 10))(
        *args, 1e-5, heads, 64, chunk)
    assert float(jnp.abs(got - xla.astype(jnp.float32)).max()) < 0.02 * scale
    assert float(jnp.abs(got - want).max()) < 0.02 * scale


def test_chunk_kernels_gradient_is_the_xla_forms():
    """The backward recomputes through ``ssd_gated_chunked``: every
    argument's gradient equals the XLA form's."""
    args = _kernel_inputs(2, 40, 4, "fast", jnp.float32, seed=3)
    probe = jax.random.normal(jax.random.key(9), (2, 40, 256))
    grad = lambda fn: jax.jit(jax.grad(
        lambda *t: jnp.sum(fn(*t, 1e-5, 4, 64, 16) * probe),
        argnums=range(6)))(*args)
    got, want = grad(ssd.ssd_chunk_kernel), grad(ssd.ssd_gated_chunked)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(w).max()))


def test_the_planted_fault_reaches_the_kernel():
    """The benchmark's fault "the state not handed over" patches
    ``ssd.hand_over``: the kernel, traced after the patch, moves as
    ``ssd_chunked`` does, and is itself again after it."""
    args = _kernel_inputs(2, 40, 4, "slow", jnp.float32, seed=4)
    # a jit of its own a call: what is traced under the patch stays there
    run = lambda fn: jax.jit(lambda *t: fn(*t, 1e-5, 4, 64, 16))(*args)
    sound = run(ssd.ssd_chunk_kernel)
    with mock.patch.object(ssd, "hand_over",
                           lambda state, decay_end, local: local):
        cut, cut_xla = run(ssd.ssd_chunk_kernel), run(ssd.ssd_gated_chunked)
    scale = float(jnp.abs(sound).max())
    assert float(jnp.abs(cut - sound).max()) > 0.1 * scale
    np.testing.assert_allclose(cut, cut_xla, atol=2e-5 * scale)
    np.testing.assert_allclose(cut[:, :16], sound[:, :16], atol=2e-5 * scale)
    np.testing.assert_allclose(run(ssd.ssd_chunk_kernel), sound, atol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_mixer_on_the_kernel_is_the_mixer_on_xla(dtype):
    """``SSMMixer`` where ``ssd_formulation`` answers ``chunk_kernel``
    (heads of 64 on a state of 128, chunks of 16 over 40 tokens) against
    the same mixer on ``ssd_chunked``: 2e-6 of the range in float32, 0.02
    in bfloat16; the counter names the kernel."""
    mixer = SSMMixer(4, 64, 128, 1, 4, 16, dtype=jnp.dtype(dtype),
                     param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(2), (2, 40, 64))
    params = mixer.init(jax.random.key(3), x)["params"]
    params = jax.tree.map(lambda l: l + 0.1 * jax.random.normal(
        jax.random.key(7), l.shape), params)
    xla = mixer.apply({"params": params}, x).astype(jnp.float32)
    before = obs.get_registry().counters("trunk.").get("ssm.chunk_kernel", 0)
    with mock.patch.object(ssd, "ssd_formulation",
                           lambda *a: "chunk_kernel"):
        got = mixer.apply({"params": params}, x).astype(jnp.float32)
    assert obs.get_registry().counters("trunk.")["ssm.chunk_kernel"] \
        == before + 1
    scale = float(jnp.abs(xla).max())
    tol = 2e-6 if dtype == "float32" else 0.02
    assert float(jnp.abs(got - xla).max()) < tol * scale


def test_the_gated_norm_hands_its_own_weight_to_a_kernel():
    norm = GatedRMSNorm(1e-5, jnp.float32)
    y = jnp.ones((2, 32))
    params = {"params": {"weight": jnp.arange(32.0)}}
    weight, eps = norm.apply(params, y, weight_only=True)
    np.testing.assert_array_equal(weight, jnp.arange(32.0))
    assert eps == 1e-5
    assert norm.init(jax.random.key(0), y, weight_only=True)["params"][
        "weight"].shape == (32,)


@pytest.mark.parametrize("sizes,dtype,want", [
    ((128, 64, 128, 1, 256), "bfloat16", "chunk_kernel"),
    ((4, 64, 128, 1, 16), "bfloat16", "chunk_kernel"),
    ((128, 64, 128, 1, 256), "float32", "chunked_xla"),
    ((64, 128, 128, 1, 256), "bfloat16", "chunked_xla"),
    ((5, 64, 128, 1, 256), "bfloat16", "chunked_xla"),
    ((128, 64, 64, 1, 256), "bfloat16", "chunked_xla"),
    ((128, 64, 128, 2, 256), "bfloat16", "chunked_xla"),
    ((128, 64, 128, 1, 512), "bfloat16", "chunked_xla")])
def test_ssd_formulation_decides_from_what_it_observes(monkeypatch, sizes,
                                                       dtype, want):
    """On a TPU, with the gate passing: bfloat16, heads of 64 in pairs, a
    state of 128, one group, chunks up to 256; off a TPU always the XLA
    form, whose gate refuses by the backend."""
    assert ssd.ssd_formulation(*sizes, jnp.dtype(dtype)) == "chunked_xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssd, "ssd_kernel_ok", lambda heads, chunk: True)
    assert ssd.ssd_formulation(*sizes, jnp.dtype(dtype)) == want


# ------------------------------------------------- the mixer's other parts
def test_the_convolution_has_a_bias_and_a_zero_left_pad():
    """``SSMMixer``'s convolution is ``causal_conv`` plus the bias over
    [u | B | C] together: token 0 sees its own tap and three zeros, and the
    mixer with a bias drawn equals the reference's, and differs from the
    mixer without."""
    x = jax.random.normal(jax.random.key(0), (1, 6, 5))
    kernel = jax.random.normal(jax.random.key(1), (4, 5))
    y = causal_conv(x, kernel)
    np.testing.assert_allclose(y[0, 0], x[0, 0] * kernel[3], rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 2], x[0, 0] * kernel[1] + x[0, 1] * kernel[2]
        + x[0, 2] * kernel[3], rtol=1e-5)
    mixer = SSMMixer(4, 16, 16, 1, 4, 8, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(2), (2, 24, 64))
    params = mixer.init(jax.random.key(3), x)["params"]
    assert params["conv_kernel"].shape == (4, 64 + 2 * 16)
    assert params["conv_bias"].shape == (64 + 2 * 16,)
    assert params["in_proj"]["kernel"].shape == (64, 64 + 96 + 4)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    flat["conv_bias"] = 0.5 * jax.random.normal(jax.random.key(4), (96,))
    tree = flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})
    got = mixer.apply({"params": tree}, x)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want = ref._ssm(x[b], flat, 4, 16, 1, 1e-5, None)
            np.testing.assert_allclose(got[b], want, atol=2e-5)
    # the bias matters
    assert float(jnp.abs(mixer.apply({"params": params}, x) - got).max()) \
        > 1e-3


def test_the_gated_norm_gates_before_it_normalises():
    y = jax.random.normal(jax.random.key(0), (3, 32))
    z = jax.random.normal(jax.random.key(1), (3, 32))
    norm = GatedRMSNorm(1e-5, jnp.float32)
    params = norm.init(jax.random.key(2), y, z)
    weight = 1.0 + 0.1 * jax.random.normal(jax.random.key(3), (32,))
    params = {"params": {"weight": weight}}
    got = norm.apply(params, y, z)
    np.testing.assert_allclose(
        got, _rms_norm(y * jax.nn.silu(z), weight, 1e-5), atol=1e-6)
    other = _rms_norm(y, weight, 1e-5) * jax.nn.silu(z)
    assert float(jnp.abs(got - other).max()) > 0.1
    # gated first, a row's mean square is 1 whatever the gate
    np.testing.assert_allclose(jnp.mean((got / weight) ** 2, -1), 1.0,
                               atol=1e-3)


def test_grouped_query_attention_equals_a_masked_softmax_with_kv_repeated():
    """32 query heads on 8 key-value heads of 128 at scale 1 / 128, 300
    tokens (two row blocks): the grouped form against a masked softmax a
    head with the key-value heads written out four times; 1e-5 of outputs of
    order 1."""
    k = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k[0], (2, 300, 32, 128))
    kk = jax.random.normal(k[1], (2, 300, 8, 128))
    v = jax.random.normal(k[2], (2, 300, 8, 128))
    got = causal_attention_blocked(q, kk, v, 1 / 128)
    assert got.shape == (2, 300, 32, 128)
    rep = lambda t: jnp.repeat(t, 4, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, rep(kk),
                        precision="highest") / 128
    causal = jnp.tril(jnp.ones((300, 300), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, rep(v), precision="highest")
    np.testing.assert_allclose(got, want, atol=1e-5)
    # equal head counts take the path they took: the same numbers
    same = causal_attention_blocked(q, rep(kk), rep(v), 1 / 128)
    np.testing.assert_allclose(same, want, atol=1e-5)
    # the scale is the family's multiplier, not head_dim^-1/2
    other = causal_attention_blocked(q, kk, v, 128 ** -0.5)
    assert float(jnp.abs(other - got).max()) > 0.05
    mixer = GQAMixer(4, 2, 16, 1 / 16, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 40, 64))
    params = mixer.init(jax.random.key(2), x)["params"]
    assert params["k_proj"]["kernel"].shape == (64, 32)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            np.testing.assert_allclose(
                mixer.apply({"params": params}, x)[b],
                ref._gqa(x[b], flat, 4, 2, 1 / 16, None), atol=2e-5)


def test_the_router_picks_by_logit_and_weighs_by_softmax_over_the_chosen():
    x = jax.random.normal(jax.random.key(0), (50, 32))
    kernel = jax.random.normal(jax.random.key(1), (32, 12))
    idx, weights = moe.route_softmax_topk(x, kernel, 4)
    logits = np.asarray(jnp.matmul(x, kernel, precision="highest"))
    order = np.argsort(-logits, -1)[:, :4]
    assert (np.sort(np.asarray(idx), -1) == np.sort(order, -1)).all()
    chosen = np.take_along_axis(logits, np.asarray(idx), -1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    # not a softmax over all twelve with the top four kept
    full = np.exp(logits - logits.max(-1, keepdims=True))
    full /= full.sum(-1, keepdims=True)
    assert np.abs(np.take_along_axis(full, np.asarray(idx), -1)
                  - np.asarray(weights)).max() > 0.05
    assert idx.dtype == jnp.int32 and weights.dtype == jnp.float32


def test_the_two_shares_add_up_to_the_uncut_reference_layer():
    """Experts 0-3 and 4-7 of 8, the shared MLP (48 wide beside experts of
    32) counted once: the two chips' parts add up to what the reference
    gives with all 8 held; 2e-5 of outputs of order 1."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))
    whole = MoEFFN(8, 8, 0, 3, 1.0, 32, "softmax_topk", 48,
                   param_dtype=jnp.float32)
    params = whole.init(jax.random.key(1), x)["params"]
    assert "bias" not in params["router"]
    assert params["shared"]["gate"]["kernel"].shape == (64, 48)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    shared = np.stack([np.asarray(ref._gated_mlp(
        x[b], flat["shared/gate/kernel"], flat["shared/up/kernel"],
        flat["shared/down/kernel"], None)) for b in range(2)])
    parts = []
    for offset in (0, 4):
        share = MoEFFN(8, 4, offset, 3, 1.0, 32, "softmax_topk", 48,
                       param_dtype=jnp.float32)
        cut = dict(params, experts={
            name: leaf[offset:offset + 4]
            for name, leaf in params["experts"].items()})
        out, sown = share.apply({"params": cut}, x, mutable=["trunk_stats"])
        assert int(sown["trunk_stats"]["group_sizes"][0].sum()) > 0
        parts.append(np.asarray(out))
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref._moe_ffn(x[b], flat, 3, 0, None)[0])
                         for b in range(2)])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want, atol=2e-5)
    np.testing.assert_allclose(whole.apply({"params": params}, x), want,
                               atol=2e-5)
    assert np.abs(parts[0] - want).max() > 1e-2  # one share is not the layer


# ------------------------------------------------------ the whole detector
def _tiny_weights(seed=0, compute="float32"):
    """A Predictor on the tiny trunk with seeded weights that leave no leaf
    at its initial constant, the patch embedding at a twelfth (times
    ``embedding_multiplier`` it enters the trunk at order one, as the
    benchmark's does: else the sub-layers' 0.22 x O(1) would be a thirtieth
    of the stream and nothing in them would show), and the same weights as
    the reference's flat dict."""
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    pred = Predictor(preset("TMR_FSCD147", backbone=TINY, image_size=SIZE,
                            emb_dim=32, compute_dtype=compute))
    params = pred.init_params(seed, image_size=SIZE)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(params).items()}
    key = jax.random.key(seed + 1)
    for i, (path, leaf) in enumerate(sorted(flat.items())):
        if path.endswith(("bias", "weight", "/D")):
            noise = 0.2 * jax.random.normal(jax.random.fold_in(key, i),
                                            leaf.shape)
            flat[path] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
        if "patch_embed/" in path:
            flat[path] = flat[path] / 12.0
    flat["objectness_head_0/conv/bias"] = jnp.full((1,), 0.3)
    # the benchmark's gain on the objectness head: else the map is its bias
    # to two digits and bfloat16's rounding of 0.3 is all a gap reads
    flat["objectness_head_0/conv/kernel"] *= 9.0
    pred.params = flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})
    return pred, flat


def _model_dict():
    z = TRUNK_CONFIGS[TINY]
    return dict(
        patch_size=16, feature_upsample=True, fusion=True,
        decoder_num_layer=1, layers=[list(l) for l in z["layers"]],
        mamba_n_heads=z["ssm_heads"], mamba_d_state=z["ssm_state"],
        mamba_n_groups=z["ssm_groups"], num_heads=z["num_heads"],
        num_key_value_heads=z["kv_heads"],
        attention_multiplier=z["attn_scale"],
        num_experts_per_token=z["top_k"], expert_offset=0,
        residual_multiplier=z["residual_multiplier"],
        embedding_multiplier=z["embedding_multiplier"], rms_norm_eps=1e-5)


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
    summation and the chunked scan against the token recurrence."""
    pred, flat = tiny_f32
    assert max(_gaps(pred, flat)) < 1e-4


def test_detector_in_bfloat16_is_within_a_tolerance_the_fp8_control_breaks(
        tiny_f32):
    """bfloat16 compute on the same leaves against the float32 reference,
    and the reference at fp8, the nearest precision below, in the program's
    place: the tolerance, 0.017 of the maps' range, lies between the two
    readings, twice over the one and half the other (0.0062 and 0.034 on
    these weights; on two other seeds 0.0065 and 0.039, 0.0089 and
    0.073)."""
    _, flat = tiny_f32
    pred16, _ = _tiny_weights(compute="bfloat16")
    bf16, fp8 = max(_gaps(pred16, flat)), max(_gaps(pred16, flat, "fp8"))
    assert bf16 < BF16_TOLERANCE, bf16
    assert fp8 > 1.5 * BF16_TOLERANCE and fp8 > 2 * bf16, (bf16, fp8)


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
    assert gained["ssm.chunked_xla"] == 2
    assert gained["gqa.blocked_xla"] == 1
    assert gained["moe.ragged_dot"] == 3
    assert gained["experts_held"] == 3 * 4
    assert gained["moe.tokens"] == 2 * 16 * 3  # images, tokens, layers
    assert 0 < gained["moe.pairs_here"] < 2 * 16 * 3 * 3  # 4 of 8 held
    # in float32 the program chooses the experts the reference chooses
    routing = []
    _gaps(pred, flat, routing=routing)
    table = np.asarray(dets[ROUTING_TABLE_KEY])
    assert table.shape == (3, 2 * 16, 3)
    mine = np.sort(table.reshape(3, 2, 16, 3), -1)
    for b in range(2):
        for layer in range(3):
            own = np.sort(routing[3 * b + layer]["own"], -1)
            assert (mine[layer, b] == own).all(), (b, layer)
    attrs = [r for r in obs.spans() if r["name"] == "compile"][-1]["attrs"]
    assert attrs["trunk_ssm"] == "chunked_xla x2"
    assert attrs["trunk_gqa"] == "blocked_xla x1"
    assert attrs["trunk_moe"] == "ragged_dot x3"  # gmm on a TPU in bfloat16
    assert attrs["trunk_pairs"] == "xla_gather x3"  # row_dma there
    assert attrs["experts_held"] == 4
    assert "trunk_kda" not in attrs and "trunk_mla" not in attrs


def test_scopes_name_what_the_new_metrics_match(tiny_f32):
    import re

    pred, _ = tiny_f32
    images, exemplars = _inputs()
    text = jax.jit(pred.model.apply).lower(
        {"params": pred.params}, jnp.asarray(images),
        jnp.asarray(exemplars)).as_text(debug_info=True)
    for scope in ("backbone/layers_0/attn/scan/",
                  "backbone/layers_1/attn/scan/",
                  "backbone/layers_0/attn/conv/",
                  "backbone/layers_0/attn/in_proj/",
                  "backbone/layers_0/attn/norm/",
                  "backbone/layers_0/attn/out_proj/",
                  "backbone/layers_2/attn/softmax/",
                  "backbone/layers_2/attn/q_proj/",
                  "backbone/layers_0/ffn/router/",
                  "backbone/layers_0/ffn/dispatch/",
                  "backbone/layers_0/ffn/experts/",
                  "backbone/layers_0/ffn/shared/"):
        assert scope in text, scope
    # the attention layer has no scan and the state-space layers no softmax
    assert not re.search(r"layers_2/attn/scan/", text)
    assert not re.search(r"layers_[01]/attn/softmax/", text)


def test_the_registry_names_the_share_at_the_published_widths():
    """``build_backbone`` finds the published share by name; its sizes are
    the configuration file's; ``jax.eval_shape`` pins the issue's
    arithmetic: 461.2 M parameters a state-space layer and 400.9 M the
    attention layer, all bfloat16; a trunk states the sizes of its own
    kinds of layer only."""
    import json
    import os

    from benchmarks.drivers.offline_predict_granite_trunk import _trunk_sizes

    z = TRUNK_CONFIGS["granite4_h_small_share2"]
    assert not {"kda_head_dim", "qk_nope_dim", "kv_rank", "dense_width",
                "hc_mult"} & set(z)
    assert z["layers"] == (("ssm", "moe"),) * 9 + (("gqa", "moe"),)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "granite4h_fscd147.json")) as f:
        config = json.load(f)
    assert _trunk_sizes(config["model"]) == z
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size"]
    shapes = jax.eval_shape(
        build_lm_trunk("granite4_h_small_share2").init, jax.random.key(0),
        jnp.zeros((1, 64, 64, 3)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree.leaves(tree))
    assert round(count(shapes["layers_0"]) / 1e6, 1) == 461.2
    assert round(count(shapes["layers_8"]) / 1e6, 1) == 461.2
    assert round(count(shapes["layers_9"]) / 1e6, 1) == 400.9
    assert round(count(shapes["layers_0"]["attn"]) / 1e6, 2) == 102.29
    assert round(count(shapes["layers_9"]["attn"]) / 1e6, 2) == 41.94
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(shapes["layers_0"]))


@pytest.mark.parametrize("name", ["kimi_linear_a3b_share2",
                                  "xing4_a4b_stage6"])
def test_the_registered_trunks_state_no_size_of_the_new_kinds(name):
    """The new sizes default to what leaves the two registered trunks as
    they were: the sigmoid router with its bias, a shared expert as wide as
    an expert, the plain add, no multiplier on the embedding."""
    z = TRUNK_CONFIGS[name]
    assert not {"router", "shared_width", "residual_multiplier",
                "embedding_multiplier", "ssm_heads", "kv_heads"} & set(z)
    trunk = build_lm_trunk(name)
    assert trunk.router == "sigmoid_bias" and trunk.shared_width is None
    assert trunk.residual_multiplier is None
    assert trunk.embedding_multiplier is None
    shapes = jax.eval_shape(trunk.init, jax.random.key(0),
                            jnp.zeros((1, 64, 64, 3)))["params"]
    ffn = shapes["layers_1"]["ffn"]
    assert "bias" in ffn["router"]
    assert ffn["shared"]["gate"]["kernel"].shape[1] == z["expert_width"]

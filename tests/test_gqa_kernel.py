"""Grouped-query attention's Pallas kernel (``ops/causal_attn.py``) in the
Pallas interpreter, at both head widths the trunks have (64: a pair of heads
a slab; 128: one) and tiny everything else: against the blocked XLA form and
a plain masked softmax with the key-value heads repeated, its gradient, what
``gqa_formulation`` answers from what it observes, and that a trunk traced
with the kernel names it where the ``compile`` span and ``trunk.gqa.ms``
read it; and block diffusion's block-causal mask (``diffusion_block``) in
the kernel and the blocked form alike."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmr_tpu import diagnostics, obs
from tmr_tpu.models.lm_trunk import build_lm_trunk
from tmr_tpu.obs.compile import _trunk_attrs
from tmr_tpu.ops import causal_attn, rope
from tmr_tpu.ops.kda import rms_norm
from tmr_tpu.ops.causal_attn import (gqa_attention_blocked,
                                     gqa_attention_kernel, gqa_formulation)

BLOCK = 128  # a query and key block of the tests: the cells' are 512


def _heads(head_dim, group, slabs=1):
    """(query heads, key-value heads): the fewest that fill ``slabs`` slabs
    of k."""
    kv_heads = slabs * max(128 // head_dim, 2 if group == 1 else 1)
    return group * kv_heads, kv_heads


def _operands(seq, head_dim, group, batch=1, rotated=False, seed=0,
              slabs=1):
    """q, k, v flat as the projections write them; ``rotated``: q and k
    turned by their token's position as the mixer turns them."""
    heads, kv_heads = _heads(head_dim, group, slabs)
    ks = jax.random.split(jax.random.key(seed), 3)
    draw = lambda key, h: jax.random.normal(
        key, (batch, seq, h, head_dim)).astype(jnp.bfloat16)
    q, k, v = draw(ks[0], heads), draw(ks[1], kv_heads), draw(ks[2], kv_heads)
    if rotated:
        inv_freq = rope.default_inv_freq(head_dim, 10000.0)
        q, k = (rope.rotate(t, jnp.arange(seq), inv_freq) for t in (q, k))
    flat = lambda t: t.reshape(batch, seq, -1)
    return flat(q), flat(k), flat(v), heads, kv_heads


def _masked_softmax(q, k, v, heads, kv_heads, diffusion_block=1):
    """Float32, all S x S scores a head at once, the key-value heads
    repeated over their groups; query ``i`` sees key ``j`` where
    ``j // L <= i // L`` (``L`` 1: causal)."""
    b, s, _ = q.shape
    f32 = lambda t, h: np.asarray(t, np.float32).reshape(b, s, h, -1)
    q, k, v = f32(q, heads), f32(k, kv_heads), f32(v, kv_heads)
    k, v = (np.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    block = np.arange(s) // diffusion_block
    seen = block[None, :] <= block[:, None]
    scores = np.where(seen, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, -1)


def _norms(head_dim, seed):
    """q's and k's norm weights about the benchmark's 1.75, and eps."""
    ks = jax.random.split(jax.random.key(100 + seed))
    draw = lambda key: jax.random.uniform(key, (head_dim,), minval=1.25,
                                          maxval=2.25).astype(jnp.bfloat16)
    return draw(ks[0]), draw(ks[1]), 1e-6


def _xla_path(q, k, heads, kv_heads, norms, theta):
    """q and k as ``GQAMixer``'s XLA path hands them to the blocked form on
    the chip: each head's RMSNorm, then the default rotary, in float32 and
    rounded once."""
    b, s, _ = q.shape
    wq, wk, eps = norms
    out = []
    for t, h, w in ((q, heads, wq), (k, kv_heads, wk)):
        t = rms_norm(t.reshape(b, s, h, -1), w, eps)
        t = rope.rotate(t, jnp.arange(s),
                        rope.default_inv_freq(t.shape[-1], theta))
        out.append(t.astype(q.dtype).reshape(b, s, -1))
    return out


# "qknorm_rope": q and k as the projections wrote them, normed and turned by
# the kernel's prologue; "_block4" under diffusion blocks of 4
@pytest.mark.parametrize("rotated", ["nope", "rope", "qknorm_rope",
                                     "qknorm_rope_block4"])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("blocks", [1, 2, 4])
# two slabs of k and v: query heads reach a key-value slab past the first,
# as both cells' 8 key-value heads do
@pytest.mark.parametrize("group,slabs", [(4, 1), (1, 1), (4, 2)],
                         ids=["gqa4", "mha", "gqa4_two_slabs"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_kernel_equals_the_blocked_form_and_a_masked_softmax(
        head_dim, group, slabs, blocks, batch, rotated):
    """bfloat16 operands on both sides, rounded in other places: 0.01 of
    outputs of order 1 against each other, and the kernel no further from
    the float32 softmax than the blocked form is. With the prologue, the
    blocked form is the mixer's XLA path (norms, rotary, blocked form) and
    the softmax is taken over that path's q and k."""
    prologue = rotated.startswith("qknorm")
    n = 4 if rotated.endswith("_block4") else 1
    q, k, v, heads, kv_heads = _operands(blocks * BLOCK, head_dim, group,
                                         batch, rotated == "rope",
                                         seed=blocks, slabs=slabs)
    scale = head_dim ** -0.5
    norms, theta, seen = None, None, (q, k)
    if prologue:
        norms, theta = _norms(head_dim, blocks), 1e6
        seen = _xla_path(q, k, heads, kv_heads, norms, theta)
    got = np.asarray(gqa_attention_kernel(
        q, k, v, heads, kv_heads, scale, norms, theta, block=(BLOCK, BLOCK),
        diffusion_block=n), np.float32)
    blocked = np.asarray(gqa_attention_blocked(
        q, k, v, heads, kv_heads, scale, norms, theta, n), np.float32)
    want = _masked_softmax(*seen, v, heads, kv_heads, n)
    assert np.isfinite(got).all()
    span = np.abs(want).max()
    assert np.abs(got - blocked).max() < 1e-2 * span
    assert np.abs(got - want).max() < 5e-3 * span
    assert np.abs(got - want).max() < 1.5 * np.abs(blocked - want).max() \
        + 1e-3 * span
    if n == 1:
        # the first row's only key is itself: each head's output is its
        # key-value head's value there
        v0 = np.asarray(v, np.float32).reshape(
            batch, -1, kv_heads, head_dim)[:, 0]
        np.testing.assert_array_equal(
            got[:, 0], np.repeat(v0, group, axis=1).reshape(batch, -1))


@pytest.mark.parametrize("diffusion_block", [1, 4, 16])
@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_block_causal_kernel_equals_the_blocked_form_and_a_masked_softmax(
        head_dim, group, diffusion_block):
    """Two query blocks, rotated operands, bfloat16 on both sides: the
    kernel against the blocked form under the same mask (0.01 of outputs of
    order 1) and the float32 masked softmax, which the causal mask does not
    give."""
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, head_dim, group,
                                         rotated=True, seed=diffusion_block)
    scale = head_dim ** -0.5
    got = np.asarray(gqa_attention_kernel(
        q, k, v, heads, kv_heads, scale, block=(BLOCK, BLOCK),
        diffusion_block=diffusion_block), np.float32)
    blocked = np.asarray(gqa_attention_blocked(
        q, k, v, heads, kv_heads, scale, diffusion_block=diffusion_block),
        np.float32)
    want = _masked_softmax(q, k, v, heads, kv_heads, diffusion_block)
    assert np.isfinite(got).all()
    span = np.abs(want).max()
    assert np.abs(got - blocked).max() < 1e-2 * span
    assert np.abs(got - want).max() < 5e-3 * span
    if diffusion_block > 1:
        assert np.abs(want - _masked_softmax(q, k, v, heads, kv_heads)
                      ).max() > 0.05 * span


@pytest.mark.parametrize("form", ["kernel", "blocked"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_a_query_sees_its_own_diffusion_block_and_none_after_it(form,
                                                                 head_dim):
    """Diffusion blocks of 4: a later key of a query's own block moves its
    row, a key of the next block moves nothing at or before that block."""
    n = 4
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, head_dim, 8)
    scale = head_dim ** -0.5

    def run(k, v):
        if form == "kernel":
            o = gqa_attention_kernel(q, k, v, heads, kv_heads, scale,
                                     block=(BLOCK, BLOCK), diffusion_block=n)
        else:
            o = gqa_attention_blocked(q, k, v, heads, kv_heads, scale,
                                      diffusion_block=n)
        return np.asarray(o, np.float32)

    got = run(k, v)
    row = BLOCK + 5  # the second query block, second of its diffusion block
    key = row + 2  # a later token of the same block of 4
    inside = run(k.at[:, key].set(7.0), v.at[:, key].set(-3.0))
    assert np.abs(inside[:, row] - got[:, row]).max() > 0.1
    np.testing.assert_array_equal(inside[:, :row - 1], got[:, :row - 1])
    after = key + 1  # the first token of the next diffusion block
    moved = run(k.at[:, after].set(7.0), v.at[:, after].set(-3.0))
    np.testing.assert_array_equal(moved[:, :after], got[:, :after])
    assert np.abs(moved[:, after] - got[:, after]).max() > 0.1


@pytest.mark.parametrize("form", ["kernel", "blocked"])
def test_a_diffusion_block_of_one_lowers_as_the_causal_call(form):
    """``diffusion_block`` 1 is the causal mask as it was traced before the
    block mask existed: the same program text as a call that does not name
    it, and no integer division or bit mask in it; 4 lowers to another."""
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, 128, 8)
    scale = 128 ** -0.5
    if form == "kernel":
        call = lambda **kw: jax.make_jaxpr(
            lambda q, k, v: gqa_attention_kernel(
                q, k, v, heads, kv_heads, scale, block=(BLOCK, BLOCK),
                **kw))(
                    q, k, v)
    else:
        call = lambda **kw: jax.jit(lambda q, k, v: gqa_attention_blocked(
            q, k, v, heads, kv_heads, scale, **kw)).lower(q, k, v)
    text = lambda lowered: (str(lowered) if form == "kernel"
                            else lowered.as_text(debug_info=False))
    # an integer divide, remainder or bit mask, as each form prints one
    integer_op = (r"i32\[[\d,]*\] = (and|div|rem) " if form == "kernel"
                  else r"stablehlo\.(and|divide|remainder) [^\n]*i32>")
    causal = text(call())
    assert text(call(diffusion_block=1)) == causal
    assert not re.search(integer_op, causal)
    assert re.search(integer_op, text(call(diffusion_block=4)))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_a_query_block_sees_no_key_after_it(head_dim):
    """Keys and values after a query block changed: its rows do not."""
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, head_dim, 4)
    run = lambda k, v: np.asarray(gqa_attention_kernel(
        q, k, v, heads, kv_heads, head_dim ** -0.5, block=(BLOCK, BLOCK)),
        np.float32)
    got = run(k, v)
    again = run(k.at[:, BLOCK + 5:].set(7.0), v.at[:, BLOCK + 5:].set(-3.0))
    np.testing.assert_array_equal(got[:, :BLOCK + 5], again[:, :BLOCK + 5])
    assert np.abs(got[:, BLOCK + 5:] - again[:, BLOCK + 5:]).max() > 0.1


@pytest.mark.parametrize("head_dim", [64, 128])
def test_kernel_differentiates_through_the_blocked_form(head_dim):
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, head_dim, 4, batch=2)
    w = jax.random.normal(jax.random.key(9), q.shape)
    scale = head_dim ** -0.5

    def loss(fn, **extra):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, heads, kv_heads, scale, **extra).astype(jnp.float32)
            * w)

    got = jax.grad(loss(gqa_attention_kernel, block=(BLOCK, BLOCK)),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(loss(gqa_attention_blocked), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_kernel_with_its_prologue_differentiates_through_the_blocked_form(
        head_dim):
    """With the q/k norms and the rotary, the gradients of q, k, v and both
    norm weights are the blocked form's (which norms and turns first)."""
    q, k, v, heads, kv_heads = _operands(2 * BLOCK, head_dim, 4, batch=2)
    wq, wk, eps = _norms(head_dim, 0)
    w = jax.random.normal(jax.random.key(9), q.shape)
    scale = head_dim ** -0.5

    def loss(fn, **extra):
        return lambda q, k, v, wq, wk: jnp.sum(
            fn(q, k, v, heads, kv_heads, scale, (wq, wk, eps), 1e6,
               **extra).astype(jnp.float32) * w)

    got = jax.grad(loss(gqa_attention_kernel, block=(BLOCK, BLOCK)),
                   tuple(range(5)))(q, k, v, wq, wk)
    want = jax.grad(loss(gqa_attention_blocked), tuple(range(5)))(
        q, k, v, wq, wk)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.abs(np.asarray(got[3], np.float32)).max() > 0


@pytest.mark.parametrize("family", ["lfm2_24b_a2b_share8",
                                    "sdar_30b_a3b_share8"])
def test_the_mixer_norms_and_turns_in_the_kernel_as_its_xla_path_does(
        family, monkeypatch):
    """``GQAMixer`` at the trunk's heads (of 64 in pairs, groups of 4; of
    128, groups of 8 under diffusion blocks of 4), its norms, eps and
    rotary, 1,024 tokens, norm weights about 1.75: with the gate's yes the
    kernel is handed q and k as the projections wrote them and norms and
    turns them itself; the layer's output is its XLA path's (norms,
    rotary, blocked form) from the same leaves, within bfloat16's
    rounding."""
    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, GQAMixer

    z = TRUNK_CONFIGS[family]
    heads, kv_heads = (8, 2) if z["head_dim"] == 64 else (16, 2)
    mixer = GQAMixer(heads, kv_heads, z["head_dim"], z["attn_scale"],
                     dtype=jnp.bfloat16, qk_norm=z["qk_norm"],
                     rope_theta=z["rope_theta"],
                     norm_eps=z.get("norm_eps", 1e-5),
                     diffusion_block=z.get("diffusion_block", 1))
    x = jax.random.normal(jax.random.key(3), (1, 1024, 64), jnp.bfloat16)
    params = mixer.init(jax.random.key(4), x)
    params = jax.tree.map(lambda p: p * 1.75 if p.ndim == 1 else p, params)
    counts = lambda: obs.get_registry().counters("trunk.gqa.")
    before = counts()
    xla = np.asarray(mixer.apply(params, x), np.float32)
    monkeypatch.setattr(causal_attn, "gqa_kernel_ok", lambda *a: True)
    fused = np.asarray(mixer.apply(params, x), np.float32)
    block = "_block4" if family.startswith("sdar") else ""
    assert {n for n, c in counts().items() if c > before.get(n, 0)} == {
        f"blocked_xla_rope{block}", f"causal_kernel_qknorm_rope{block}"}
    span = np.abs(xla).max()
    assert np.isfinite(fused).all()
    assert np.abs(fused - xla).max() < 1e-2 * span


def test_the_gate_is_asked_with_the_mixers_prologue(monkeypatch):
    """Each trunk's attention asks the gate at its head width, diffusion
    block, q/k norms and rotary: the kernel it would run is the one
    checked."""
    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, GQAMixer

    asked = []
    monkeypatch.setattr(causal_attn, "gqa_kernel_ok",
                        lambda *a: asked.append(a) or False)
    for family, want in (("granite4_h_small_share2", (128, 1, False, False)),
                         ("lfm2_24b_a2b_share8", (64, 1, True, True)),
                         ("sdar_30b_a3b_share8", (128, 4, True, True))):
        z = TRUNK_CONFIGS[family]
        mixer = GQAMixer(z["num_heads"], z["kv_heads"], z["head_dim"],
                         z["attn_scale"], dtype=jnp.bfloat16,
                         qk_norm=z.get("qk_norm", False),
                         rope_theta=z.get("rope_theta"),
                         diffusion_block=z.get("diffusion_block", 1))
        x = jax.ShapeDtypeStruct((1, 4096, z["hidden"]), jnp.bfloat16)
        jax.eval_shape(mixer.init, jax.random.key(0), x)
        assert asked.pop() == want


@pytest.mark.parametrize("case,want", [
    ("lfm2", "causal_kernel"), ("granite", "causal_kernel"),
    ("the_1536_bucket", "causal_kernel"), ("mha_of_64", "causal_kernel"),
    ("float32", "blocked_xla"), ("head_dim_80", "blocked_xla"),
    ("odd_length", "blocked_xla"), ("too_long", "blocked_xla"),
    ("group_splits_a_slab", "blocked_xla"), ("cpu_backend", "blocked_xla"),
    ("partitioned", "blocked_xla"), ("sdar_block4", "causal_kernel"),
    ("diffusion_block_3", "blocked_xla"),
    ("diffusion_block_1024", "blocked_xla")])
def test_gqa_formulation_by_what_it_observes(case, want, monkeypatch):
    """The kernel where the type, the head geometry and the length allow it
    and its gate says yes; the blocked form everywhere else, and off a TPU
    and in a trace XLA partitions the gate says why."""
    if case not in ("cpu_backend", "partitioned"):  # there the gate answers
        monkeypatch.setattr(causal_attn, "gqa_kernel_ok",
                            lambda *a: True)
    seq, heads, kv_heads, head_dim, dtype = {
        "granite": (4096, 32, 8, 128, jnp.bfloat16),
        "the_1536_bucket": (9216, 32, 8, 64, jnp.bfloat16),
        "mha_of_64": (4096, 16, 16, 64, jnp.bfloat16),
        "float32": (4096, 32, 8, 64, jnp.float32),
        "head_dim_80": (4096, 32, 8, 80, jnp.bfloat16),
        "odd_length": (4000, 32, 8, 64, jnp.bfloat16),
        "too_long": (32768, 32, 8, 64, jnp.bfloat16),
        # three heads a group: heads 2 and 3 share a slab of q and not a
        # key-value head
        "group_splits_a_slab": (4096, 24, 8, 64, jnp.bfloat16),
        # groups of 8 heads of 128, diffusion blocks of 4
        "sdar_block4": (4096, 32, 4, 128, jnp.bfloat16),
    }.get(case, (4096, 32, 8, 64, jnp.bfloat16))
    # a block that is no power of two, or longer than a key block
    diffusion_block = {"sdar_block4": 4, "diffusion_block_3": 3,
                       "diffusion_block_1024": 1024}.get(case, 1)
    if case == "cpu_backend":  # a verdict another test left answers quietly
        causal_attn.gqa_kernel_ok.cache_clear()
    diagnostics.drain_gate_refusals()
    if case == "partitioned":
        with diagnostics.mosaic_kernels_off("a two-chip mesh"):
            got = gqa_formulation(seq, heads, kv_heads, head_dim, dtype)
    else:
        got = gqa_formulation(seq, heads, kv_heads, head_dim, dtype,
                              diffusion_block)
    causes = {(r["gate"], r["cause"])
              for r in diagnostics.drain_gate_refusals()}
    assert causes == {"cpu_backend": {("gqa_kernel_ok", "backend")},
                      "partitioned": {("gqa_kernel_ok", "partitioned")}
                      }.get(case, set())
    assert got == want


_TRUNKS = {
    # a state-space layer and a NoPE attention layer, heads of 128
    "granite4_h_small_share2": dict(
        hidden=64, layers=(("ssm", "moe"), ("gqa", "moe")), ssm_heads=2,
        ssm_head_dim=16, ssm_state=16, num_heads=4, kv_heads=1,
        expert_width=32, shared_width=32, num_experts=4, experts_held=4,
        top_k=2),
    # a short convolution and a rotary attention layer with q/k norms,
    # heads of 64
    "lfm2_24b_a2b_share8": dict(
        hidden=64, layers=(("short_conv", "dense"), ("gqa", "moe")),
        num_heads=8, kv_heads=2, dense_width=64, expert_width=32,
        num_experts=4, experts_held=4, top_k=2),
    # rotary attention with q/k norms under diffusion blocks of 4, groups of
    # 8 heads of 128
    "sdar_30b_a3b_share8": dict(
        hidden=64, layers=(("gqa", "moe"),) * 2, num_heads=16, kv_heads=2,
        expert_width=32, num_experts=4, experts_held=4, top_k=2),
}


@pytest.mark.parametrize("name,formulation", [
    ("granite4_h_small_share2", "causal_kernel"),
    ("lfm2_24b_a2b_share8", "causal_kernel_qknorm_rope"),
    ("sdar_30b_a3b_share8", "causal_kernel_qknorm_rope_block4")],
    ids=["granite4_h_small_share2-causal_kernel",
         "lfm2_24b_a2b_share8-causal_kernel_rope",
         "sdar_30b_a3b_share8-causal_kernel_rope_block4"])
def test_a_trunk_traced_with_the_kernel_names_it_where_the_metrics_read(
        name, formulation, monkeypatch):
    """With the gate's yes, every attention layer counts
    ``trunk.gqa.<kernel's name>`` (the ``compile`` span's ``trunk_gqa``;
    ``_qknorm`` where the norms ride in the kernel) and the lowered text
    carries the kernel's call under ``backbone/layers_N/attn/softmax/``, the
    scope ``trunk.gqa.ms`` owns time by, and nothing under the layer's
    ``rope/``, ``q_norm/`` or ``k_norm/``; without it, the blocked form's.
    The trunk at its heads' widths in bfloat16, tiny everything else, 1,024
    patches: two query blocks."""
    over = _TRUNKS[name]
    model = build_lm_trunk(name, dtype=jnp.bfloat16, **over)
    image = jax.ShapeDtypeStruct((1, 512, 512, 3), jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.key(0), image)
    gqa_layers = [i for i, (mixer, _) in enumerate(over["layers"])
                  if mixer == "gqa"]
    counts = lambda: obs.get_registry().counters("trunk.")

    def lowered():
        before = counts()
        text = jax.jit(model.apply).lower(params, image).as_text(
            debug_info=True)
        return _trunk_attrs(before, counts())["trunk_gqa"], text

    call = r"layers_(\d+)/attn/softmax/[^\"]*_gqa_kernel_fwd_impl"
    attr, text = lowered()
    blocked = formulation.replace("causal_kernel", "blocked_xla").replace(
        "_qknorm", "")
    assert attr == f"{blocked} x{len(gqa_layers)}"
    assert not re.search(call, text)
    prologue = r"layers_\d+/attn/(rope|q_norm|k_norm)/"
    nope = name == "granite4_h_small_share2"
    assert bool(re.search(prologue, text)) != nope
    monkeypatch.setattr(causal_attn, "gqa_kernel_ok", lambda *a: True)
    attr, text = lowered()
    assert attr == f"{formulation} x{len(gqa_layers)}"
    assert sorted({int(n) for n in re.findall(call, text)}) == gqa_layers
    for layer in gqa_layers:  # and nothing of the blocked form is left
        assert f"layers_{layer}/attn/softmax/bqgrd" not in text
    assert not re.search(prologue, text)


# ------------------------------------------------ the causal sliding window
def _band_softmax(q, k, v, heads, kv_heads, window):
    """Float32, all S x S scores a head at once, query ``i`` on keys
    ``i - W < j <= i``."""
    b, s, _ = q.shape
    f32 = lambda t, h: np.asarray(t, np.float32).reshape(b, s, h, -1)
    q, k, v = f32(q, heads), f32(k, kv_heads), f32(v, kv_heads)
    k, v = (np.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = np.where((j <= i) & (j > i - window), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, -1)


def _windowed(form, q, k, v, heads, kv_heads, window, **kw):
    scale = (q.shape[-1] // heads) ** -0.5
    fn = gqa_attention_kernel if form == "kernel" else gqa_attention_blocked
    return np.asarray(fn(q, k, v, heads, kv_heads, scale, window=window,
                         **kw), np.float32)


@pytest.mark.parametrize("window", [1, 128, 1024], ids=["w1", "w128",
                                                        "w_at_least_s"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("form", ["kernel", "blocked"])
def test_a_window_is_the_band_and_at_least_the_sequence_is_causal(
        form, head_dim, window):
    """1,024 tokens, groups of 8, rotated operands: under a window of 1
    each query sees itself alone (its key-value head's value), of 128 the
    band ``i - 128 < j <= i`` (the kernel in two query blocks of
    ``_WINDOW_BLOCK``, the second of which skips the first key block), and
    of the whole sequence the causal softmax, the very output of the form
    without a window (the kernel's in the same blocks); against the float32
    band softmax, 5e-3 of outputs of order 1."""
    q, k, v, heads, kv_heads = _operands(1024, head_dim, 8, rotated=True,
                                         seed=window)
    got = _windowed(form, q, k, v, heads, kv_heads, window)
    want = _band_softmax(q, k, v, heads, kv_heads, window)
    span = np.abs(want).max()
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 5e-3 * span
    if window == 1:
        v_own = np.repeat(np.asarray(v, np.float32).reshape(
            1, 1024, kv_heads, head_dim), 8, axis=2).reshape(1, 1024, -1)
        np.testing.assert_array_equal(got, v_own)
    if window == 1024:  # the causal form, the kernel in the same blocks
        same = ({"block": causal_attn._WINDOW_BLOCK} if form == "kernel"
                else {})
        causal = _windowed(form, q, k, v, heads, kv_heads, None, **same)
        np.testing.assert_array_equal(got, causal)
    else:
        assert np.abs(want - _masked_softmax(q, k, v, heads, kv_heads)
                      ).max() > 0.05 * span


@pytest.mark.parametrize("form", ["kernel", "blocked"])
def test_a_key_wholly_before_every_window_of_a_row_block_is_never_read(form):
    """Values of NaN in the first 256 keys (the kernel's first key block):
    under a window of 128 the rows from 512 on (the kernel's second and
    third query blocks of 512, the blocked form's third to sixth row blocks
    of 256) neither multiply nor read them, so they stay finite and as they
    were; a masked key that was read would carry its NaN through the
    values product."""
    q, k, v, heads, kv_heads = _operands(1536, 128, 8, rotated=True)
    got = _windowed(form, q, k, v, heads, kv_heads, 128)
    poisoned = _windowed(form, q, k, v.at[:, :256].set(jnp.nan), heads,
                         kv_heads, 128)
    np.testing.assert_array_equal(poisoned[:, 512:], got[:, 512:])
    assert np.isnan(poisoned[:, :256]).all()


@pytest.mark.parametrize("block,window,want", [
    ((256, 128), 128, 2 + 15 * 3), ((128, 128), 128, 1 + 31 * 2),
    ((512, 512), 128, 1 + 7 * 2), ((256, 128), 1, 2 * 16),
    ((256, 128), None, sum(2 * (i + 1) for i in range(16))),
    ((512, 256), 128, 2 + 7 * 3)])
def test_the_key_blocks_a_query_block_visits_are_pinned(block, window,
                                                        want):
    """At 4,096 tokens a head visits, over its query blocks, the key blocks
    from the one that holds the block's first query's first key to the
    diagonal's last: under the cell's window of 128 in blocks of
    ``_WINDOW_BLOCK`` (512, 256), 23 of the 128 a full grid holds and the
    72 the causal triangle visits in those blocks."""
    bq, bk = block
    spans = [causal_attn.key_blocks(i, bq, bk, window)
             for i in range(4096 // bq)]
    assert sum(end - first for first, end in spans) == want
    assert all(0 <= first < end for first, end in spans)
    if window is not None:  # no key block lies wholly before a window
        assert all(first * bk + bk > i * bq - window + 1 or first == 0
                   for i, (first, _) in enumerate(spans))
    assert causal_attn._WINDOW_BLOCK == (512, 256)


def test_the_windowed_kernel_differentiates_through_the_blocked_form():
    """With a window, the q/k norms and the rotary, the gradients are the
    blocked form's under the same window."""
    q, k, v, heads, kv_heads = _operands(512, 128, 8, batch=2)
    wq, wk, eps = _norms(128, 0)
    w = jax.random.normal(jax.random.key(9), q.shape)
    scale = 128 ** -0.5

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, heads, kv_heads, scale, (wq, wk, eps), 1e6,
               window=128).astype(jnp.float32) * w)

    got = jax.grad(loss(gqa_attention_kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(gqa_attention_blocked), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("form", ["kernel", "blocked"])
def test_no_window_lowers_as_the_causal_call(form):
    """``window`` None is the causal text as it was: the same program as a
    call that does not name it; a window of 128 lowers to another."""
    q, k, v, heads, kv_heads = _operands(512, 128, 8)
    scale = 128 ** -0.5
    fn = gqa_attention_kernel if form == "kernel" else gqa_attention_blocked
    text = lambda **kw: str(jax.make_jaxpr(
        lambda q, k, v: fn(q, k, v, heads, kv_heads, scale, **kw))(q, k, v))
    causal = text()
    assert text(window=None) == causal
    assert text(window=128) != causal


@pytest.mark.parametrize("case,want", [
    ("kexaone_sliding", "causal_kernel"), ("window_of_one", "causal_kernel"),
    ("window_under_block4", "blocked_xla"), ("window_zero", "blocked_xla"),
    ("float32", "blocked_xla"), ("odd_length", "blocked_xla")])
def test_a_windowed_formulation_by_what_it_observes(case, want, monkeypatch):
    """The kernel where the type, the head geometry, a length of whole
    ``_WINDOW_BLOCK`` blocks and a window of at least one token allow it and
    the gate, asked with the window, says yes; no window under a diffusion
    block."""
    asked = []
    monkeypatch.setattr(causal_attn, "gqa_kernel_ok",
                        lambda *a: asked.append(a) or True)
    window = {"window_of_one": 1, "window_zero": 0}.get(case, 128)
    seq = 4000 if case == "odd_length" else 4096
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    block = 4 if case == "window_under_block4" else 1
    got = gqa_formulation(seq, 64, 8, 128, dtype, block, True, True,
                          window=window)
    assert got == want
    if want == "causal_kernel":
        assert asked == [(128, 1, True, True, window)]


def test_the_gate_is_asked_with_the_window_and_the_full_layer_without():
    """K-EXAONE's sliding layers ask the gate at heads of 128 with q/k
    norms, rotary and the window of 128; its full layer with q/k norms and
    neither rotary nor window, the four arguments the other trunks ask."""
    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, GQAMixer

    z = TRUNK_CONFIGS["k_exaone_236b_a23b_share8"]
    asked = []
    x = jax.ShapeDtypeStruct((1, 4096, z["hidden"]), jnp.bfloat16)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(causal_attn, "gqa_kernel_ok",
                  lambda *a: asked.append(a) or False)
        for window, theta in ((z["sliding_window"], z["sliding_rope_theta"]),
                              (None, None)):
            mixer = GQAMixer(z["num_heads"], z["kv_heads"], z["head_dim"],
                             z["attn_scale"], dtype=jnp.bfloat16,
                             qk_norm=True, rope_theta=theta, window=window)
            jax.eval_shape(mixer.init, jax.random.key(0), x)
    assert asked == [(128, 1, True, True, 128), (128, 1, True, False)]

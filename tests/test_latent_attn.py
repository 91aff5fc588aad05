"""Latent attention's Pallas kernel (``ops/causal_attn.py``) in the Pallas
interpreter, at the kernel's own widths (128 + 64 / 128) and tiny
everything else: against the blocked XLA form and a plain masked softmax,
its gradient, what ``mla_formulation`` answers from what it observes, and
that a trunk traced with the kernel names it where the benchmark's
``mla_attn_roofline`` and the ``compile`` span read it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmr_tpu import diagnostics, obs
from tmr_tpu.models.lm_trunk import build_lm_trunk
from tmr_tpu.obs.compile import _trunk_attrs
from tmr_tpu.ops import causal_attn, rope
from tmr_tpu.ops.causal_attn import (latent_attention_blocked,
                                     latent_attention_kernel,
                                     mla_formulation)

BLOCK = 128  # a query and key block of the tests: the cells' are 512
SCALE = 192 ** -0.5
ROT = (tuple(rope.yarn_inv_freq(64, 10000, 64, 4096, 32, 1).tolist()), 1.1)


def _operands(seq, heads, k_pe="own", batch=1, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, seq, heads * 192)).astype(bf)
    kv = jax.random.normal(ks[1], (batch, seq, heads * 256)).astype(bf)
    pe = jax.random.normal(ks[2], (batch, seq, 64)).astype(bf)
    return q, kv, pe if k_pe == "own" else jnp.zeros_like(pe)


def _masked_softmax(q, kv, k_pe, heads, rot):
    """Float32, all S x S scores a head at once; the query turned as the
    program turns it (bfloat16 in, bfloat16 out)."""
    b, s, _ = q.shape
    q = q.reshape(b, s, heads, 192)
    if rot is not None:
        q = jnp.concatenate([q[..., :128], rope.rotate(
            q[..., 128:], *causal_attn._rotation(s, rot))], -1)
    f32 = lambda t: np.asarray(t, np.float32)
    q, kv = f32(q), f32(kv).reshape(b, s, heads, 256)
    k = np.concatenate([kv[..., :128], np.broadcast_to(
        f32(k_pe)[:, :, None, :], (b, s, heads, 64))], -1)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) * SCALE
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, kv[..., 128:]).reshape(b, s, -1)


@pytest.mark.parametrize("rot", [None, ROT], ids=["nope", "rope"])
@pytest.mark.parametrize("k_pe", ["own", "zero"])
@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("blocks,bq", [(1, 128), (2, 128), (4, 128),
                                       (2, 256)])
def test_kernel_equals_the_blocked_form_and_a_masked_softmax(blocks, bq,
                                                             heads, k_pe,
                                                             rot):
    """bfloat16 operands on both sides, rounded in other places: 0.01 of
    outputs of order 1 against each other, and the kernel no further from
    the float32 softmax than the blocked form is (both read 0.002-0.003)."""
    q, kv, pe = _operands(blocks * bq, heads, k_pe, seed=blocks)
    got = np.asarray(latent_attention_kernel(  # key blocks of 128
        q, kv, pe, heads, SCALE, rot, (bq, BLOCK)), np.float32)
    blocked = np.asarray(latent_attention_blocked(
        q, kv, pe, heads, SCALE, rot), np.float32)
    want = _masked_softmax(q, kv, pe, heads, rot)
    assert np.isfinite(got).all()
    span = np.abs(want).max()
    assert np.abs(got - blocked).max() < 1e-2 * span
    assert np.abs(got - want).max() < 5e-3 * span
    assert np.abs(got - want).max() < 1.5 * np.abs(blocked - want).max() \
        + 1e-3 * span
    # the first row's only key is itself: its output is its own value
    v0 = np.asarray(kv, np.float32).reshape(1, -1, heads, 256)[:, 0, :, 128:]
    np.testing.assert_array_equal(got[:, 0], v0.reshape(1, -1))


def test_a_query_block_sees_no_key_after_it():
    """Keys and values after a query block changed: its rows do not."""
    q, kv, pe = _operands(2 * BLOCK, 2)
    got = latent_attention_kernel(q, kv, pe, 2, SCALE, ROT, (BLOCK, BLOCK))
    kv2 = kv.at[:, BLOCK + 5:].set(7.0)
    pe2 = pe.at[:, BLOCK + 5:].set(-3.0)
    again = latent_attention_kernel(q, kv2, pe2, 2, SCALE, ROT,
                                    (BLOCK, BLOCK))
    np.testing.assert_array_equal(np.asarray(got[:, :BLOCK + 5], np.float32),
                                  np.asarray(again[:, :BLOCK + 5], np.float32))
    assert np.abs(np.asarray(got[:, BLOCK + 5:] - again[:, BLOCK + 5:],
                             np.float32)).max() > 0.1


@pytest.mark.parametrize("rot", [None, ROT], ids=["nope", "rope"])
def test_kernel_differentiates_through_the_blocked_form(rot):
    q, kv, pe = _operands(2 * BLOCK, 2, batch=2)
    w = jax.random.normal(jax.random.key(9), (2, 2 * BLOCK, 256))

    def loss(fn, *extra):
        return lambda q, kv, pe: jnp.sum(
            fn(q, kv, pe, 2, SCALE, rot, *extra).astype(jnp.float32) * w)

    got = jax.grad(loss(latent_attention_kernel, (BLOCK, BLOCK)),
                   (0, 1, 2))(q, kv, pe)
    want = jax.grad(loss(latent_attention_blocked), (0, 1, 2))(q, kv, pe)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("case,want", [
    ("the_cells", "causal_kernel"), ("the_1536_bucket", "causal_kernel"),
    ("odd_length", "blocked_xla"), ("too_long", "blocked_xla"),
    ("other_widths", "blocked_xla"), ("odd_heads", "blocked_xla"),
    ("float32", "blocked_xla"), ("cpu_backend", "blocked_xla"),
    ("partitioned", "blocked_xla")])
def test_mla_formulation_by_what_it_observes(case, want, monkeypatch):
    """The kernel where the type, the widths, the heads and the length
    allow it and its gate says yes; the blocked form everywhere else, and
    off a TPU and in a trace XLA partitions the gate says why."""
    if case not in ("cpu_backend", "partitioned"):  # there the gate answers
        monkeypatch.setattr(causal_attn, "latent_kernel_ok", lambda rope: True)
    seq, heads, widths, dtype = {
        "the_1536_bucket": (9216, 32, (128, 64, 128), jnp.bfloat16),
        "odd_length": (4000, 32, (128, 64, 128), jnp.bfloat16),
        "too_long": (32768, 32, (128, 64, 128), jnp.bfloat16),
        "other_widths": (4096, 32, (64, 64, 64), jnp.bfloat16),
        "odd_heads": (4096, 3, (128, 64, 128), jnp.bfloat16),
        "float32": (4096, 32, (128, 64, 128), jnp.float32),
    }.get(case, (4096, 32, (128, 64, 128), jnp.bfloat16))
    if case == "cpu_backend":  # a verdict another test left answers quietly
        causal_attn.latent_kernel_ok.cache_clear()
    diagnostics.drain_gate_refusals()
    if case == "partitioned":
        with diagnostics.mosaic_kernels_off("a two-chip mesh"):
            got = mla_formulation(seq, heads, *widths, dtype, True)
    else:
        got = mla_formulation(seq, heads, *widths, dtype, True)
    causes = {(r["gate"], r["cause"])
              for r in diagnostics.drain_gate_refusals()}
    assert causes == {"cpu_backend": {("latent_kernel_ok", "backend")},
                      "partitioned": {("latent_kernel_ok", "partitioned")}
                      }.get(case, set())
    assert got == want


def _rehearsal_trunk(name, **over):
    """A trunk of the named family at the kernel's head widths and tiny
    everything else, in bfloat16, its parameters as shapes; 1,024 patches,
    two query blocks."""
    sizes = dict(hidden=64, num_heads=2, kv_rank=32, dense_width=64,
                 expert_width=32, num_experts=4, experts_held=4, top_k=2)
    model = build_lm_trunk(name, dtype=jnp.bfloat16, **{**sizes, **over})
    image = jax.ShapeDtypeStruct((1, 512, 512, 3), jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.key(0), image)
    return model, params, image


@pytest.mark.parametrize("name,over,formulation", [
    ("xing4_a4b_stage6", dict(q_rank=32, layers=(
        ("mla", "dense"), ("mla", "moe"), ("mla", "moe"))),
     "causal_kernel_rope"),
    ("kimi_linear_a3b_share2", dict(kda_head_dim=16, layers=(
        ("kda", "dense"), ("mla", "moe"))), "causal_kernel")])
def test_a_trunk_traced_with_the_kernel_names_it_where_the_metrics_read(
        name, over, formulation, monkeypatch):
    """With the gate's yes, every latent layer counts
    ``trunk.mla.<kernel's name>`` (the ``compile`` span's ``trunk_mla``)
    and the lowered text carries the kernel's call under
    ``backbone/layers_N/attn/softmax/``, the scope ``mla_attn_roofline`` and
    ``trunk.mla.ms`` own time by; without it, the blocked form's."""
    model, params, image = _rehearsal_trunk(name, **over)
    mla_layers = [i for i, (mixer, _) in enumerate(over["layers"])
                  if mixer == "mla"]
    counts = lambda: obs.get_registry().counters("trunk.")

    def lowered():
        before = counts()
        text = jax.jit(model.apply).lower(params, image).as_text(
            debug_info=True)
        return _trunk_attrs(before, counts())["trunk_mla"], text

    call = r"layers_(\d+)/attn/softmax/[^\"]*_latent_kernel_fwd_impl"
    attr, text = lowered()
    blocked = formulation.replace("causal_kernel", "blocked_xla")
    assert attr == f"{blocked} x{len(mla_layers)}"
    assert not re.search(call, text)
    monkeypatch.setattr(causal_attn, "latent_kernel_ok", lambda rope: True)
    attr, text = lowered()
    assert attr == f"{formulation} x{len(mla_layers)}"
    assert sorted({int(n) for n in re.findall(call, text)}) == mla_layers
    for layer in mla_layers:  # and nothing of the blocked form is left
        assert f"layers_{layer}/attn/softmax/bhqk" not in text

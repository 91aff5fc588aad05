"""How the experts' results reach their tokens (``tmr_tpu/ops/moe.py``):
the two row kernels of the ``row_dma`` formulation, run here in the Pallas
interpreter (scalar prefetch, an operand left in HBM, DMA semaphores),
against ``combine`` as XLA runs it, and ``pairs_formulation`` by what it
observes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmr_tpu import diagnostics
from tmr_tpu.ops import moe

BF16 = jnp.bfloat16
D = 256  # one pair of 128-lane slabs


def _pairs(tokens, k, experts, seed=0, one_expert=False):
    """Tokens, choices without a repeat within a token, weights and the
    products' stand-in results, all bfloat16-exact."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((tokens, D), np.float32)
    if one_expert:  # every token picks the same k experts
        idx = np.tile(np.arange(2, 2 + k), (tokens, 1))
    else:
        idx = np.argsort(rng.standard_normal((tokens, experts)), -1)[:, :k]
    weights = np.abs(rng.standard_normal((tokens, k), np.float32)) + 0.1
    ys = rng.standard_normal((tokens * k, D), np.float32)
    return (jnp.asarray(x, BF16), jnp.asarray(idx, jnp.int32),
            jnp.asarray(weights), jnp.asarray(ys, BF16))


CASES = {
    # (tokens, k, experts, held, offset, every token picks the same)
    "8_of_half": (128, 8, 32, 16, 0, False),
    "4_of_all": (128, 4, 16, 16, 0, False),
    "10_of_the_upper_half": (256, 10, 24, 12, 12, False),
    "one_expert_held_one_not": (256, 2, 8, 3, 0, True),
    "none_held_here": (128, 4, 16, 4, 16, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_rows_hold_the_results_below_the_total(case):
    """The first kernel alone: every block that holds a pair held here is
    written as rows of words, word j a row's elements j and j + D / 2, and
    one block more as rows of zeros, which a pair not held here reads."""
    tokens, k, experts, held, offset, same = CASES[case]
    x, idx, weights, ys = _pairs(tokens, k, experts, one_expert=same)
    _, sizes, here, slot = moe.dispatch(x, idx, held, offset)
    total = int(sizes.sum())
    assert total == int(np.asarray(here).sum())
    assert (total == 0) == (case == "none_held_here")
    packed = moe._pack_impl(ys, sizes.sum())
    assert packed.shape == (tokens * k + 512, 1, D // 2)
    assert not np.asarray(packed[tokens * k:]).any()
    low, high = moe._halves(packed[:total, 0])
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([low, high], -1)),
        np.asarray(ys[:total], np.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_results_out_equal_combine_and_garbage_stays_out(case):
    tokens, k, experts, held, offset, same = CASES[case]
    x, idx, weights, ys = _pairs(tokens, k, experts, one_expert=same)
    _, sizes, here, slot = moe.dispatch(x, idx, held, offset)
    # rows past the groups' total hold whatever the product left there
    ys = ys.at[int(sizes.sum()):].set(jnp.nan)
    want = np.asarray(moe.combine(ys, weights, here, slot))
    got = np.asarray(moe.combine(ys, weights, here, slot, "row_dma"))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    if case == "none_held_here":
        assert not got.any()
    else:
        assert np.abs(got).max() > 1.0


def test_a_word_is_two_values_and_comes_back_bit_for_bit():
    x = _pairs(64, 2, 4)[0]
    word = moe._word(x[:, :D // 2], x[:, D // 2:])
    assert word.dtype == jnp.uint32
    low, high = moe._halves(word)
    assert low.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([low, high], -1)),
        np.asarray(x, np.float32))


def test_the_gradient_goes_through_the_xla_form():
    tokens, k, held = 128, 4, 8
    x, idx, weights, _ = _pairs(tokens, k, 16)
    mix = jnp.asarray(np.random.default_rng(3).standard_normal(
        (tokens, D), np.float32))

    def loss(formulation):
        def fn(x, weights):
            xs, _, here, slot = moe.dispatch(x, idx, held, 0)
            out = moe.combine((xs * 2).astype(BF16), weights, here, slot,
                              formulation)
            return jnp.sum(out * mix)

        return fn

    got = jax.grad(loss("row_dma"), argnums=(0, 1))(x, weights)
    want = jax.grad(loss("xla_gather"), argnums=(0, 1))(x, weights)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.abs(np.asarray(
            b, np.float32)).max() > 0
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)


@pytest.mark.parametrize("case,want", [
    ("granite4h", "row_dma"), ("kimilinear", "row_dma"),
    ("xing4", "row_dma"), ("the_1536_bucket", "row_dma"),
    ("float32", "xla_gather"), ("cpu_backend", "xla_gather"),
    ("odd_width", "xla_gather"), ("a_part_block_of_rows", "xla_gather"),
    ("a_part_block_of_tokens", "xla_gather"),
    ("more_rows_than_smem_holds", "xla_gather"),
    ("the_gate_says_no", "xla_gather"), ("partitioned", "xla_gather")])
def test_pairs_formulation_by_what_it_observes(case, want, monkeypatch):
    """The row kernels where a TPU, the type and the shapes allow them and
    their gate says yes; ``xla_gather`` everywhere else, and a trace XLA
    partitions says why."""
    if case != "partitioned":  # there the gate's own wrapper answers
        monkeypatch.setattr(moe, "pairs_kernels_ok",
                            lambda: case != "the_gate_says_no")
    if case != "cpu_backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, k, d, dtype = {
        "kimilinear": (16384, 8, 2304, BF16), "xing4": (16384, 4, 3584, BF16),
        "the_1536_bucket": (9216, 10, 4096, BF16),
        "float32": (8192, 10, 4096, jnp.float32),
        "odd_width": (8192, 10, 4096 + 128, BF16),
        "a_part_block_of_rows": (128, 2, 4096, BF16),
        "a_part_block_of_tokens": (64 + 32, 16, 4096, BF16),
        "more_rows_than_smem_holds": (4 * 9216, 8, 2304, BF16),
    }.get(case, (8192, 10, 4096, BF16))
    diagnostics.drain_gate_refusals()
    if case == "partitioned":
        with diagnostics.mosaic_kernels_off("a two-chip mesh"):
            got = moe.pairs_formulation(tokens * k, k, d, dtype)
        causes = {(r["gate"], r["cause"])
                  for r in diagnostics.drain_gate_refusals()}
        assert ("pairs_kernels_ok", "partitioned") in causes
    else:
        got = moe.pairs_formulation(tokens * k, k, d, dtype)
        assert not diagnostics.drain_gate_refusals()
    assert got == want


def test_off_a_tpu_the_gate_refuses_by_backend_and_a_kernel_by_shape():
    diagnostics.drain_gate_refusals()
    moe.pairs_kernels_ok.cache_clear()
    assert moe.pairs_kernels_ok() is False
    assert [(r["gate"], r["cause"])
            for r in diagnostics.drain_gate_refusals()] == [
                ("pairs_kernels_ok", "backend")]
    x, idx, weights, ys = _pairs(100, 2, 4)  # 200 rows: no whole block
    _, _, here, slot = moe.dispatch(x, idx, 2, 0)
    with pytest.raises(ValueError, match="pairs_formulation"):
        moe.combine(ys, weights, here, slot, "row_dma")
    x, idx, weights, ys = _pairs(256, 2, 4)
    _, _, here, slot = moe.dispatch(x, idx, 2, 0)
    with pytest.raises(ValueError, match="pairs_formulation"):
        moe.combine(ys.astype(jnp.float32), weights, here, slot, "row_dma")


def test_the_compile_span_names_how_the_pairs_travelled():
    """``trunk.pairs.<formulation>`` counts a layer a trace beside
    ``trunk.moe.<formulation>`` and becomes the span's ``trunk_pairs``."""
    from tmr_tpu.obs.compile import _trunk_attrs

    before = {"pairs.xla_gather": 4, "moe.gmm": 4, "experts_held": 144}
    after = {"pairs.xla_gather": 4, "pairs.row_dma": 10, "moe.gmm": 14,
             "experts_held": 504}
    attrs = _trunk_attrs(before, after)
    assert attrs == {"trunk_pairs": "row_dma x10", "trunk_moe": "gmm x10",
                     "experts_held": 36}

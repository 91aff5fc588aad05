"""``correct`` has to be able to fail in the cell whose backbone is the
SDAR-MoE trunk, as the sibling files show for the other trunks: the
rehearsal as stated is correct; the control at fp8 is not; and a fault
planted in each of the new mechanisms is not: the mask causal (diffusion
blocks of 1), diffusion blocks of 8, the q/k norms left out, the rotary
left out, and the chosen experts' weights not renormalised.

All at the rehearsal size (``sdar_tiny``, float32 compute: see the
configuration's ``rehearsal.why_float32``), on the CPU; the readings at the
cell's own size are chip runs, in PERF.md. ``FAULTS`` is what a scratch
script plants at the cell's own size, one ``benchmarks/run.py:main`` a
fault: each is planted where the timed path reaches it on the chip, in what
``GQAMixer`` calls or in the router, not in one formulation alone.
"""

import contextlib
import json
from unittest import mock

import pytest

from benchmarks import run
from benchmarks.tests.test_correct_lfm2_trunk import (_qk_norms_left_out,
                                                      _rotary_left_out)

CELL = "sdar30b_fscd147.eval"
ARGS = ["--workload", CELL, "--seconds", "1", "--trace", "0", "--rehearsal"]


def _result(capsys, extra, seed):
    rc = run.main(ARGS + ["--seed", str(seed)] + extra)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_program_as_stated_is_correct(capsys):
    out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"] == {} and out["rehearsal"] is True


@pytest.mark.parametrize("seed", [3000000011, 5, 2718281828])
def test_control_at_fp8_is_not_correct(capsys, seed):
    out = _result(capsys, ["--control", "fp8"], seed)
    assert out["correct"] is False, out["compared"]


@contextlib.contextmanager
def _diffusion_block(n: int):
    """Diffusion blocks of ``n`` in place of the configuration's, in all
    that ``GQAMixer`` asks: the formulation (and so the kernel's gate), the
    kernel and the blocked form."""
    from tmr_tpu.models import lm_trunk

    formulation = lm_trunk.gqa_formulation
    kernel = lm_trunk.gqa_attention_kernel
    blocked = lm_trunk.causal_attention_blocked
    with mock.patch.object(lm_trunk, "gqa_formulation",
                           lambda *a: formulation(*a[:5], n)), \
            mock.patch.object(
                lm_trunk, "gqa_attention_kernel",
                lambda *a, diffusion_block: kernel(*a, diffusion_block=n)), \
            mock.patch.object(
                lm_trunk, "causal_attention_blocked",
                lambda *a, diffusion_block: blocked(*a, diffusion_block=n)):
        yield


def _weights_not_renormalised():
    """The chosen experts weighed by their share of the softmax over all
    the experts, not renormalised over the chosen."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.ops import moe

    real = moe.route_softmax_topk

    def published_softmax_unrenormalised(x, kernel, top_k):
        idx, _ = real(x, kernel, top_k)
        probs = jax.nn.softmax(jnp.matmul(
            x.astype(jnp.float32), kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        return idx, jnp.take_along_axis(probs, idx, axis=-1)

    return mock.patch.object(moe, "route_softmax_topk",
                             published_softmax_unrenormalised)


#: fault -> (how it is planted, the number that has to pass its limit)
FAULTS = {"mask_causal": (lambda: _diffusion_block(1), "score_gap"),
          "diffusion_block_8": (lambda: _diffusion_block(8), "score_gap"),
          "qk_norms_left_out": (_qk_norms_left_out, "score_gap"),
          "rotary_left_out": (_rotary_left_out, "score_gap"),
          "weights_not_renormalised": (_weights_not_renormalised,
                                       "score_gap")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_a_new_mechanism_is_not_correct(capsys, fault):
    plant, number = FAULTS[fault]
    with plant():
        out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is False, (fault, out["compared"])
    read = out["compared"][number]
    assert read["value"] > read["limit"], (fault, out["compared"])

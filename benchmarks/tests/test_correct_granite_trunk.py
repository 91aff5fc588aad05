"""``correct`` has to be able to fail in the cell whose backbone is the
Granite 4.0-H trunk, as the sibling files show for the other trunks: the
rehearsal as stated is correct; the control at fp8 is not; and a fault
planted in each of the new mechanisms is not: the state not handed from
chunk to chunk, ``residual_multiplier`` left out, sigmoid scores in the
softmax's place, and ``attention_multiplier`` replaced by ``head_dim^-1/2``.

All at the rehearsal size (``granite4_tiny``, float32 compute: see the
configuration's ``rehearsal.why_float32``), on the CPU; the readings at the
cell's own size are chip runs, in PERF.md.
"""

import json
from unittest import mock

import pytest

from benchmarks import run

CELL = "granite4h_fscd147.eval"
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


def _state_not_handed_over():
    """Every chunk starts from what its own tokens left: H_0 = 0 a chunk."""
    from tmr_tpu.ops import ssd

    return mock.patch.object(ssd, "hand_over",
                             lambda state, decay_end, local: local)


def _residual_multiplier_left_out():
    """``x + f(norm(x))`` in place of ``x + 0.22 f(norm(x))``."""
    from tmr_tpu import models

    real = models.build_lm_trunk
    return mock.patch.object(
        models, "build_lm_trunk", lambda name, **kw: real(
            name, residual_multiplier=None, **kw))


def _sigmoid_in_the_softmaxs_place():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tmr_tpu.ops import moe

    def broken(x, kernel, top_k):
        logits = jnp.matmul(x.astype(jnp.float32),
                            kernel.astype(jnp.float32), precision=moe.HI)
        chosen, idx = lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.sigmoid(chosen)

    return mock.patch.object(moe, "route_softmax_topk", broken)


def _attention_multiplier_replaced():
    """``head_dim^-1/2``, the usual scale, where the family states its own
    ``attention_multiplier`` (1 / head_dim here, as 1 / 128 published)."""
    from tmr_tpu.models import lm_trunk

    real = lm_trunk.causal_attention_blocked
    return mock.patch.object(
        lm_trunk, "causal_attention_blocked",
        lambda q, k, v, scale: real(q, k, v, q.shape[-1] ** -0.5))


FAULTS = {"state_not_handed_over": _state_not_handed_over,
          "residual_multiplier_left_out": _residual_multiplier_left_out,
          "sigmoid_in_the_softmaxs_place": _sigmoid_in_the_softmaxs_place,
          "attention_multiplier_replaced": _attention_multiplier_replaced}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_a_new_mechanism_is_not_correct(capsys, fault):
    with FAULTS[fault]():
        out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is False, (fault, out["compared"])

"""``correct`` has to be able to fail in the cell whose backbone is the
Xing4.0 trunk, as ``test_correct_lm_trunk.py`` shows for Kimi-Linear's: the
rehearsal as stated is correct; the control at fp8 is not; and a fault
planted in each of the new mechanisms is not: Sinkhorn stopped after one
sweep, ``H_post`` without its factor 2, the rotation left out, YaRN's
``mscale^2`` left out of the softmax scale, and the coefficients computed
in bfloat16 where the configuration states float32.

All at the rehearsal size (``xing4_tiny``, float32 compute: see the
configuration's ``rehearsal.why_float32``), on the CPU; the readings at the
cell's own size are chip runs, in PERF.md.
"""

import json
from unittest import mock

import pytest

from benchmarks import run

CELL = "xing4_fscd147.eval"
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
    assert out["correct"] is False
    gap = out["compared"]["score_gap"]
    assert gap["value"] > gap["limit"]


def _one_sinkhorn_sweep():
    from tmr_tpu.ops import hyper_conn

    real = hyper_conn.sinkhorn
    return mock.patch.object(hyper_conn, "sinkhorn",
                             lambda logits, iters, eps: real(logits, 1, eps))


def _h_post_without_its_factor():
    from tmr_tpu.ops import hyper_conn

    real = hyper_conn.post_mix
    return mock.patch.object(
        hyper_conn, "post_mix",
        lambda x, y, h_post, h_res: real(x, y, 0.5 * h_post, h_res))


def _rotation_left_out():
    from tmr_tpu.ops import rope

    return mock.patch.object(rope, "rotate", lambda x, *a, **k: x)


def _mscale_left_out():
    from tmr_tpu.ops import rope

    return mock.patch.object(rope, "yarn_mscale", lambda *a, **k: 1.0)


def _coefficients_in_bfloat16():
    """The streams, the product, the norm's scalar and the Sinkhorn sweeps
    all rounded to bfloat16; the mixes still accumulate in float32."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.ops import hyper_conn

    bf = jnp.bfloat16

    def broken(x, phi, alpha, b_pre, b_post, b_res, iters, eps, clamp,
               norm_eps):
        n, c = x.shape[0], x.shape[-1]
        rows = x.reshape(n, -1, c).astype(bf)
        u = sum(jnp.matmul(rows[j], phi.reshape(n, c, -1)[j].astype(bf),
                           preferred_element_type=bf) for j in range(n))
        mean_sq = sum(jnp.mean(jnp.square(rows[j]), -1)
                      for j in range(n)) / n
        u = (u * jax.lax.rsqrt(mean_sq + bf(norm_eps))[:, None]).T
        a = alpha.astype(bf)
        col = lambda t: t.astype(bf).reshape(t.shape + (1,))
        h_pre = jax.nn.sigmoid(a[0] * u[:n] + col(b_pre))
        h_post = 2 * jax.nn.sigmoid(a[1] * u[n:2 * n] + col(b_post))
        h_res = hyper_conn.sinkhorn(
            jnp.clip(a[2] * u[2 * n:].reshape(n, n, -1) + col(b_res),
                     clamp[0], clamp[1]), iters, bf(eps))
        tokens = x.shape[1:-1]
        f32 = jnp.float32
        return (h_pre.reshape((n,) + tokens).astype(f32),
                h_post.reshape((n,) + tokens).astype(f32),
                h_res.reshape((n, n) + tokens).astype(f32))

    return mock.patch.object(hyper_conn, "coefficients", broken)


FAULTS = {"one_sinkhorn_sweep": _one_sinkhorn_sweep,
          "h_post_without_its_factor_2": _h_post_without_its_factor,
          "rotation_left_out": _rotation_left_out,
          "yarn_mscale_left_out": _mscale_left_out,
          "coefficients_in_bfloat16": _coefficients_in_bfloat16}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_a_new_mechanism_is_not_correct(capsys, fault):
    with FAULTS[fault]():
        out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is False, (fault, out["compared"])

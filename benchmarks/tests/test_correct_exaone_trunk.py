"""``correct`` has to be able to fail in the cell whose backbone is the
K-EXAONE trunk, as the sibling files show for the other trunks: the
rehearsal as stated is correct; the control at fp8 is not; and a fault
planted in each of the new mechanisms is not: the window ignored (full
causal attention on the sliding layers), the window widened by one, the
rotary applied on the full layer, pre-norms in place of the post-sublayer
norms, and a ``routed_scaling_factor`` of 1.

All at the rehearsal size (``k_exaone_tiny``, float32 compute: see the
configuration's ``rehearsal.why_float32``), on the CPU; the readings at the
cell's own size are chip runs, in PERF.md. ``FAULTS`` is what a scratch
script plants at the cell's own size, one ``benchmarks/run.py:main`` a
fault: each is planted where the timed path reaches it on the chip, in what
``GQAMixer`` and ``TrunkLayer`` call or in the router, not in one
formulation alone.
"""

import contextlib
import json
from unittest import mock

import pytest

from benchmarks import run

CELL = "kexaone_fscd147.eval"
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
def _window(change):
    """The sliding layers' window replaced by ``change(window)`` (None: no
    window, full causal attention) in all that ``GQAMixer`` asks: the
    formulation (and so the kernel's gate), the kernel and the blocked
    form. The counters keep the layer's own name."""
    from tmr_tpu.models import lm_trunk

    real = {name: getattr(lm_trunk, name) for name in (
        "gqa_formulation", "gqa_attention_kernel", "causal_attention_blocked")}

    def moved(name):
        def call(*a, window=None, **kw):
            if window is not None and change(window) is not None:
                kw["window"] = change(window)
            return real[name](*a, **kw)
        return call

    with contextlib.ExitStack() as stack:
        for name in real:
            stack.enter_context(mock.patch.object(lm_trunk, name,
                                                  moved(name)))
        yield


def _rotary_on_the_full_layer():
    """The full layers turned by the sliding layers' rotary: the family's
    code without its global NoPE."""
    from tmr_tpu.models import lm_trunk

    real, sliding = lm_trunk.GQAMixer, {}

    def turned(*a, **kw):
        # a sliding layer comes first and names the theta
        if kw.get("window") is not None:
            sliding["theta"] = kw["rope_theta"]
        else:
            kw["rope_theta"] = sliding["theta"]
        return real(*a, **kw)

    return mock.patch.object(lm_trunk, "GQAMixer", turned)


def _pre_norms():
    """Each sub-layer on its post-norm's output, ``x + f(norm(x))``: the
    same leaves, applied ahead of the sub-layer."""
    from tmr_tpu.models import lm_trunk

    return mock.patch.object(lm_trunk, "post_normed",
                             lambda x, f, norm: x + f(norm(x)))


def _routed_scale_one():
    """The chosen experts' weights renormalised and not scaled by 2.5."""
    from tmr_tpu.ops import moe

    real = moe.route
    return mock.patch.object(
        moe, "route", lambda x, kernel, bias, top_k, scale, eps=1e-20: real(
            x, kernel, bias, top_k, 1.0, eps))


#: fault -> (how it is planted, the number that has to pass its limit)
FAULTS = {"window_ignored": (lambda: _window(lambda w: None), "score_gap"),
          "window_widened_by_one": (lambda: _window(lambda w: w + 1),
                                    "score_gap"),
          "rotary_on_the_full_layer": (_rotary_on_the_full_layer,
                                       "score_gap"),
          "pre_norms_in_place": (_pre_norms, "score_gap"),
          "routed_scale_one": (_routed_scale_one, "route_moved")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_a_new_mechanism_is_not_correct(capsys, fault):
    plant, number = FAULTS[fault]
    with plant():
        out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is False, (fault, out["compared"])
    read = out["compared"][number]
    assert read["value"] > read["limit"], (fault, out["compared"])

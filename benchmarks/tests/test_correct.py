"""``correct`` has to be able to fail.

The control: the plain reference put in the program's place at the nearest
precision below the one the configurations state (fp8 for bfloat16) must come
out as not correct. The faults: the harness's look for a chip skipped, the
rest of a run driven as it is, with the timed path broken underneath, once
for each fault an inference cell can have: an answer altered where it is
produced (a score, a box, every box moved onto the first), every other answer
left out. (Suppression left out does not show at the rehearsal's 16 x 16 map,
where boxes of neighbouring peaks do not overlap.)

All at the rehearsal size, on the CPU; the readings at the cells' own size
are chip runs, in PERF.md.
"""

import json
from unittest import mock

import pytest

from benchmarks import run

CELL = "vitb_fscd147.eval"
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


def _alter(field, fn):
    """Break ``Predictor.__call__`` underneath the driver: its answer,
    altered where it is produced."""
    from tmr_tpu.inference import Predictor

    real = Predictor.__call__

    def broken(self, image, exemplars):
        dets = dict(real(self, image, exemplars))
        dets[field] = fn(dets[field])
        return dets

    return mock.patch.object(Predictor, "__call__", broken)


FAULTS = {
    "score_altered": (lambda: _alter("scores", lambda s: s * 0.8),
                      "score_gap"),
    "box_altered": (lambda: _alter("boxes", lambda b: b + 0.05), "box_gap"),
    "boxes_collapsed_onto_the_first": (
        lambda: _alter("boxes", lambda b: 0 * b + b[:, :1]), "nms_overlaps"),
    "every_other_answer_left_out": (
        lambda: _alter("valid", lambda v: v.at[:, ::2].set(False)),
        "missed_clear"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(capsys, fault):
    patch, number = FAULTS[fault]
    with patch():
        out = _result(capsys, [], seed=3000000011)
    assert out["correct"] is False, (fault, out["compared"])
    c = out["compared"][number]
    assert c["value"] > c["limit"], (fault, out["compared"])

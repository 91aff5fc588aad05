"""The two reducers that read the program's own spans
(``reducers/program_span_ms.py``, ``reducers/program_setup_s.py``).

On a ring filled by a rehearsal-size run they give positive numbers that
close the window; on a ring filled by hand they pick the traced window's
spans and not the warm-up's, subtract a child's time, and refuse a ring that
cannot be the window's; on an empty ring, as on a program without coarse
spans, they give nothing. All on the CPU; the readings at the cells' own
size are chip runs, in PERF.md.
"""

import json
import re

import pytest

from benchmarks import run
from tmr_tpu.diagnostics import run_outside_trace
from tmr_tpu.obs import tracing

CELL = "vitb_fscd147.eval"
MS = ("predict.stage_ms", "predict.dispatch_ms", "predict.fetch_wait_ms",
      "predict.unpack_ms")
SETUP = ("setup.gate_checks_s", "setup.program_load_s")


def _reduce(name: str, reduced: dict):
    spec = run.load_json("layer_metrics", name + ".json")
    return run.load_module("reducers", spec["reduce"]).reduce(reduced, spec)


def _record(name, t0, t1, scope="batch", **kw):
    tracing.add_span(name, t0, t1, scope=scope, **kw)


def test_a_rehearsal_run_leaves_spans_that_close_its_window(capsys):
    # a gate's self-check cannot run on the CPU: one stands in for it
    run_outside_trace(lambda: sum(range(10**5)), gate="stand_in_ok")
    rc = run.main(["--workload", CELL, "--seconds", "1", "--trace", "0",
                   "--rehearsal", "--seed", "3000000011"])
    assert rc == 0
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    window = re.search(r"in (\d+) batches over ([0-9.]+)s", captured.err)
    batches, seconds = int(window.group(1)), float(window.group(2))
    assert out["correct"] is True and batches >= 2
    reduced = {"batches": batches, "window_s": seconds}
    got = {name: _reduce(name, reduced) for name in MS + SETUP}
    assert all(v is not None and v > 0 for v in got.values()), got
    a_batch = 1e3 * seconds / batches
    assert 0.9 * a_batch <= sum(got[name] for name in MS) <= a_batch, got
    # the warm-up's calls are in the ring too, and are not the window's
    stages = [r for r in tracing.spans() if r["name"] == "predict.stage"]
    assert len(stages) > batches
    assert _reduce(MS[0], {"batches": len(stages), "window_s": seconds}) is None


def test_the_last_batches_spans_are_read_and_a_childs_time_is_not():
    _record("compile", 0.0, 4.0, scope="setup", span_id=900)
    _record("gate.selfcheck", 1.0, 2.5, scope="setup", parent=900, gate="g")
    _record("gate.selfcheck", 5.0, 5.5, scope="setup", gate="h")
    _record("predict.stage", 6.0, 9.0)          # the warm-up's, not read
    _record("predict.stage", 10.0, 10.010)
    _record("predict.dispatch", 10.010, 10.013)
    _record("predict.stage", 10.5, 10.530)
    _record("predict.dispatch", 10.530, 10.533)
    _record("compile", 10.530, 10.532, scope="setup")  # inside the window
    reduced = {"batches": 2, "window_s": 1.0}
    assert _reduce("predict.stage_ms", reduced) == pytest.approx(20.0)
    # the second dispatch holds a compile span of 2 of its 3 ms
    assert _reduce("predict.dispatch_ms", reduced) == pytest.approx(2.0)
    # set-up: what ended before the window, less what a child covers
    assert _reduce("setup.program_load_s", reduced) == pytest.approx(2.5)
    assert _reduce("setup.gate_checks_s", reduced) == pytest.approx(2.0)


def test_spans_that_cannot_be_the_windows_give_nothing():
    _record("predict.stage", 10.0, 10.010)
    _record("predict.stage", 12.0, 12.010)
    _record("compile", 1.0, 2.0, scope="setup")
    assert _reduce("predict.stage_ms", {"batches": 3, "window_s": 9.0}) is None
    assert _reduce("predict.stage_ms", {"batches": 2, "window_s": 1.0}) is None
    assert _reduce("setup.program_load_s",
                   {"batches": 2, "window_s": 1.0}) is None
    assert _reduce("predict.stage_ms",
                   {"batches": 2, "window_s": 9.0}) == pytest.approx(10.0)
    assert _reduce("predict.unpack_ms", {"batches": 2, "window_s": 9.0}) is None
    assert _reduce("setup.gate_checks_s",
                   {"batches": 2, "window_s": 9.0}) is None


@pytest.mark.parametrize("name", MS + SETUP)
def test_an_empty_ring_gives_nothing(name):
    assert tracing.spans() == []
    assert _reduce(name, {"batches": 2, "window_s": 1.0}) is None
    with open(run.ROOT + "/BENCHMARK.json") as f:
        entry = run.find(json.load(f)["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL, "vith_rpine.eval"]

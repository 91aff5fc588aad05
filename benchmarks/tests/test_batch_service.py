"""The reducer that reads what the program stamps on a batch's fetch
(``reducers/program_batch_service.py``: ``predict.stall_ms``,
``predict.service_max_over_min``, ``predict.d2h_ms``), on rings filled by
hand. The readings at the cells' own size are chip runs, in PERF.md."""

import json
import re

import pytest

from benchmarks import run
from tmr_tpu.obs import tracing

NAMES = ("predict.stall_ms", "predict.service_max_over_min",
         "predict.d2h_ms")
CELLS = ["vitb_fscd147.eval", "vith_rpine.eval", "kimilinear_fscd147.eval",
         "xing4_fscd147.eval", "granite4h_fscd147.eval"]
COPY = 0.0005


def _reduce(name: str, batches: int, window_s: float = 100.0):
    spec = run.load_json("layer_metrics", name + ".json")
    return run.load_module("reducers", spec["reduce"]).reduce(
        {"batches": batches, "window_s": window_s}, spec)


def _all(batches: int) -> list:
    return [_reduce(name, batches) for name in NAMES]


def _fetches(services, t0=10.0, capacity=9, late=False, least=None):
    """One ``predict.fetch`` a service time, back to back from ``t0``; each
    ends ``COPY`` after its answer became ready and carries as ``least_s``
    ``least``, else the smallest so far. Returns where they end."""
    so_far = float("inf")
    for service in services:
        ready = t0 + service
        so_far = min(so_far, service)
        known = {} if late else {
            "least_s": so_far if least is None else least}
        tracing.add_span("predict.fetch", t0, ready + COPY, scope="batch",
                         rows=4, program="run_single", capacity=capacity,
                         batch=1, ready_ts=ready, service_s=service,
                         late=late, **known)
        t0 = ready + COPY
    return t0


def test_a_sound_window_reads_nothing_waited():
    _fetches([0.3] * 8)
    stall, ratio, d2h = _all(8)
    assert stall == pytest.approx(0.0, abs=1e-9)
    assert ratio == pytest.approx(1.0)
    assert d2h == pytest.approx(1e3 * COPY)


def test_one_stalled_batch_of_eight():
    services = [0.4] * 8
    services[3] = 0.4 + 2.4
    _fetches(services)
    stall, ratio, _ = _all(8)
    assert stall == pytest.approx(1e3 * 2.4 / 8)
    assert ratio == pytest.approx(7.0)


def test_all_eight_stalled_are_held_to_what_the_program_saw_before():
    t = _fetches([0.45, 0.4])  # the warm-up's, before the window
    _fetches([0.656] * 8, t0=t + 5.0, least=0.4)
    stall, ratio, _ = _all(8)
    assert stall == pytest.approx(256.0)
    assert ratio == pytest.approx(1.64)


def test_two_programs_are_each_held_to_their_own_smallest():
    t = 10.0
    for service, capacity in [(0.3, 9), (0.5, 17), (0.3, 9), (0.5, 17),
                              (0.36, 9), (0.5, 17), (0.3, 9), (0.75, 17)]:
        t = _fetches([service], t0=t, capacity=capacity,
                     least={9: 0.3, 17: 0.5}[capacity])
    stall, ratio, _ = _all(8)
    assert stall == pytest.approx(1e3 * (0.06 + 0.25) / 8)
    assert ratio == pytest.approx(1.5)


def test_a_batch_the_host_noticed_late_is_made_up_by_the_next():
    """The stamps are the host's: noticed 0.1 s late, a batch reads 0.1 over
    and the next 0.1 under, and the device waited for neither."""
    _fetches([0.35, 0.35, 0.45, 0.25, 0.35, 0.35, 0.35, 0.35], least=0.35)
    stall, ratio, _ = _all(8)
    assert stall == pytest.approx(0.0, abs=1e-9)
    assert ratio == pytest.approx(0.45 / 0.35)


def test_the_windows_last_word_on_the_smallest_holds_for_all_its_batches():
    """The warm-up's batches paid their uploads in series and the window's
    first one does too: ``least_s`` is still falling when the window opens,
    and its first batch's wait is held to where it came to rest."""
    t = _fetches([0.33, 0.33])  # the warm-up's
    t = _fetches([0.33, 0.30], t0=t + 5.0, least=0.33)
    _fetches([0.30] * 6, t0=t, least=0.30)
    stall, ratio, _ = _all(8)
    assert stall == pytest.approx(1e3 * 0.03 / 8)
    assert ratio == pytest.approx(1.1)


def test_a_late_batch_is_left_out_of_the_wait_and_kept_in_the_copy():
    t = _fetches([0.3] * 4)
    t = _fetches([0.9], t0=t, late=True)
    _fetches([0.3, 0.33, 0.3], t0=t, least=0.3)
    stall, ratio, d2h = _all(8)
    assert stall == pytest.approx(1e3 * 0.03 / 7)
    assert ratio == pytest.approx(1.1)
    assert d2h == pytest.approx(1e3 * COPY)


def test_a_rehearsal_runs_ring_reads_all_three(capsys):
    """The program's own spans, not hand-made ones: every fetch of the
    window is stamped, and the three numbers come out of them."""
    rc = run.main(["--workload", CELLS[0], "--seconds", "1", "--trace", "0",
                   "--rehearsal", "--seed", "3000000019"])
    assert rc == 0
    window = re.search(r"in (\d+) batches over ([0-9.]+)s",
                       capsys.readouterr().err)
    batches, seconds = int(window.group(1)), float(window.group(2))
    fetches = [r["attrs"] for r in tracing.spans()
               if r["name"] == "predict.fetch"][-batches:]
    assert all(a["program"] == "run_single" and a["ready_ts"] > 0
               and a["service_s"] > 0 for a in fetches)
    got = [_reduce(name, batches, seconds) for name in NAMES]
    if all(a["late"] for a in fetches):  # this CPU outran its host
        assert got == [None, None, None]
        return
    stall, ratio, d2h = got
    assert ratio >= 1.0 and d2h > 0
    assert abs(stall) <= 1e3 * seconds / batches


def test_a_ring_without_ready_ts_gives_nothing():
    for k in range(8):  # as the parent's tree records them
        tracing.add_span("predict.fetch", 10.0 + k, 10.3 + k, scope="batch",
                         rows=4)
    assert _all(8) == [None, None, None]
    # and one such span among stamped ones is enough
    tracing.clear()
    t = _fetches([0.3] * 7)
    tracing.add_span("predict.fetch", t, t + 0.3, scope="batch", rows=4)
    assert _all(8) == [None, None, None]
    assert _reduce("predict.fetch_wait_ms", 8) == pytest.approx(300.0,
                                                                rel=0.01)


def test_too_few_spans_or_all_late_give_nothing():
    _fetches([0.3] * 7)
    assert _all(8) == [None, None, None]
    assert None not in _all(7)
    assert [_reduce(name, 7, window_s=1.0) for name in NAMES] == [None] * 3
    tracing.clear()
    _fetches([0.3] * 8, late=True)
    assert _all(8) == [None, None, None]


def test_the_two_halves_of_a_fetch_abut_at_ready_ts():
    _fetches([0.3, 0.4])
    tracing.add_span("predict.unpack", 20.0, 20.001, scope="batch", rows=4)
    rows = tracing.spans_ns()
    assert [r[0] for r in rows] == 2 * [
        "predict.fetch", "predict.fetch.wait", "predict.fetch.copy"] + [
        "predict.unpack"]
    for whole, wait, copy in (rows[0:3], rows[3:6]):
        assert whole[1] == wait[1] < wait[2] == copy[1] < copy[2] == whole[2]
        assert copy[2] - copy[1] == pytest.approx(1e9 * COPY, rel=1e-3)
    assert tracing.spans_ns(("predict.fetch.wait",)) == [rows[1], rows[4]]
    # the accepted metric still reads the whole span
    assert _reduce("predict.fetch_wait_ms", 2) == pytest.approx(
        1e3 * (0.35 + COPY))


@pytest.mark.parametrize("name", NAMES)
def test_an_empty_ring_gives_nothing_and_every_cell_lists_the_metric(name):
    assert tracing.spans() == []
    assert _reduce(name, 2) is None
    with open(run.ROOT + "/BENCHMARK.json") as f:
        entry = run.find(json.load(f)["per_layer"], name, "metric")
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "Predictor host path"
    assert (entry["moves"], entry["better"]) == ("img_per_s", "lower")

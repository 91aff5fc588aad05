"""The program times its own host path and set-up (obs/tracing.py scopes).

Coarse spans (``scope="setup"`` / ``"batch"``) are recorded with
``TMR_TRACE`` off, at the Predictor's seams (``predict.stage`` /
``predict.dispatch`` / ``predict.fetch`` / ``predict.unpack``, the four of
one batch joined by ``batch``; the fetch stamps when the answer became
ready and reports a batch that waited), at the
set-up seams (``compile``, ``gate.selfcheck``) and once a batch at the serve
pipeline's four batch stages; request-scope spans stay behind the knob and
name the batch that carried them. ``spans_ns`` hands them over on the clock
``benchmarks/trace.py:Spans`` reads.
"""

import inspect
import time

import numpy as np
import pytest

from tmr_tpu import obs
from tmr_tpu.diagnostics import TRACE_SERVE_STAGES, run_outside_trace

PREDICT = ("predict.stage", "predict.dispatch", "predict.fetch",
           "predict.unpack")
BATCH_STAGES = ("serve.batch_assemble", "serve.stage", "serve.execute",
                "serve.postprocess")
SIZE = 64


@pytest.fixture(autouse=True)
def _tracing_off_and_drained():
    obs.configure(enabled=False)
    obs.clear()
    yield
    obs.configure(enabled=False)
    obs.clear()


@pytest.fixture(scope="module")
def pred():
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=SIZE,
                 compute_dtype="float32", batch_size=1)
    p = Predictor(cfg)
    p.init_params(seed=0, image_size=SIZE)
    return p


def _batch(rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((rows, SIZE, SIZE, 3)).astype(np.float32)
    exemplars = np.tile(np.asarray([[[0.4, 0.4, 0.6, 0.6]]], np.float32),
                        (rows, 1, 1))
    return images, exemplars


def _end(rec: dict) -> float:
    return rec["ts"] + rec["dur"]


def test_two_rounds_leave_two_ordered_spans_of_each_seam(pred):
    from tmr_tpu.inference import detections_to_numpy

    pred.invalidate_compiled()  # so that this test pays the first call
    obs.clear()
    assert not obs.tracing_enabled()
    images, exemplars = _batch(2)
    for _ in range(2):
        assert len(detections_to_numpy(pred(images, exemplars))) == 2
    spans = obs.spans()
    by_name = {n: [r for r in spans if r["name"] == n] for n in PREDICT}
    assert all(len(v) == 2 for v in by_name.values()), by_name
    assert all(r["scope"] == "batch" and r["attrs"]["rows"] == 2
               for v in by_name.values() for r in v)
    for n in ("predict.stage", "predict.dispatch"):
        assert all(r["attrs"]["program"] == "run_single"
                   and r["attrs"]["capacity"] >= 1 for r in by_name[n])
    # ordered and non-overlapping in a round, and round after round
    order = [by_name[n][k] for k in range(2) for n in PREDICT]
    for a, b in zip(order, order[1:]):
        assert _end(a) <= b["ts"], (a["name"], b["name"])
    # one first call: a set-up span inside the first dispatch, its child
    compiles = [r for r in spans if r["name"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["scope"] == "setup"
    first = by_name["predict.dispatch"][0]
    assert compiles[0]["parent"] == first["span"]
    assert first["ts"] <= compiles[0]["ts"] and _end(compiles[0]) <= _end(first)
    assert compiles[0]["attrs"]["cause"] in ("cold", "key-change")
    assert {r["name"] for r in spans} == set(PREDICT) | {"compile"}


def test_request_scope_stays_behind_the_knob():
    assert not obs.tracing_enabled()
    assert obs.span("a") is obs.span("b", key="value")  # the shared no-op
    obs.add_span("queue", 1.0, 2.0)
    assert obs.spans() == []
    with obs.span("coarse", scope="batch", rows=3):
        with obs.span("request"):
            pass
    obs.add_span("stamped", 1.0, 2.0, scope="setup", gate="g")
    got = {r["name"]: r for r in obs.spans()}
    assert sorted(got) == ["coarse", "stamped"]
    assert got["coarse"]["scope"] == "batch" and got["coarse"]["trace"] == ""
    assert got["stamped"]["attrs"] == {"gate": "g"}
    obs.configure(enabled=True, annotate=False)
    with obs.span("request"):
        pass
    assert obs.spans()[-1]["scope"] == "request"
    assert all(e["args"]["scope"] for e in obs.chrome_trace()["traceEvents"]
               if e["ph"] == "X")


def test_a_program_span_lies_inside_the_benchmark_span_around_it():
    from benchmarks.trace import Spans

    bench = Spans()
    with bench("bench.dispatch"):
        with obs.span("predict.stage", scope="batch"):
            with obs.span("other", scope="batch"):
                pass
    (name, t0, t1), = bench.records
    rows = obs.spans_ns(("predict.stage",))
    assert [r[0] for r in rows] == ["predict.stage"]
    assert t0 <= rows[0][1] <= rows[0][2] <= t1
    assert [r[0] for r in obs.spans_ns()] == ["predict.stage", "other"]
    assert all(isinstance(v, int) for r in obs.spans_ns() for v in r[1:])


def test_a_self_check_asked_in_a_first_call_is_the_compile_spans_child():
    def program(x):
        return run_outside_trace(lambda: x + 1, gate="some_gate_ok")

    fn = obs.track_compile(program, "test_kind_program_spans", ("k", 1))
    assert fn(1) == 2 and fn(2) == 3
    got = {}
    for r in obs.spans():
        got.setdefault(r["name"], []).append(r)
    assert len(got["compile"]) == 1 and len(got["predict.dispatch"]) == 2
    assert len(got["gate.selfcheck"]) == 2
    first, later = got["gate.selfcheck"]
    assert first["scope"] == "setup"
    assert first["attrs"] == {"gate": "some_gate_ok"}
    assert first["parent"] == got["compile"][0]["span"]
    assert first["tid"] == got["compile"][0]["tid"]  # the calling thread
    assert later["parent"] == got["predict.dispatch"][1]["span"]
    assert "rows" not in got["predict.dispatch"][0]["attrs"]


@pytest.mark.parametrize("attr,name,blocks", [
    ("win_attn", "packed", 8), ("global_attn", "packed", 4)])
def test_the_compile_span_names_the_formulations_it_traced(
    attr, name, blocks
):
    """What ``vit.win_attn.*`` and ``vit.global_attn.*`` counted during a
    program's first call is on its ``compile`` span, and a later call adds
    nothing to it."""
    def program(x):
        for _ in range(blocks):
            obs.counter(f"vit.{attr}.{name}").inc()
        return x

    obs.counter(f"vit.{attr}.{name}").inc()  # another program's block
    fn = obs.track_compile(program, f"test_kind_{attr}", ("k", 2))
    assert fn(1) == 1 and fn(2) == 2
    (span,) = [r for r in obs.spans() if r["name"] == "compile"
               and r["attrs"]["kind"] == f"test_kind_{attr}"]
    assert span["attrs"][attr] == name
    assert span["attrs"][f"{attr}_blocks"] == blocks
    other = "global_attn" if attr == "win_attn" else "win_attn"
    assert other not in span["attrs"]


def _serve(pred, n: int, seed: int):
    from tmr_tpu.serve import ServeEngine

    _, exemplars = _batch(1)
    with ServeEngine(pred, batch=2, max_wait_ms=10, exemplar_cache=0,
                     feature_cache=0) as eng:
        futs = [eng.submit(_batch(1, seed + i)[0][0], exemplars[0])
                for i in range(n)]
        for f in futs:
            f.result(timeout=600)
    return obs.spans()


def _stages_by_batch(spans: list) -> dict:
    out = {}
    for r in spans:
        if r["scope"] == "batch" and r["name"] in BATCH_STAGES:
            out.setdefault(r["attrs"]["batch"], []).append(r)
    return out


def test_serve_records_its_four_batch_stages_once_a_batch(pred):
    spans = _serve(pred, 5, seed=10)
    assert all(r["scope"] in ("batch", "setup") for r in spans)
    batches = _stages_by_batch(spans)
    assert len(batches) >= 3  # five requests under a bound of two
    served = 0
    for bid, recs in batches.items():
        assert sorted(r["name"] for r in recs) == sorted(BATCH_STAGES), bid
        shapes = {(r["attrs"]["rows"], r["attrs"]["slots"],
                   r["attrs"]["bucket"], r["attrs"]["device"]) for r in recs}
        assert len(shapes) == 1
        rows, slots = recs[0]["attrs"]["rows"], recs[0]["attrs"]["slots"]
        assert 1 <= rows <= slots <= 2
        served += rows
    assert served == 5
    # the program's own dispatch span comes from the one wrapper
    assert sum(r["name"] == "predict.dispatch" for r in spans) == len(batches)


def test_traced_request_spans_name_the_batch_that_carried_them(pred):
    obs.configure(enabled=True, annotate=False)
    obs.clear()
    spans = _serve(pred, 4, seed=20)
    batches = _stages_by_batch(spans)
    assert all(len(recs) == 4 for recs in batches.values())  # still once
    waits = [r for r in spans if r["name"] == "serve.queue_wait"]
    assert len(waits) == 4
    assert all(r["attrs"]["batch"] in batches for r in waits)
    by_trace = {}
    for r in spans:
        if r["scope"] == "request" and r["name"].startswith("serve."):
            by_trace.setdefault(r["trace"], []).append(r)
    assert len(by_trace) == 4
    for recs in by_trace.values():
        assert {r["name"] for r in recs} == set(TRACE_SERVE_STAGES)
        named = {r["attrs"]["batch"] for r in recs
                 if r["name"] != "serve.submit"}
        assert len(named) == 1 and named <= set(batches)


KINDS = {
    "single": lambda p: p._get_fn(9),
    "multi": lambda p: p._get_multi_fn(9, 2),
    "multi_batched": lambda p: p._get_multi_batched_fn(9, 2),
    "backbone": lambda p: p._get_backbone_fn(),
    "heads": lambda p: p._get_heads_fn(9, SIZE),
    "gallery": lambda p: p._get_gallery_fn(9, 2, 2),
    "gallery_heads": lambda p: p._get_gallery_heads_fn(9, 2, 2, SIZE),
    "gallery_prefilter": lambda p: p._get_gallery_prefilter_fn(2, 2),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_each_program_kind_has_a_module_name_of_its_own(pred, kind):
    """A trace names a program by its jitted function (module
    ``jit_<name>``): every kind's starts with ``run`` (the benchmark
    driver's ``run_prefix``) and no two kinds share one."""
    name = lambda k: inspect.unwrap(
        KINDS[k](pred), stop=lambda f: hasattr(f, "lower")).__name__
    assert name(kind) == "run_" + kind
    assert sum(name(k) == name(kind) for k in KINDS) == 1


def _pipelined(pred, n: int) -> list:
    """``n`` batches with one in flight, as the offline drivers run them:
    batch k is fetched after batch k + 1 was dispatched."""
    from tmr_tpu.inference import detections_to_numpy

    images, exemplars = _batch(2)
    detections_to_numpy(pred(images, exemplars))  # the first call, apart
    obs.clear()
    pending = None
    for _ in range(n):
        dets = pred(images, exemplars)
        if pending is not None:
            detections_to_numpy(pending)
        pending = dets
    detections_to_numpy(pending)
    return obs.spans()


def test_the_four_spans_of_a_batch_share_its_id(pred):
    spans = _pipelined(pred, 3)
    by_batch = {}
    for r in spans:
        by_batch.setdefault(r["attrs"]["batch"], []).append(r["name"])
    assert len(by_batch) == 3 and list(by_batch) == sorted(by_batch)
    assert all(sorted(names) == sorted(PREDICT)
               for names in by_batch.values()), by_batch
    # the fetch of batch k came after the dispatch of batch k + 1 ...
    names = [(r["name"], r["attrs"]["batch"]) for r in spans]
    first, second = sorted(by_batch)[:2]
    assert names.index(("predict.dispatch", second)) \
        < names.index(("predict.fetch", first))
    # ... and still names its own batch, program and bucket
    dispatch = {r["attrs"]["batch"]: r for r in spans
                if r["name"] == "predict.dispatch"}
    for r in spans:
        if r["name"] == "predict.fetch":
            mine = dispatch[r["attrs"]["batch"]]
            assert r["attrs"]["program"] == "run_single"
            assert r["attrs"]["capacity"] == mine["attrs"]["capacity"]
            assert _end(mine) <= r["attrs"]["ready_ts"]


def test_ready_ts_lies_inside_the_fetch_and_no_span_does(pred):
    spans = _pipelined(pred, 3)
    fetches = [r for r in spans if r["name"] == "predict.fetch"]
    assert len(fetches) == 3
    for r in fetches:
        attrs = r["attrs"]
        assert r["ts"] <= attrs["ready_ts"] <= _end(r)
        assert 0.0 < attrs["service_s"] and attrs["late"] in (True, False)
        # a child span would be taken off predict.fetch_wait_ms
        inside = [c["name"] for c in spans if c is not r
                  and (c["parent"] == r["span"]
                       or (c["tid"] == r["tid"] and r["ts"] <= c["ts"]
                           and _end(c) <= _end(r)))]
        assert inside == []
    # the two halves of a fetch, as rows for ``trace.gaps_add``
    rows = obs.spans_ns(("predict.fetch", "predict.fetch.wait",
                         "predict.fetch.copy"))
    assert [r[0] for r in rows] == 3 * ["predict.fetch", "predict.fetch.wait",
                                        "predict.fetch.copy"]
    for whole, wait, copy in zip(rows[::3], rows[1::3], rows[2::3]):
        assert whole[1] == wait[1] <= wait[2] == copy[1] <= copy[2] == whole[2]
    assert [r[0] for r in obs.spans_ns(("predict.fetch",))] \
        == 3 * ["predict.fetch"]


@pytest.mark.parametrize("remake", ["numpy", "by_hand"])
def test_an_answer_the_table_does_not_know_is_fetched_as_before(pred, remake):
    import jax.numpy

    from tmr_tpu.inference import detections_to_numpy

    images, exemplars = _batch(2)
    dets = pred(images, exemplars)
    other = (jax.tree.map(np.asarray, dets) if remake == "numpy"
             else {k: jax.numpy.array(v) for k, v in dets.items()})
    obs.clear()
    unknown = detections_to_numpy(other)
    known = detections_to_numpy(dets)
    for a, b in zip(unknown, known):
        assert all(np.array_equal(a[k], b[k]) for k in b)
    (first, second) = [r for r in obs.spans() if r["name"] == "predict.fetch"]
    assert first["attrs"] == {"rows": 2}
    assert second["attrs"]["batch"] >= 1 and "ready_ts" in second["attrs"]
    unpacks = [r for r in obs.spans() if r["name"] == "predict.unpack"]
    assert unpacks[0]["attrs"] == {"rows": 2}
    assert unpacks[1]["attrs"] == {"rows": 2,
                                   "batch": second["attrs"]["batch"]}
    # taken once: a second fetch of the same answer knows nothing of it
    detections_to_numpy(dets)
    assert obs.spans()[-2]["attrs"] == {"rows": 2}


def _tiny_program(kind: str, capacity: int = 9):
    """A tracked program whose answer is on the host already: its batches'
    times are what the patched readiness call makes them."""
    def run_tiny(rows):
        return {"boxes": np.zeros((rows, 3, 4), np.float32),
                "scores": np.zeros((rows, 3), np.float32),
                "refs": np.zeros((rows, 3, 2), np.float32),
                "valid": np.ones((rows, 3), bool)}

    return obs.track_compile(run_tiny, kind, ("k", capacity),
                             bucket={"capacity": capacity})


def test_a_dispatch_no_stage_came_before_mints_its_own_id():
    fn = _tiny_program("test_kind_bare_dispatch")
    fn(1), fn(1)
    first, second = [r["attrs"]["batch"] for r in obs.spans()
                     if r["name"] == "predict.dispatch"]
    assert first < second
    assert not any(r["name"] == "predict.stage" for r in obs.spans())


def test_answers_never_fetched_roll_off_the_table():
    from tmr_tpu.obs import compile as obs_compile

    fn = _tiny_program("test_kind_never_fetched")
    answers = [fn(1) for _ in range(1000)]
    assert len(obs_compile._ANSWERS) == obs_compile._MAX_ANSWERS
    assert obs.take_answer(answers[0]) is None
    last = obs.take_answer(answers[-1])
    assert last["program"] == "run_tiny" and last["capacity"] == 9
    assert obs.take_answer(answers[-1]) is None
    assert len(obs_compile._ANSWERS) == obs_compile._MAX_ANSWERS - 1


def _fetch_batches(monkeypatch, waits: list, late: bool = False,
                   fn=None) -> list:
    """Fetch one batch of a tiny program (``fn``, else a new one) a wait,
    each made to take that many seconds to become ready; the
    ``predict.fetch`` spans."""
    from tmr_tpu import inference

    monkeypatch.setattr(inference, "_CLOCK", inference._BatchClock())
    pending = iter(waits)

    def wait_ready(arrays):
        time.sleep(next(pending))
        return late

    monkeypatch.setattr(inference, "_wait_ready", wait_ready)
    fn = fn or _tiny_program("test_kind_stall")
    for _ in waits:
        inference.detections_to_numpy(fn(2))
    return [r for r in obs.spans() if r["name"] == "predict.fetch"]


def test_a_planted_wait_marks_its_batch_and_no_other(monkeypatch, capfd):
    stalled = obs.counter("predict.batches_stalled")
    before = stalled.value
    waits = [0.1] * 8
    waits[5] = 0.5
    fetches = _fetch_batches(monkeypatch, waits)
    marked = [r for r in fetches if r["attrs"].get("stalled")]
    assert marked == [fetches[5]]
    assert 0.35 < marked[0]["attrs"]["excess_s"] < 0.5
    assert marked[0]["attrs"]["service_s"] > 0.5
    assert stalled.value == before + 1
    lines = [l for l in capfd.readouterr().err.splitlines()
             if "stalled" in l]
    assert len(lines) == 1
    ids = [r["attrs"]["batch"] for r in fetches]
    assert f"batch {ids[5]} run_tiny(capacity=9)" in lines[0]
    assert "waiting for the answer" in lines[0]
    assert f"batch {ids[3]} run_tiny" in lines[0]
    assert f"batch {ids[4]} run_tiny" in lines[0]


def test_every_stalled_batch_is_reported_not_the_first_only(
    monkeypatch, capfd
):
    stalled = obs.counter("predict.batches_stalled")
    before = stalled.value
    waits = [0.1] * 8
    waits[4] = waits[6] = 0.5
    fetches = _fetch_batches(monkeypatch, waits)
    assert [i for i, r in enumerate(fetches)
            if r["attrs"].get("stalled")] == [4, 6]
    assert stalled.value == before + 2
    assert sum("stalled" in l
               for l in capfd.readouterr().err.splitlines()) == 2


def test_no_batch_is_tested_before_its_program_has_shown_four(monkeypatch):
    fetches = _fetch_batches(monkeypatch, [0.1, 0.5, 0.1, 0.1, 0.1])
    assert not any(r["attrs"].get("stalled") for r in fetches)


def test_a_late_batch_is_never_stalled(monkeypatch, capfd):
    stalled = obs.counter("predict.batches_stalled")
    before = stalled.value
    waits = [0.1] * 8
    waits[5] = 0.5
    fetches = _fetch_batches(monkeypatch, waits, late=True)
    assert all(r["attrs"]["late"] is True for r in fetches)
    assert not any(r["attrs"].get("stalled") for r in fetches)
    assert stalled.value == before
    assert "stalled" not in capfd.readouterr().err


def test_a_slow_copy_home_is_a_stall_of_the_copy(monkeypatch, capfd):
    from tmr_tpu import inference

    fn = _tiny_program("test_kind_slow_copy")
    fetches = _fetch_batches(monkeypatch, [0.05] * 5, fn=fn)
    assert not any(r["attrs"].get("stalled") for r in fetches)
    # the readiness call returns at once and the copy takes the time
    monkeypatch.setattr(inference, "_wait_ready", lambda arrays: False)
    real = inference._CLOCK.fetched

    def slow_copy(known, ready, late):
        time.sleep(0.1)
        return real(known, ready, late)

    monkeypatch.setattr(inference._CLOCK, "fetched", slow_copy)
    inference.detections_to_numpy(fn(2))
    last = [r for r in obs.spans() if r["name"] == "predict.fetch"][-1]
    assert last["attrs"]["stalled"] is True
    assert 0.05 < last["attrs"]["excess_s"] < 0.3
    assert "copying it home" in capfd.readouterr().err


def test_a_batch_in_flight_while_the_host_came_late_is_late_too(monkeypatch):
    """Its service time would start at a stamp taken late: an upper bound
    at best, and no yardstick for the batches after it."""
    from tmr_tpu import inference

    monkeypatch.setattr(inference, "_CLOCK", inference._BatchClock())
    came_late = iter([True, False, False, False])
    monkeypatch.setattr(inference, "_wait_ready",
                        lambda arrays: next(came_late))
    fn = _tiny_program("test_kind_late_chain")
    first, second = fn(2), fn(2)  # two in flight
    inference.detections_to_numpy(first)   # the host came late to it
    third = fn(2)                          # in flight with the second
    inference.detections_to_numpy(second)  # ready at once: since when?
    inference.detections_to_numpy(third)   # its own stamp was in time
    inference.detections_to_numpy(fn(2))   # dispatched after all
    a, b, third, c = [r["attrs"] for r in obs.spans()
                      if r["name"] == "predict.fetch"]
    assert (a["late"], b["late"], c["late"]) == (True, True, False)
    # with one batch always in flight the bound must not be handed on
    assert third["late"] is False
    assert third["service_s"] == pytest.approx(
        third["ready_ts"] - b["ready_ts"])
    dispatches = [r for r in obs.spans() if r["name"] == "predict.dispatch"]
    # the bound starts at the batch's own dispatch, not at the late stamp
    assert b["service_s"] >= b["ready_ts"] - _end(dispatches[1])
    assert b["service_s"] > b["ready_ts"] - a["ready_ts"]
    assert 0.0 < c["service_s"] <= c["ready_ts"] - _end(dispatches[3]) + 1e-3


def test_a_batch_the_host_noticed_late_does_not_lower_the_smallest(
    monkeypatch
):
    """Noticed 0.04 s late, a batch reads 0.14 and the next, whose time
    starts at that stamp, 0.06: no batch ran in 0.06, and the batches after
    are not held to it."""
    waits = [0.1] * 5 + [0.14, 0.06] + [0.1] * 3
    fetches = _fetch_batches(monkeypatch, waits)
    assert not any(r["attrs"].get("stalled") for r in fetches)
    least = [r["attrs"]["least_s"] for r in fetches]
    assert all(0.099 < v < 0.13 for v in least), least
    assert 0.05 < fetches[6]["attrs"]["service_s"] < 0.09
    # what the device waited, the host's late notice taken out of it
    waited = sum(r["attrs"]["service_s"] - r["attrs"]["least_s"]
                 for r in fetches)
    assert abs(waited) < 0.04  # held to 0.06 the last three would add 0.12


def test_two_programs_of_one_name_and_bucket_are_held_apart(monkeypatch):
    """Another Predictor's program of the same name and capacity is another
    program: its batches are no yardstick for this one's."""
    fast = _tiny_program("test_kind_two_programs")
    slow = _tiny_program("test_kind_two_programs")
    _fetch_batches(monkeypatch, [0.05] * 5, fn=fast)
    from tmr_tpu import inference

    pending = iter([0.2] * 6)
    monkeypatch.setattr(inference, "_wait_ready",
                        lambda arrays: time.sleep(next(pending)))
    for _ in range(6):
        inference.detections_to_numpy(slow(2))
    fetches = [r["attrs"] for r in obs.spans()
               if r["name"] == "predict.fetch"]
    assert not any(a.get("stalled") for a in fetches)
    assert all(a["least_s"] >= 0.2 for a in fetches[5:])
    assert all("compiled" not in a for a in fetches)

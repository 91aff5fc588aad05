"""The program times its own host path and set-up (obs/tracing.py scopes).

Coarse spans (``scope="setup"`` / ``"batch"``) are recorded with
``TMR_TRACE`` off, at the Predictor's seams (``predict.stage`` /
``predict.dispatch`` / ``predict.fetch`` / ``predict.unpack``), at the
set-up seams (``compile``, ``gate.selfcheck``) and once a batch at the serve
pipeline's four batch stages; request-scope spans stay behind the knob and
name the batch that carried them. ``spans_ns`` hands them over on the clock
``benchmarks/trace.py:Spans`` reads.
"""

import inspect

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

"""``trace.py`` gives the same numbers from the recorded trace every time.

``testdata/vitb_fscd147.eval.trace.json.gz`` holds the first two program runs
(both of the capacity-9 program) of a traced window of ``vitb_fscd147.eval``
on a TPU v5e (chip call 4 of PR 25, seed 1001), as ``run.py --keep-trace``
recorded them:
device operations, program runs, the benchmark's host spans, one scope table a
run, and the work and peaks the reducers divide by. ``expected.json`` beside
it is what the reduction gave when the recording was made. A number that
moves here was moved by a change to the yardstick, not to the program.
"""

import json
import os

import pytest

from benchmarks import run, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")
CELL = "vitb_fscd147.eval"


def _metrics():
    doc, tables = trace.load_recording(
        os.path.join(DATA, CELL + ".trace.json.gz"))
    reduced = trace.reduce_events(doc["records"], tables, doc["model"],
                                  doc["run_prefix"])
    reduced.update(images=doc["images"], batches=doc["batches"],
                   work=doc["work"], peaks=doc["peaks"])
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return {name: m["value"] for name, m in
            run.layer_metrics(manifest, CELL, reduced).items()}, reduced


def test_the_recorded_trace_reduces_to_the_recorded_numbers():
    with open(os.path.join(DATA, CELL + ".expected.json")) as f:
        expected = json.load(f)
    first, _ = _metrics()
    second, _ = _metrics()
    assert first == second
    assert sorted(first) == sorted(expected)
    for name, value in expected.items():
        assert first[name] == pytest.approx(value, rel=1e-9), name


def test_every_device_second_has_one_owner_or_none():
    metrics, reduced = _metrics()
    layers = ("global_attn.ms", "backbone_rest.ms", "matcher.ms", "heads.ms",
              "tail.ms")
    owned = sum(metrics[name] for name in layers) * reduced["images"] / 1e3
    assert owned == pytest.approx(reduced["op_s"] - reduced["unowned_s"],
                                  rel=1e-9)
    assert reduced["unowned_s"] <= 0.1 * reduced["op_s"]
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    assert metrics["device.idle_pct"] == pytest.approx(
        100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"]))
    assert metrics["host.gap_ms"] == pytest.approx(
        1e3 * sum(reduced["gaps"].values()) / reduced["batches"], rel=1e-6)
    assert metrics["global_attn_roofline"] <= 100.0
    assert metrics["program.mfu_pct"] <= 100.0


def test_a_scope_table_reads_the_compiled_text():
    text = '''
  %fusion.7 = bf16[2,4]{1,0} fusion(%p0), kind=kLoop, calls=%fc.7, metadata={op_name="jit(run)/MatchingNet/backbone/blocks_2/attn/qkv/dot_general" source_file="x.py" source_line=3}
  ROOT %tuple.1 = (bf16[2,4]{1,0}) tuple(%fusion.7)
'''
    assert trace.scope_table(text) == {
        "fusion.7": "jit(run)/MatchingNet/backbone/blocks_2/attn/qkv/dot_general"}
    assert trace.instruction_name(
        "%fusion.7 = bf16[2,4]{1,0} fusion(%p0), kind=kLoop") == "fusion.7"
    spec = {"match": "backbone/blocks_{global_attn_indexes}/attn/"}
    match, _ = trace.scope_pattern(spec, {"global_attn_indexes": [2, 5]})
    assert match.search("x/backbone/blocks_2/attn/qkv")
    assert not match.search("x/backbone/blocks_25/attn/qkv")
    assert not match.search("x/backbone/blocks_3/attn/qkv")

"""Profiling/tracing subsystem (tmr_tpu/utils/profiling.py).

The reference has no profiler (SURVEY §5.1); these tests pin down the
subsystem we add: phase timers, trace capture producing on-disk artifacts,
annotations composing with jit, and the reducer.py-compatible stderr
protocol."""

import os

import jax
import jax.numpy as jnp

from tmr_tpu.utils.profiling import (
    PhaseTimer,
    annotate,
    log_info,
    log_progress,
    log_warning,
    step_annotation,
    trace,
)


def test_phase_timer_accumulates():
    t = PhaseTimer()
    for _ in range(3):
        with t.phase("a"):
            pass
    with t.phase("b"):
        pass
    assert t.counts["a"] == 3 and t.counts["b"] == 1
    assert t.totals["a"] >= 0.0
    d = t.as_dict()
    assert set(d) == {"time/a", "time/b"}
    rep = t.report()
    assert "PHASE" in rep and "a" in rep and "MEAN_MS" in rep
    t.reset()
    assert not t.totals and not t.counts


def test_trace_capture_writes_artifacts(tmp_path):
    logdir = str(tmp_path / "prof")
    with trace(logdir):
        with annotate("matmul_region"):
            x = jnp.ones((64, 64))
            y = (x @ x).block_until_ready()
        with step_annotation("step", 0):
            (x + 1).block_until_ready()
    assert y is not None
    # jax.profiler.trace writes plugins/profile/<run>/*.{trace.json.gz,xplane.pb}
    found = []
    for root, _, files in os.walk(logdir):
        found.extend(files)
    assert found, "profiler trace produced no artifacts"


def test_trace_none_is_noop():
    with trace(None):
        pass
    with trace(""):
        pass


def test_annotations_compose_with_jit():
    @jax.jit
    def f(x):
        return x * 2

    with annotate("jitted"):
        out = f(jnp.arange(8.0))
    assert out.shape == (8,)


def test_stderr_protocol_format(capsys):
    log_info("hello")
    log_warning("careful")
    log_progress("3/10")
    err = capsys.readouterr().err
    assert "[INFO] hello" in err
    assert "[WARNING] careful" in err
    assert "[PROGRESS] 3/10" in err

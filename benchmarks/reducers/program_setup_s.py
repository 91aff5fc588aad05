"""Seconds of process set-up that the program itself spent in the set-up
spans named ``span`` (``scope="setup"`` in ``tmr_tpu/obs/tracing.py``: a
gate's self-check, a program's first call): their summed self time (see
``program_span_ms``), over those that ended before the traced window's
first ``window_span`` began. No such span: nothing returned, never 0."""

from benchmarks.reducers.program_span_ms import self_seconds, window_spans
from tmr_tpu.obs import tracing


def reduce(reduced: dict, spec: dict):
    spans = tracing.spans()
    window = window_spans(spans, spec["window_span"], reduced)
    if window is None:
        return None
    mine = [r for r in spans
            if r["name"] == spec["span"] and r.get("scope") == "setup"
            and r["ts"] + r["dur"] <= window[0]["ts"]]
    if not mine:
        return None
    return sum(self_seconds(r, spans) for r in mine)

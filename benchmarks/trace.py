"""From the profiler's trace to numbers: the one reduction every PR shares.

Two stages. ``load_xplane`` reads the ``.xplane.pb`` with nothing but
``jax.profiler.ProfileData`` into plain records (device operations with the
program run they belong to, the benchmark's own host spans, the window).
``reduce_events`` turns records into what the reducers of
``benchmarks/reducers/`` read. The second stage is checked on a recorded
trace (``benchmarks/tests/test_trace.py``).

The profiler's host tracer is off: at its lowest level that still records a
``TraceAnnotation`` it stretched an 8-batch window of ``vitb_fscd147.eval``
from 4.24 s to 6.87 s and ``stop_trace`` from 2 s to 47 s (my chip call 3,
PR 25), so the traced run measured the tracer. The benchmark's spans are
taken by ``Spans`` on the host's own clock instead, and tied to the trace's
clock by ``mark``: one tiny program (``bench_marker``) run and waited for
before and after the window, whose device event must lie between the two
host readings around it.

A device operation is owned by a *scope path*, never by a kernel's name.
Flax wraps every module call in a name scope, so the HLO ``op_name`` of an
instruction carries ``.../backbone/blocks_2/attn/qkv/...``. ``scope_table``
builds instruction name -> scope path out of the timed program's own
compiled text, and each device event is classified by the table of the
program run it falls in (the window knows which program it dispatched when).

Run ``python3 benchmarks/trace.py dump <xplane.pb>`` to look at a trace by
hand: planes, lines, and the first events with their stats.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import sys
import time

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
MARKER = "bench_marker"


class Spans:
    """The benchmark's host spans, ``[name, start_ns, end_ns]`` on
    ``time.perf_counter_ns``, kept in memory. ``with spans(name):`` records
    one; ``mark`` ties the clock to the device's."""

    def __init__(self):
        self.records, self.marks, self._marker = [], [], None

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.records.append([name, t0, time.perf_counter_ns()])

    def mark(self) -> None:
        """Run ``bench_marker`` on the idle device and wait for it: its
        device event lies between the two host readings. The first call
        compiles it, so make one in set-up."""
        import jax

        if self._marker is None:
            def bench_marker(x):
                return x + 1

            self._marker = (jax.jit(bench_marker),
                            jax.block_until_ready(jax.numpy.zeros((8, 128))))
        fn, x = self._marker
        t0 = time.perf_counter_ns()
        jax.block_until_ready(fn(x))
        self.marks.append([t0, time.perf_counter_ns()])

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def scope_table(hlo_text: str) -> dict:
    """HLO instruction name -> ``op_name`` (the scope path), from
    ``compiled.as_text()``."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            table[m.group(1)] = m.group(2)
    return table


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.7 = bf16[...] fusion(...)``): keep the instruction's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _events(line, name=lambda n: n):
    for e in line.events:
        yield name(e.name), float(e.start_ns), float(e.duration_ns)


def load_xplane(path: str, spans: Spans) -> dict:
    """Plain records, times in seconds on the trace's clock: ``planes`` (one
    per device: ``ops`` and ``modules`` as ``[name, start, duration]``),
    ``spans`` (the benchmark's host spans inside the window, ``[name, start,
    duration]``), ``window`` ``(start, end)`` and ``clock`` (how the host's
    clock was tied to the trace's)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, marker_events = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if not (plane.name.startswith("/device:") and OP_LINE in lines):
            continue
        modules = [[n, s / 1e9, d / 1e9] for n, s, d in
                   _events(lines[MODULE_LINE])] if MODULE_LINE in lines else []
        marker_events += [m for m in modules if MARKER in m[0]]
        planes[plane.name] = {
            "ops": [[n, s / 1e9, d / 1e9] for n, s, d in
                    _events(lines[OP_LINE], instruction_name)],
            "modules": [m for m in modules if MARKER not in m[0]]}
    clock = tie_clocks(sorted(marker_events, key=lambda m: m[1]), spans.marks)
    off = clock["offset_s"]
    window, inside = None, []
    for name, t0, t1 in spans.records:
        if name == WINDOW_SPAN:
            window = (t0 / 1e9 - off, t1 / 1e9 - off)
        else:
            inside.append([name, t0 / 1e9 - off, (t1 - t0) / 1e9])
    if window is None:
        raise ValueError(f"the driver recorded no {WINDOW_SPAN!r} span")
    return {"planes": planes, "spans": sorted(inside, key=lambda r: r[1]),
            "window": window, "clock": clock}


def tie_clocks(events: list, marks: list) -> dict:
    """host time = trace time + ``offset_s``. Each marker's device event
    ``[name, start, duration]`` lies between its two host readings, which
    brackets the offset; the offset is the middle of the brackets' overlap
    and ``slack_s`` is how wide the widest bracket was."""
    if not events or len(events) != len(marks):
        raise ValueError(
            f"the trace holds {len(events)} {MARKER!r} runs for {len(marks)} "
            "marks: the host's clock cannot be tied to the device's")
    lows = [t0 / 1e9 - e[1] for e, (t0, _) in zip(events, marks)]
    highs = [t1 / 1e9 - (e[1] + e[2]) for e, (_, t1) in zip(events, marks)]
    low, high = max(lows), min(highs)
    return {"offset_s": (low + high) / 2.0,
            "slack_s": max(h - l for l, h in zip(lows, highs)),
            "disagree_s": max(low - high, 0.0)}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(records: dict, tables: list, model: dict,
                  run_prefix: str) -> dict:
    """``tables``: one scope table per program run of the window, in
    dispatch order; a program run is a module whose name starts with
    ``run_prefix`` (the driver's: ``jit_run`` for ``Predictor._get_fn``).
    Returns per device plane the time by scope path, the busy union and the
    idle gaps by host span, averaged over the planes."""
    w0, w1 = records["window"]
    per_plane = []
    for name, plane in sorted(records["planes"].items()):
        ops = [r for r in plane["ops"] if r[1] + r[2] > w0 and r[1] < w1]
        runs = sorted((r for r in plane["modules"]
                       if r[1] + r[2] > w0 and r[1] < w1
                       and r[0].startswith(run_prefix)), key=lambda r: r[1])
        by_scope, by_op, unowned = {}, {}, 0.0
        k = 0
        for op_name, start, dur in sorted(ops, key=lambda r: r[1]):
            while k + 1 < len(runs) and start >= runs[k + 1][1]:
                k += 1
            table = tables[min(k, len(tables) - 1)] if tables else {}
            inside = bool(runs) and runs[k][1] <= start < runs[k][1] + runs[k][2]
            scope = table.get(op_name) if inside else None
            by_op[op_name] = by_op.get(op_name, 0.0) + dur
            if scope is None:
                unowned += dur
            else:
                by_scope[scope] = by_scope.get(scope, 0.0) + dur
        busy = _union([[max(s, w0), min(s + d, w1)] for _, s, d in ops])
        gaps, edge = {}, w0
        for s, e in busy + [[w1, w1]]:
            if s > edge:
                gaps_add(gaps, records["spans"], edge, s)
            edge = max(edge, e)
        per_plane.append({
            "plane": name, "by_scope": by_scope, "by_op": by_op,
            "unowned_s": unowned, "op_s": sum(r[2] for r in ops),
            "busy_s": sum(e - s for s, e in busy), "gaps": gaps,
            "program_runs": len(runs)})
    if not per_plane:
        raise ValueError("the trace holds no device plane with an "
                         f"{OP_LINE!r} line: nothing ran on the device")
    n = len(per_plane)
    mean = lambda key: sum(p[key] for p in per_plane) / n
    merged = lambda key: {
        k: sum(p[key].get(k, 0.0) for p in per_plane) / n
        for k in {k for p in per_plane for k in p[key]}}
    return {"window_s": w1 - w0, "busy_s": mean("busy_s"),
            "op_s": mean("op_s"), "unowned_s": mean("unowned_s"),
            "by_scope": merged("by_scope"), "by_op": merged("by_op"),
            "gaps": merged("gaps"), "model": model,
            "program_runs": per_plane[0]["program_runs"]}


def gaps_add(gaps: dict, spans: list, start: float, end: float) -> None:
    """Attribute the idle gap [start, end) to the host spans that cover it;
    what no span covers is ``host.other``."""
    left = end - start
    for name, s, d in spans:
        overlap = min(end, s + d) - max(start, s)
        if overlap > 0:
            gaps[name] = gaps.get(name, 0.0) + overlap
            left -= overlap
    if left > 1e-9:
        gaps["host.other"] = gaps.get("host.other", 0.0) + left


def tables_of(texts: list) -> list:
    """One scope table per run; runs of one program share their table."""
    cache = {}
    return [cache.setdefault(id(t), scope_table(t)) for t in texts]


def scope_pattern(spec: dict, model: dict) -> tuple:
    """A per-layer metric's ``match`` (and optional ``exclude``) as compiled
    regexes; ``{key}`` stands for the alternation of the configuration's
    list of that name, e.g. ``blocks_{global_attn_indexes}/``."""
    def fill(pattern):
        for key, value in model.items():
            if isinstance(value, list):
                pattern = pattern.replace(
                    "{" + key + "}", "(?:" + "|".join(map(str, value)) + ")")
        return re.compile(pattern)

    return fill(spec["match"]), (fill(spec["exclude"])
                                 if spec.get("exclude") else None)


def scope_seconds(reduced: dict, spec: dict) -> float:
    """Device seconds of the window owned by the scopes a metric matches."""
    match, exclude = scope_pattern(spec, reduced["model"])
    return sum(sec for scope, sec in reduced["by_scope"].items()
               if match.search(scope) and not (exclude and exclude.search(scope)))


def breakdown(reduced: dict) -> dict:
    """The ten device operations that took most time, with the share no
    scope owns, and the idle gaps by what the host was doing."""
    ops = sorted(reduced["by_op"].items(), key=lambda kv: -kv[1])[:9]
    ops.append(("unowned_by_any_scope", reduced["unowned_s"]))
    gaps = sorted(reduced["gaps"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def trim(records: dict, runs: int, run_prefix: str) -> dict:
    """The records of the first ``runs`` program runs: the window then ends
    where run ``runs + 1`` starts (or the last kept run ends)."""
    w0, w1 = records["window"]
    starts = sorted(r[1] for plane in records["planes"].values()
                    for r in plane["modules"]
                    if r[0].startswith(run_prefix) and w0 <= r[1] < w1)
    n_planes = max(len(records["planes"]), 1)
    if len(starts) // n_planes > runs:
        w1 = starts[runs * n_planes]
    inside = lambda r: r[1] < w1 and r[1] + r[2] > w0
    return {
        "planes": {name: {"ops": [r for r in plane["ops"] if inside(r)],
                          "modules": [r for r in plane["modules"]
                                      if inside(r)]}
                   for name, plane in records["planes"].items()},
        "spans": [r for r in records["spans"] if inside(r)],
        "window": (w0, w1), "clock": records.get("clock")}


def save_recording(path: str, records: dict, tables: list, extra: dict) -> None:
    """A small recorded trace for ``benchmarks/tests/check_trace.py``: the
    records, one scope table per run cut to the operations that occur, and
    whatever the reducers read besides (``extra``)."""
    import gzip
    import json

    seen = {r[0] for plane in records["planes"].values() for r in plane["ops"]}
    distinct, index = [], []
    for t in tables:
        cut = {k: v for k, v in t.items() if k in seen}
        if cut not in distinct:
            distinct.append(cut)
        index.append(distinct.index(cut))
    with gzip.open(path, "wt") as f:
        json.dump({"records": records, "tables": distinct,
                   "table_of_run": index, **extra}, f)


def load_recording(path: str):
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    doc["records"]["window"] = tuple(doc["records"]["window"])
    tables = [doc["tables"][i] for i in doc["table_of_run"]]
    return doc, tables


def dump(path: str, out=sys.stdout, limit: int = 6) -> None:
    """Planes, lines and each line's first events, to read by hand."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for e in events[:limit]:
                print(f"    {e.name[:120]!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} stats "
                      f"{[(k, str(v)[:80]) for k, v in list(e.stats)[:8]]}",
                      file=out)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "dump":
        dump(sys.argv[2])
    else:
        sys.exit(__doc__)

#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, per-layer metric or
driver sits in a file of its own, found by the name ``BENCHMARK.json`` gives:
``workloads/<cell>.json``, ``configs/<config>.json``,
``layer_metrics/<metric>.json`` (with its reducer, ``reducers/<reduce>.py``)
and ``drivers/<driver>.py``. See ``benchmarks/README.md``.

It needs a TPU and as many chips as the cell asks for: without them it exits
with a code other than 0 and prints no result. ``--rehearsal`` (with
``JAX_PLATFORMS=cpu``) runs the same paths at the tiny size the
configuration's file names, on whatever JAX finds, and prints no metric.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``),
and last the numbers ``correct`` compared, each beside its limit.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import collections
import importlib.util
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T_START:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json names no {what} {name!r}")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layer_metrics(manifest: dict, cell: str, reduced: dict) -> dict:
    """The cell's per-layer metrics, each by the reducer its file names; a
    reducer that finds nothing to read leaves its metric out."""
    out = {}
    for entry in manifest["per_layer"]:
        if not reports(entry, cell):
            continue
        spec = load_json("layer_metrics", entry["name"] + ".json")
        value = load_module("reducers", spec["reduce"]).reduce(reduced, spec)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def read_trace(ctx, driver, state, win, programs, trace_dir, device,
               keep_dir) -> tuple:
    """The traced window's per-layer metrics and breakdown; ``device`` gets
    ``busy_s`` and ``window_s``. The trace is deleted when it has been read."""
    from benchmarks import trace

    cell, config = ctx.cell["name"], ctx.config
    xplane = trace.find_xplane(trace_dir)
    records = trace.load_xplane(xplane, ctx.span)
    say("clock: host = trace + {offset_s:.6f}s, marks bracket it within "
        "{slack_s:.6f}s, disagree by {disagree_s:.6f}s".format(
            **records["clock"]))
    tables = trace.tables_of(
        [programs[state["caps"][p]]["text"] for p in win["order"]])
    prefix = win["run_prefix"]
    reduced = trace.reduce_events(records, tables, config["model"], prefix)
    reduced.update(images=win["images"], batches=win["batches"],
                   work=driver.work_per_image(ctx, state), peaks=ctx.peaks)
    device["busy_s"], device["window_s"] = reduced["busy_s"], \
        reduced["window_s"]
    metrics = layer_metrics(ctx.manifest, cell, reduced)
    say(f"trace: {reduced['program_runs']} program runs, device operations "
        f"{reduced['op_s']:.3f}s, of them owned by no scope "
        f"{reduced['unowned_s']:.3f}s")
    if keep_dir:
        os.makedirs(keep_dir, exist_ok=True)
        keep = 2
        with open(os.path.join(keep_dir, cell + ".planes.txt"), "w") as f:
            trace.dump(xplane, f)
        trace.save_recording(
            os.path.join(keep_dir, cell + ".trace.json.gz"),
            trace.trim(records, keep, prefix), tables[:keep],
            {"model": config["model"], "peaks": ctx.peaks,
             "run_prefix": prefix,
             "work": reduced["work"], "batches": keep,
             "images": keep * win["images"] // win["batches"]})
    shutil.rmtree(trace_dir, ignore_errors=True)
    return metrics, trace.breakdown(reduced)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on whatever JAX finds; no metric printed")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: record the first two program runs "
                         "of the trace there, small enough to commit, and a "
                         "listing of the trace's planes and lines")
    ap.add_argument("--control", default=None, metavar="PRECISION",
                    help="put the reference at this lower precision in the "
                         "program's place (fp8|int8): must print correct false")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = find(manifest["workloads"], args.workload, "workload")
    workload = load_json("workloads", cell["name"] + ".json")
    config = load_json("configs", cell["config"] + ".json")
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    overrides = dict(config["overrides"])
    if args.rehearsal:
        overrides.update(config["rehearsal"]["overrides"])
        config = dict(config, model=dict(config["model"],
                                         **config["rehearsal"].get("model", {})))

    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearsal:
        if dev.platform != "tpu":
            say(f"benchmarks/run.py: JAX found no TPU (platform "
                f"{dev.platform!r}); the benchmark runs on the chip only")
            return 2
        if len(devices) < cell["chips"]:
            say(f"benchmarks/run.py: {cell['name']} needs {cell['chips']} "
                f"chips, JAX sees {len(devices)}")
            return 2
    say(f"device: platform {dev.platform!r} device_kind {dev.device_kind!r} "
        f"x{len(devices)}; jax {jax.__version__}")
    from benchmarks import check_detections, trace, work

    peaks = None if args.rehearsal else work.peaks_for(dev.device_kind)

    # the compile cache: where the environment says, else one fixed path
    # inside the checkout; every program is kept, however quick its compile
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.update([name]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.update([name]))
    compiles = lambda: events["/jax/core/compile/backend_compile_duration"]

    def cache_bytes() -> int:
        return sum(os.path.getsize(os.path.join(cache_dir, f))
                   for f in os.listdir(cache_dir)) if os.path.isdir(
                       cache_dir) else 0

    say(f"compile cache: {cache_dir} ({cache_bytes() / 2**20:.1f} MiB)")

    ctx = types.SimpleNamespace(
        manifest=manifest, cell=cell, workload=workload, config=config,
        overrides=overrides, seed=args.seed, rehearsal=args.rehearsal,
        say=say, peaks=peaks, device=dev, span=trace.Spans())
    driver = load_module("drivers", workload["driver"])
    state = driver.setup(ctx)
    if args.trace:
        ctx.span.mark()  # compiles the marker; the window's marks come below
        ctx.span.marks.clear()
    setup_s = time.perf_counter() - _T_START
    say(f"set-up {setup_s:.2f}s; persistent-cache hits "
        f"{events['/jax/compilation_cache/cache_hits']} misses "
        f"{events['/jax/compilation_cache/cache_misses']}; backend compiles "
        f"{compiles()}; cache now {cache_bytes() / 2**20:.1f} MiB")

    trace_dir = os.path.join(TRACE_DIR, cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0  # it slows the window: see trace.py
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ctx.span.mark()
    compiles_before = compiles()
    try:
        win = driver.window(
            ctx, state, seconds,
            max_batches=workload["trace"]["batches"] if args.trace else None)
    finally:
        if args.trace:
            ctx.span.mark()
            jax.profiler.stop_trace()
    in_window = compiles() - compiles_before
    say(f"window: {win['images']} images in {win['batches']} batches over "
        f"{win['seconds']:.3f}s; compiles inside the window: {in_window}")
    if in_window:
        say("benchmarks/run.py: a program compiled inside the measured "
            "window; the run is void")
        return 3

    # the runtime's peak counts buffers only: a program's temporaries are
    # held beside them while it runs (PERF.md, section 6), so the peak on
    # the chip is the two together
    stats = dev.memory_stats() or {}
    programs = driver.hlo_texts(state)
    temporaries = max(p["memory"].temp_size_in_bytes
                      for p in programs.values())
    for cap, prog in programs.items():
        m = prog["memory"]
        say(f"program capacity {cap}: memory_analysis arguments "
            f"{m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
            f"temporaries {m.temp_size_in_bytes}; "
            f"{prog['text'].count('tpu_custom_call')} tpu_custom_call")
    say(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"bytes_in_use {stats.get('bytes_in_use')} bytes_limit "
        f"{stats.get('bytes_limit')}; largest program temporaries "
        f"{temporaries}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": None if args.rehearsal else
              stats["peak_bytes_in_use"] + temporaries}

    metrics, breakdown = {}, None
    if args.trace and args.rehearsal:
        say("rehearsal: the CPU's trace has no device plane to reduce; "
            "benchmarks/tests/test_trace.py reduces a recorded one")
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif args.trace:
        metrics, breakdown = read_trace(
            ctx, driver, state, win, programs, trace_dir, device,
            args.keep_trace)
    else:
        for entry in manifest["end_to_end"]:
            if not reports(entry, cell["name"]):
                continue
            value = setup_s if entry["name"] == "setup_s" else \
                win["metrics"][entry["name"]]
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    driver.release(state)
    t0 = time.perf_counter()
    numbers = driver.check(ctx, state, quant=args.control)
    limits = dict(workload["correct"]["limits"])
    if args.rehearsal:
        limits.update(workload["correct"].get("rehearsal_limits", {}))
    correct, compared = check_detections.verdict(numbers, limits)
    say(f"check: {time.perf_counter() - t0:.1f}s after the window"
        + (f" (CONTROL at {args.control})" if args.control else ""))

    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if args.rehearsal:
        result.update(metrics={}, rehearsal=True)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        say(f"compared {name}: {c['value']} ({c['kind']} {c['limit']})")
    say(f"correct: {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

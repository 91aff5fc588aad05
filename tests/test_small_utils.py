"""Direct unit coverage for small leaf modules (bench_guard, COCOIndex)
plus repo-wide hygiene lints (report-schema/validator parity, stdout
discipline under tmr_tpu/)."""

import json
import os
import re

import pytest

from tmr_tpu.data.coco_index import COCOIndex
from tmr_tpu.utils.bench_guard import run_guarded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------ repo hygiene (thin
# wrappers: the lints themselves moved to tmr_tpu/analysis as framework
# passes — tests/test_analysis.py proves each rule fires on fixtures;
# these keep the tier-1 zero-findings coverage at its original site)
def _rule_findings(rule_id: str):
    from tmr_tpu.analysis import Baseline, default_baseline_path, \
        run_ast_passes

    baseline = Baseline.load(default_baseline_path(REPO))
    return [
        str(f) for f in run_ast_passes(root=REPO, rules=[rule_id],
                                       baseline=baseline)
        if not baseline.allows(f)
    ]


def test_every_report_schema_has_a_validator():
    """Parity pin (analysis rule ``report-parity``): every ``*_report/v1``
    schema constant declared in diagnostics.py must ship a matching
    ``validate_*`` function, and every scripts/*.py referencing a
    ``*_REPORT_SCHEMA`` constant must call its validator."""
    assert _rule_findings("report-parity") == []
    # and the declared validators are actually importable callables
    import tmr_tpu.diagnostics as diag

    src = open(os.path.join(REPO, "tmr_tpu", "diagnostics.py")).read()
    schemas = re.findall(
        r'^([A-Z][A-Z_]*)_SCHEMA\s*=\s*"(\w+_report)/v\d+"', src, re.M
    )
    assert len(schemas) >= 4  # map/serve/metrics/trace/analysis at least
    for const, tag in schemas:
        assert callable(getattr(diag, f"validate_{tag}", None)), (
            f"{const}_SCHEMA ({tag}) has no importable validate_{tag}()"
        )


def test_env_knob_registry_parity():
    """Every TMR_* env knob consumed under tmr_tpu/ must be documented
    in ``config.ENV_KNOBS`` and every registry entry consumed somewhere
    on the repo surface (analysis rule ``knob-parity``), and no knob may
    be read at import time outside config.py (``knob-import-time``)."""
    assert _rule_findings("knob-parity") == []
    assert _rule_findings("knob-import-time") == []


def test_no_bare_stdout_prints_under_tmr_tpu():
    """Stdout under tmr_tpu/ is reserved for machine-readable protocol
    output; human-readable lines go to stderr (analysis rule
    ``stdout-hygiene``)."""
    assert _rule_findings("stdout-hygiene") == []


def test_run_guarded_success_and_cancel(monkeypatch):
    monkeypatch.setenv("TMR_BENCH_ALARM", "3300")
    seen = []

    def run(cancel):
        cancel()  # contract: callable before the success print
        seen.append("ran")
        return 0

    rc = run_guarded(run, lambda msg: seen.append(("err", msg)))
    assert rc == 0 and seen == ["ran"]


def test_run_guarded_funnels_exceptions(monkeypatch):
    monkeypatch.setenv("TMR_BENCH_ALARM", "0")  # no watchdog thread
    errs = []
    rc = run_guarded(
        lambda cancel: (_ for _ in ()).throw(RuntimeError("boom")),
        errs.append,
    )
    assert rc == 1
    assert "RuntimeError: boom" in errs[0]

    # SystemExit funnels too (an in-library sys.exit must still yield the
    # contractual JSON record, not an empty stdout)
    errs = []
    rc = run_guarded(
        lambda cancel: (_ for _ in ()).throw(SystemExit(3)), errs.append
    )
    assert rc == 1 and "SystemExit" in errs[0]


def test_run_guarded_malformed_alarm_env(monkeypatch):
    monkeypatch.setenv("TMR_BENCH_ALARM", "")  # int() would raise
    rc = run_guarded(lambda cancel: 0, lambda msg: None)
    assert rc == 0


def test_run_guarded_keyboardinterrupt_reraises(monkeypatch):
    monkeypatch.setenv("TMR_BENCH_ALARM", "0")
    with pytest.raises(KeyboardInterrupt):
        run_guarded(
            lambda cancel: (_ for _ in ()).throw(KeyboardInterrupt()),
            lambda msg: None,
        )


def test_coco_index_read_paths(tmp_path):
    data = {
        "images": [{"id": 7, "file_name": "a.jpg"},
                   {"id": 9, "file_name": "b.jpg"}],
        "annotations": [
            {"id": 1, "image_id": 7, "bbox": [0, 0, 5, 5]},
            {"id": 2, "image_id": 7, "bbox": [1, 1, 3, 3]},
            {"id": 3, "image_id": 9, "bbox": [2, 2, 4, 4]},
        ],
    }
    p = tmp_path / "inst.json"
    p.write_text(json.dumps(data))
    idx = COCOIndex(str(p))
    assert sorted(idx.get_img_ids()) == [7, 9]
    assert idx.imgs[9]["file_name"] == "b.jpg"
    ids = idx.get_ann_ids([7])
    assert sorted(ids) == [1, 2]
    anns = idx.load_anns(ids)
    assert [a["id"] for a in anns] == sorted(ids)
    assert idx.get_ann_ids([9, 7]) and len(idx.get_ann_ids([9, 7])) == 3


def _run_cpu(*argv: str, **env):
    """``python *argv`` in a fresh interpreter held to the CPU, at the repo
    root, with no cache directory placed from outside."""
    import subprocess
    import sys

    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


_CACHE_PROBE = (
    "import jax\n"
    "from tmr_tpu.utils.cache import enable_compilation_cache\n"
    "print(enable_compilation_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compilation_cache_dir_from_outside_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache is placed from
    outside: enable_compilation_cache sets no directory in code, and
    jax's config stays at that value."""
    target = str(tmp_path / "outside-cache")
    out = _run_cpu("-c", _CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=target)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [target, target]
    assert not os.path.exists(os.path.join(REPO, "outside-cache"))


def test_compilation_cache_default_is_one_fixed_path_in_the_checkout():
    """Unset, the cache directory is inside the checkout and identical
    across two processes (never ~, a temp name, a pid or a time: a
    directory that moves never hits)."""
    first = _run_cpu("-c", _CACHE_PROBE)
    second = _run_cpu("-c", _CACHE_PROBE)
    assert first.returncode == 0, first.stderr[-2000:]
    assert first.stdout == second.stdout
    returned, configured = first.stdout.split()
    assert returned == configured == os.path.join(REPO, ".jax_cache")


def test_compilation_cache_failure_degrades_to_warning(
    monkeypatch, tmp_path
):
    """An un-writable cache dir (or any enabling failure) warns and
    returns None instead of crashing the caller — the uniform script call
    sites must never turn a cache nicety into a benchmark failure."""
    from tmr_tpu.utils import cache as cache_mod

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def denied(*a, **k):
        raise OSError("read-only filesystem")

    monkeypatch.setattr(cache_mod.os, "makedirs", denied)
    with pytest.warns(UserWarning, match="compilation cache disabled"):
        assert cache_mod.enable_compilation_cache() is None


def test_chip_smoke_fails_without_a_chip():
    """chip_smoke.py under JAX_PLATFORMS=cpu exits non-zero and prints no
    result — no CPU continuation, no ok line."""
    out = _run_cpu("chip_smoke.py")
    assert out.returncode != 0
    assert "ok" not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("cli", ["main.py", "demo.py", "extract_feature.py"])
def test_device_tpu_without_a_chip_is_an_error(cli):
    """--device tpu (the default) on a process held to the CPU exits with
    the error instead of carrying on without the chip."""
    args = {"main.py": [], "demo.py": ["--image", "none.png"],
            "extract_feature.py": ["none.png"]}[cli]
    out = _run_cpu(cli, "--device", "tpu", *args)
    assert out.returncode != 0
    assert "--device tpu: the default JAX backend is 'cpu'" in out.stderr


def test_platform_peak_raises_for_an_unknown_accelerator(monkeypatch):
    """An accelerator whose device kind has no row is an error, not a
    made-up peak; the CPU keeps its labeled nominal row."""
    import jax

    from tmr_tpu.obs import devtime

    assert devtime.platform_peak()["peak_source"] == "nominal"

    class _Dev:
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(LookupError, match="TPU v99"):
        devtime.platform_peak()
    _Dev.device_kind = "TPU v5 lite"
    peak = devtime.platform_peak()
    assert peak["peak_source"] == "table" and peak["peak_tflops"] == 197.0

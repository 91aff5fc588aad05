"""Full-program A/B arbitration (scripts/pick_full_program.py): the
one-stage autotune sweep's ranking can disagree with the production
program (round 4: a formulation won the sweep, lost a profile), so the
battery's env-pinned whole-program benches decide — a decisive winner's
knobs are pinned into the autotune seed with fresh variant stamps.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arbiter():
    spec = importlib.util.spec_from_file_location(
        "pick_full_program",
        os.path.join(REPO, "scripts", "pick_full_program.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(value, knobs=None, autotuned=None):
    return {
        "metric": "m", "value": value, "unit": "img/s", "vs_baseline": 0.1,
        "batch": 4, "knobs": knobs or {}, "autotuned": autotuned or {},
    }


@pytest.fixture
def seed_file(tmp_path, monkeypatch):
    path = tmp_path / "seed.json"
    path.write_text(json.dumps({
        "TPU v5 lite|1024|128|4|512|vit_b": {
            "TMR_XCORR_IMPL_SMALL": "conv",
            "_variants_TMR_XCORR_IMPL_SMALL": "stale",
        }
    }))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(path))
    return path


def test_decisive_full_program_winner_pins_seed(tmp_path, seed_file, capsys):
    """An env-pinned combo beating the autotuned headline by >3% rewrites
    the seed's formulation knobs with CURRENT variant stamps (so the entry
    loads as a cached hit, not stale) and keeps the A/B evidence."""
    arb = _arbiter()
    # headline: autotune exported its picks into the env, so knobs ==
    # autotuned (nothing externally pinned)
    (tmp_path / "bench_live.json").write_text(json.dumps(_rec(
        10.1,
        knobs={"TMR_XCORR_IMPL_SMALL": "conv"},
        autotuned={"TMR_XCORR_IMPL_SMALL": "conv"},
    )))
    # pinned run: the correlation's formulation forced in the env (absent
    # from autotuned)
    (tmp_path / "bench_vmap.json").write_text(json.dumps(_rec(
        27.4, knobs={"TMR_XCORR_IMPL_SMALL": "vmap"},
    )))
    rc = arb.main([str(tmp_path / "bench_live.json"),
                   str(tmp_path / "bench_vmap.json")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["updated"] is True and out["best"] == "bench_vmap.json"

    from tmr_tpu.utils.autotune import _load_validated, _variants_sig

    seed = json.loads(seed_file.read_text())
    entry = seed["TPU v5 lite|1024|128|4|512|vit_b"]
    assert entry["TMR_XCORR_IMPL_SMALL"] == "vmap"
    assert entry["_variants_TMR_XCORR_IMPL_SMALL"] == _variants_sig(
        "TMR_XCORR_IMPL_SMALL"
    )
    assert "_full_program_ab" in entry
    # and the written entry survives the loader's validation
    loaded = _load_validated(str(seed_file))
    assert loaded["TPU v5 lite|1024|128|4|512|vit_b"][
        "TMR_XCORR_IMPL_SMALL"] == "vmap"


def test_non_decisive_win_leaves_seed_alone(tmp_path, seed_file, capsys):
    arb = _arbiter()
    before = seed_file.read_text()
    (tmp_path / "bench_live.json").write_text(json.dumps(_rec(
        10.1, knobs={"TMR_XCORR_IMPL_SMALL": "conv"},
        autotuned={"TMR_XCORR_IMPL_SMALL": "conv"},
    )))
    (tmp_path / "bench_vmap.json").write_text(json.dumps(_rec(
        10.2, knobs={"TMR_XCORR_IMPL_SMALL": "vmap"},
    )))
    rc = arb.main([str(tmp_path / "bench_live.json"),
                   str(tmp_path / "bench_vmap.json")])
    assert rc == 3
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["updated"] is False
    assert seed_file.read_text() == before


def test_no_baseline_refuses_to_pin(tmp_path, seed_file, capsys):
    """A pinned record with no valid autotuned headline to compare against
    must NOT be pinned — without the margin check the combo was never shown
    to beat the autotuned program (review finding r5)."""
    arb = _arbiter()
    before = seed_file.read_text()
    (tmp_path / "bench_vmap.json").write_text(json.dumps(_rec(
        27.4, knobs={"TMR_XCORR_IMPL_SMALL": "vmap"},
    )))
    rc = arb.main([str(tmp_path / "bench_vmap.json")])
    assert rc == 3
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["updated"] is False and "baseline" in out["reason"]
    assert seed_file.read_text() == before


def test_pins_only_matching_batch_entries(tmp_path, seed_file, capsys):
    """A batch-4 A/B must not overwrite a batch-8 seed entry's winners."""
    arb = _arbiter()
    seed = json.loads(seed_file.read_text())
    seed["TPU v5 lite|1024|128|8|512|vit_b"] = {
        "TMR_XCORR_IMPL_SMALL": "fft",
        "_variants_TMR_XCORR_IMPL_SMALL": "whatever",
    }
    seed_file.write_text(json.dumps(seed))
    (tmp_path / "bench_live.json").write_text(json.dumps(_rec(
        10.0, knobs={"TMR_XCORR_IMPL_SMALL": "conv"},
        autotuned={"TMR_XCORR_IMPL_SMALL": "conv"},
    )))
    (tmp_path / "bench_vmap.json").write_text(json.dumps(_rec(
        20.0, knobs={"TMR_XCORR_IMPL_SMALL": "vmap"},
    )))
    rc = arb.main([str(tmp_path / "bench_live.json"),
                   str(tmp_path / "bench_vmap.json")])
    assert rc == 0
    seed = json.loads(seed_file.read_text())
    assert seed["TPU v5 lite|1024|128|4|512|vit_b"][
        "TMR_XCORR_IMPL_SMALL"] == "vmap"
    # the batch-8 entry is untouched
    assert seed["TPU v5 lite|1024|128|8|512|vit_b"][
        "TMR_XCORR_IMPL_SMALL"] == "fft"


def test_size_match_is_positional_not_substring(tmp_path, seed_file, capsys):
    """A 512-px record must NOT update the 1024 entry: '|512|' would
    substring-match the emb field of EVERY key (kind|image|up_hw|batch|emb|
    vit) — the match must compare the image field positionally."""
    arb = _arbiter()
    base = _rec(10.0, knobs={"TMR_XCORR_IMPL_SMALL": "conv"},
                autotuned={"TMR_XCORR_IMPL_SMALL": "conv"})
    pin = _rec(20.0, knobs={"TMR_XCORR_IMPL_SMALL": "vmap"})
    for r in (base, pin):
        r["image_size"] = 512
        r["device_kind"] = "TPU v5 lite"
    (tmp_path / "bench_live.json").write_text(json.dumps(base))
    (tmp_path / "bench_vmap.json").write_text(json.dumps(pin))
    rc = arb.main([str(tmp_path / "bench_live.json"),
                   str(tmp_path / "bench_vmap.json")])
    assert rc == 0
    seed = json.loads(seed_file.read_text())
    # the 1024 entry is untouched; a NEW 512 key was created instead
    assert seed["TPU v5 lite|1024|128|4|512|vit_b"][
        "TMR_XCORR_IMPL_SMALL"] == "conv"
    assert seed["TPU v5 lite|512|64|4|512|vit_b"][
        "TMR_XCORR_IMPL_SMALL"] == "vmap"


def test_error_records_and_missing_files_are_skipped(tmp_path, seed_file,
                                                     capsys):
    arb = _arbiter()
    (tmp_path / "bench_err.json").write_text(json.dumps(
        {"metric": "m", "value": 0.0, "error": "wedge"}
    ))
    rc = arb.main([str(tmp_path / "bench_err.json"),
                   str(tmp_path / "nonexistent.json")])
    assert rc == 3
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["updated"] is False


def test_pinned_tile_knobs_round_trip_the_cache(tmp_path, monkeypatch):
    """A pinned formulation and the stored-int8 arm written into the seed
    must survive cache validation (an off-contract value is dropped) and
    be exported to the env by autotune() as cached hits."""
    import jax

    from tmr_tpu.utils import autotune as at

    seed = tmp_path / "seed.json"
    seed.write_text(json.dumps({
        "cpu|1024|128|4|512|vit_b": {
            "TMR_XCORR_IMPL_SMALL": "vmap",
            "_variants_TMR_XCORR_IMPL_SMALL": at._variants_sig(
                "TMR_XCORR_IMPL_SMALL"),
            "TMR_QUANT_STORAGE": "int8",
            "_variants_TMR_QUANT_STORAGE": at._variants_sig(
                "TMR_QUANT_STORAGE"),
            "TMR_XCORR_PRECISION": "fp8",  # not a precision: dropped
        }
    }))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(seed))
    monkeypatch.setenv("TMR_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    loaded = at._load_validated(str(seed))
    entry = loaded["cpu|1024|128|4|512|vit_b"]
    assert entry["TMR_XCORR_IMPL_SMALL"] == "vmap"
    assert entry["TMR_QUANT_STORAGE"] == "int8"
    assert "TMR_XCORR_PRECISION" not in entry

    for k in ("TMR_XCORR_IMPL", "TMR_XCORR_IMPL_SMALL",
              "TMR_XCORR_PRECISION", "TMR_QUANT_STORAGE"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.01}
    )
    # the decoder/quant stages are NOT what this test pins — unmocked they
    # compile real stage programs at the 1024 geometry
    monkeypatch.setattr(
        at, "pick_decoder_impl", lambda *a, **k: {"xla": 0.01}
    )
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: {"off": 0.01}
    )

    class _Dev:
        device_kind = "cpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    from tmr_tpu.config import preset

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=256,
                 batch_size=1)
    report = at.autotune(cfg, 1024, 4, tune_precision=False)
    try:
        assert report["TMR_XCORR_IMPL_SMALL"] == {"picked": "vmap",
                                                  "cached": True}
        assert report["TMR_QUANT_STORAGE"] == {"picked": "int8",
                                               "cached": True}
        assert os.environ["TMR_XCORR_IMPL_SMALL"] == "vmap"
        assert os.environ["TMR_QUANT_STORAGE"] == "int8"
    finally:
        for k in ("TMR_XCORR_IMPL_SMALL", "TMR_XCORR_PRECISION",
                  "TMR_QUANT_STORAGE", "TMR_DECODER_IMPL"):
            os.environ.pop(k, None)

"""Promotion of user-cache autotune winners into the committed seed
(scripts/promote_cache_to_seed.py): stamped-fresh winners are promoted,
stale ones are not, and full-program pins (which outrank one-stage sweep
winners) are preserved.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = "TPU v5 lite|1024|128|4|512|vit_b"


def _promoter():
    spec = importlib.util.spec_from_file_location(
        "promote_cache_to_seed",
        os.path.join(REPO, "scripts", "promote_cache_to_seed.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def paths(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    seed = tmp_path / "seed.json"
    monkeypatch.setenv("TMR_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(seed))
    return cache, seed


def test_fresh_winners_promote_and_stale_do_not(paths, capsys):
    from tmr_tpu.utils.autotune import _variants_sig

    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "vmap",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
        "TMR_DECODER_IMPL": "fused",
        "_variants_TMR_DECODER_IMPL": "stale,old,set",  # must not move
        "TMR_BENCH_BATCH": "8",
    }}))
    seed.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "conv",
        "_variants_TMR_XCORR_IMPL_SMALL": "old",
        "TMR_DECODER_IMPL": "xla",
        "_variants_TMR_DECODER_IMPL": "old",
    }}))
    rc = _promoter().main([])
    assert rc == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["TMR_XCORR_IMPL_SMALL"] == "vmap"
    assert out["_variants_TMR_XCORR_IMPL_SMALL"] == _variants_sig(
        "TMR_XCORR_IMPL_SMALL"
    )
    # the stale-stamped correlation winner did NOT launder into the seed
    assert out["TMR_DECODER_IMPL"] == "xla"
    assert out["_variants_TMR_DECODER_IMPL"] == "old"
    # measured batch rides along
    assert out["TMR_BENCH_BATCH"] == "8"


def test_full_program_pins_outrank_sweep_winners(paths, capsys):
    from tmr_tpu.utils.autotune import _variants_sig

    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "fft",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
        "TMR_DECODER_IMPL": "fused",
        "_variants_TMR_DECODER_IMPL": _variants_sig("TMR_DECODER_IMPL"),
    }}))
    # seed entry written by pick_full_program: conv won the WHOLE-program
    # A/B — the sweep's one-stage fft pick must not overwrite it
    seed.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "conv",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
        "_full_program_ab": "{}",
    }}))
    rc = _promoter().main([])
    assert rc == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["TMR_XCORR_IMPL_SMALL"] == "conv"         # preserved
    assert out["_full_program_ab"] == "{}"         # marker intact
    assert out["TMR_DECODER_IMPL"] == "fused"   # non-pinnable knob promoted


def test_stale_full_program_pin_does_not_block_promotion(paths, capsys):
    """Once a sweep-revision bump stales a full-program pin's stamp, the
    runtime drops it and re-sweeps — so the fresh sweep winner MUST
    promote, or every fresh container re-sweeps forever
    (review finding r5)."""
    from tmr_tpu.utils.autotune import _variants_sig

    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "fft",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
    }}))
    seed.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "conv",
        "_variants_TMR_XCORR_IMPL_SMALL": "pre-revision,stale",
        "_full_program_ab": "{}",
    }}))
    rc = _promoter().main([])
    assert rc == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["TMR_XCORR_IMPL_SMALL"] == "fft"


def test_overwritten_stale_pin_loses_its_marker(paths, capsys):
    """When a stale full-program pin is replaced by a sweep winner, the
    _full_program_ab marker must go with it — otherwise the sweep pick
    inherits pin-level protection it never earned and blocks every later
    fresh sweep winner (review finding r5)."""
    from tmr_tpu.utils.autotune import _variants_sig

    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "fft",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
    }}))
    seed.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "conv",
        "_variants_TMR_XCORR_IMPL_SMALL": "pre-revision,stale",
        "_full_program_ab": "{}",
    }}))
    assert _promoter().main([]) == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["TMR_XCORR_IMPL_SMALL"] == "fft"
    assert "_full_program_ab" not in out


def test_lone_precision_impl_does_not_ride(paths, capsys):
    """_precision_impl moves only with its owner TMR_XCORR_PRECISION: a
    stale precision winner's pairing must not overwrite the seed's
    validated pairing (review finding r5)."""
    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_PRECISION": "bf16",
        "_variants_TMR_XCORR_PRECISION": "stale",  # owner NOT promoted
        "_precision_impl": "vmap",
        "TMR_BENCH_BATCH": "8",  # independent: rides alone
    }}))
    seed.write_text(json.dumps({KEY: {
        "TMR_XCORR_PRECISION": "default",
        "_precision_impl": "conv",
    }}))
    rc = _promoter().main([])
    assert rc == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["_precision_impl"] == "conv"  # pairing untouched
    assert out["TMR_XCORR_PRECISION"] == "default"
    assert out["TMR_BENCH_BATCH"] == "8"


def test_corrupt_seed_entry_degrades_gracefully(paths, capsys):
    """A non-dict seed entry (hand-edited file) must degrade to absent,
    not crash the promote stage (review finding r5)."""
    from tmr_tpu.utils.autotune import _variants_sig

    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "vmap",
        "_variants_TMR_XCORR_IMPL_SMALL": _variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
    }}))
    seed.write_text(json.dumps({KEY: "corrupt-string-entry"}))
    rc = _promoter().main([])
    assert rc == 0
    out = json.loads(seed.read_text())[KEY]
    assert out["TMR_XCORR_IMPL_SMALL"] == "vmap"


def test_nothing_to_promote(paths, capsys):
    cache, seed = paths
    cache.write_text(json.dumps({KEY: {
        "TMR_XCORR_IMPL_SMALL": "fft",
        "_variants_TMR_XCORR_IMPL_SMALL": "stale",
    }}))
    before = json.dumps({KEY: {"TMR_XCORR_IMPL_SMALL": "conv"}})
    seed.write_text(before)
    rc = _promoter().main([])
    assert rc == 3
    assert seed.read_text() == before

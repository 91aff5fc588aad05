"""Measured formulation selection (tmr_tpu/utils/autotune.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmr_tpu.config import preset
from tmr_tpu.utils import autotune as at

KNOBS = ("TMR_XCORR_IMPL", "TMR_XCORR_IMPL_SMALL",
         "TMR_XCORR_PRECISION", "TMR_GLOBAL_ATTN",
         "TMR_GLOBAL_SCORES_DTYPE", "TMR_DECODER_IMPL", "TMR_QUANT")


@pytest.fixture
def clean_knobs(monkeypatch, tmp_path):
    """No knobs set on entry; anything autotune exports is popped on exit.
    The persistent winner cache is redirected to a per-test file so tests
    never read/pollute ~/.cache/tmr_tpu/autotune.json (a prior test's
    winners would otherwise short-circuit later measurements).

    The decoder-tail picks are stubbed by default (xla wins, so the quant
    stage short-circuits to "off" without a sweep): a REAL
    pick_decoder_impl at the production 128^2 x 1024 geometry is minutes
    of CPU matmul, and the pre-existing autotune tests exercise the
    attention/xcorr stages. Tail-election tests re-patch with their own
    stubs (or call the picks directly at tiny geometry)."""
    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("TMR_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(tmp_path / "no_seed.json"))
    monkeypatch.delenv("TMR_AUTOTUNE_FORCE", raising=False)
    monkeypatch.setattr(
        at, "pick_decoder_impl",
        lambda *a, **k: {"xla": 0.01, "fused": 0.02},
    )
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: {"off": 0.01, "int8": 0.02},
    )
    yield
    for k in KNOBS:
        os.environ.pop(k, None)


def _cfg():
    return preset("TMR_FSCD147", backbone="sam_vit_b", image_size=256,
                  batch_size=1)


def test_autotune_noop_off_tpu(clean_knobs):
    if jax.default_backend() == "tpu":
        pytest.skip("selection legitimately runs on TPU")
    assert at.autotune(_cfg(), 256, 1) == {}
    assert not any(k in os.environ for k in KNOBS)


def test_autotune_picks_min_and_exports_env(clean_knobs, monkeypatch):
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.03, "vmap": 0.05, "fft": 0.01},
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: {"blockwise": 0.03, "flash": 0.02},
    )
    report = at.autotune(_cfg(), 1024, 4)
    # the xcorr winner exports through the SMALL-scoped knob only: the
    # 127/191 buckets must keep their FFT auto path
    assert report["TMR_XCORR_IMPL_SMALL"]["picked"] == "fft"
    assert report["TMR_GLOBAL_ATTN"]["picked"] == "flash"
    assert os.environ["TMR_XCORR_IMPL_SMALL"] == "fft"
    assert "TMR_XCORR_IMPL" not in os.environ
    assert os.environ["TMR_GLOBAL_ATTN"] == "flash"


def test_autotune_exports_nothing_the_windowed_blocks_read(
    clean_knobs, monkeypatch
):
    """An ``autotune()`` run on a TPU backend must leave the windowed
    blocks' formulation where ``ops/pallas_attn.window_formulation`` put
    it: it sets no environment variable that models/vit.py or
    ops/pallas_attn.py reads on a windowed block's path (the parent swept
    the windows' knob among four variants without ``packed`` and exported
    the fastest, displacing PR 28's path), and a bfloat16 trace on that
    backend still takes ``packed`` afterwards."""
    import inspect

    from tmr_tpu.models import vit
    from tmr_tpu.ops import pallas_attn

    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.03, "fft": 0.01})
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: {"blockwise": 0.03, "flash": 0.02})
    before = dict(os.environ)
    report = at.autotune(_cfg(), 1024, 4)
    exported = {k for k, v in os.environ.items() if before.get(k) != v}
    assert exported and exported <= set(report), (exported, set(report))
    assert not any("WIN" in k for k in exported | set(report))
    # and the choice's own code reads no environment at all
    for fn in (vit.Attention._window_formulation,
               pallas_attn.window_formulation, pallas_attn.packed_supported):
        src = inspect.getsource(fn)
        assert "environ" not in src and "TMR_" not in src, fn.__name__
    monkeypatch.setattr(pallas_attn, "packed_window_ok", lambda *a: True)
    assert pallas_attn.window_formulation(
        (14, 14), 12, 64, jnp.bfloat16) == "packed"


def test_fallback_annotated_entries_never_win(clean_knobs, monkeypatch):
    """A gate-refused variant's timing is recorded annotated ("<impl>
    (fallback)") and must be excluded from winner selection even when it is
    the fastest row — it measured a DIFFERENT formulation than its label,
    and exporting it would set an invalid env value (ADVICE r4)."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.01, "pallas" + at.FALLBACK_SUFFIX: 1e-5},
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: {"blockwise": 0.03, "flash (fallback)": 0.001},
    )
    report = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert report["TMR_XCORR_IMPL_SMALL"]["picked"] == "conv"
    assert os.environ["TMR_XCORR_IMPL_SMALL"] == "conv"
    assert report["TMR_GLOBAL_ATTN"]["picked"] == "blockwise"
    assert os.environ["TMR_GLOBAL_ATTN"] == "blockwise"
    # the annotated evidence is preserved in the report
    assert "flash (fallback)" in report["TMR_GLOBAL_ATTN"]["times"]
    assert "pallas" + at.FALLBACK_SUFFIX in (
        report["TMR_XCORR_IMPL_SMALL"]["times"]
    )


def test_autotune_sweep_false_exports_cached_and_reports_pending(
    clean_knobs, monkeypatch, tmp_path
):
    """sweep=False (bench.py's preliminary pass) must export cached
    winners, run NO measurements, and report the knobs a full call would
    sweep under "_pending"."""
    import json

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    boom = lambda tag: lambda *a, **k: (_ for _ in ()).throw(
        AssertionError(f"{tag} swept under sweep=False")
    )
    monkeypatch.setattr(at, "pick_xcorr_impl", boom("x"))
    monkeypatch.setattr(at, "pick_global_attn_impl", boom("g"))
    monkeypatch.setattr(at, "pick_xcorr_precision", boom("p"))
    monkeypatch.setattr(at, "measure_rtt_floor", boom("rtt"))

    class _Dev:
        device_kind = "cpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    seed = tmp_path / "seed.json"
    seed.write_text(json.dumps({
        "cpu|1024|128|4|512|vit_b": {
            "TMR_GLOBAL_ATTN": "blockwise",
            "_variants_TMR_GLOBAL_ATTN": at._variants_sig(
                "TMR_GLOBAL_ATTN"
            ),
        }
    }))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(seed))
    report = at.autotune(_cfg(), 1024, 4, sweep=False)
    assert report["TMR_GLOBAL_ATTN"] == {"picked": "blockwise",
                                         "cached": True}
    assert os.environ["TMR_GLOBAL_ATTN"] == "blockwise"
    # the un-cached knobs are reported, not measured; the scores knob
    # resolved to its measurement-free no-op (seeded global formulation is
    # not folded) so it is recorded, not pending
    assert report["TMR_GLOBAL_SCORES_DTYPE"] == {"picked": "f32",
                                                 "times": {}}
    assert set(report["_pending"]) == {
        "TMR_XCORR_IMPL_SMALL", "TMR_XCORR_PRECISION",
        "TMR_DECODER_IMPL", "TMR_QUANT",
    }


def test_autotune_respects_explicit_knobs(clean_knobs, monkeypatch):
    monkeypatch.setenv("TMR_XCORR_IMPL", "conv")
    monkeypatch.setenv("TMR_XCORR_PRECISION", "highest")
    monkeypatch.setenv("TMR_GLOBAL_ATTN", "blockwise")
    monkeypatch.setenv("TMR_DECODER_IMPL", "xla")
    monkeypatch.setenv("TMR_QUANT", "off")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    called = []
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: called.append("x") or {}
    )
    monkeypatch.setattr(
        at, "pick_xcorr_precision", lambda *a, **k: called.append("p") or {}
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl", lambda *a, **k: called.append("g") or {}
    )
    monkeypatch.setattr(
        at, "pick_decoder_impl", lambda *a, **k: called.append("d") or {}
    )
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: called.append("q") or {}
    )
    # the one unpinned knob (scores dtype) completes its cache entry as
    # the f32 no-op — no measurement runs (the pinned global formulation
    # is not folded, so there is nothing to sweep)
    assert at.autotune(_cfg(), 1024, 4) == {
        "TMR_GLOBAL_SCORES_DTYPE": {"picked": "f32", "times": {}}
    }
    assert called == []
    assert os.environ["TMR_XCORR_IMPL"] == "conv"


def test_small_scope_keeps_fft_for_big_buckets(clean_knobs, monkeypatch):
    """TMR_XCORR_IMPL_SMALL must not reroute a >threshold capacity: the
    127/191 buckets stay on the FFT path regardless of the tuned winner."""
    from tmr_tpu.ops import xcorr

    monkeypatch.setenv("TMR_XCORR_IMPL_SMALL", "vmap")
    B, C, H, W, cap = 1, 2, 16, 16, 67
    assert cap > xcorr.FFT_CAPACITY_THRESHOLD
    feat = jnp.asarray(
        np.random.default_rng(0).standard_normal((B, C, H, W)), jnp.float32
    )
    tmpl = jnp.zeros((B, C, cap, cap), jnp.float32)
    tmpl = tmpl.at[:, :, cap // 2, cap // 2].set(1.0)
    thw = jnp.array([[1, 1]], jnp.int32)
    got = xcorr.cross_correlation(feat, tmpl, thw)
    # identity template: out == feat up to FFT rounding. The conv paths at
    # Precision.HIGHEST reproduce it exactly (diff == 0); nonzero rounding
    # proves the FFT path ran despite the small-scope knob.
    np.testing.assert_allclose(np.asarray(got), np.asarray(feat), atol=1e-4)
    assert abs(np.asarray(got) - np.asarray(feat)).max() > 0


@pytest.mark.slow
def test_microbenchmarks_run_and_time_all_variants(clean_knobs):
    """The pick_* functions themselves must run every variant end to end
    (tiny shapes; CPU is fine for exercising the machinery). Off-TPU the
    pallas xcorr gate refuses, so that row reports ANNOTATED — labeled
    with what was measured (the conv fallback), like the block sweeps."""
    tx = at.pick_xcorr_impl(1, 8, 16, 5, rtt=0.0)
    assert {k.replace(at.FALLBACK_SUFFIX, "") for k in tx} == set(
        at.XCORR_VARIANTS
    )
    assert "pallas" + at.FALLBACK_SUFFIX in tx and "pallas" not in tx
    assert all(v > 0 for v in tx.values())
    # global block (1024 tokens, the smallest grid that dispatches on the
    # knob): the kernels fall back off-TPU but must not crash the sweep;
    # the XLA formulations always time
    tg = at.pick_global_attn_impl(1, 32, 16, 2, rtt=0.0)
    assert {"blockwise", "blockfolded"} <= set(tg)
    assert all(v > 0 for v in tg.values())
    assert "TMR_XCORR_IMPL" not in os.environ  # knobs restored
    assert "TMR_GLOBAL_ATTN" not in os.environ


def test_autotune_precision_stage_flips_only_on_decisive_win(
    clean_knobs, monkeypatch
):
    """The TMR_XCORR_PRECISION sweep runs on the winning small-bucket impl
    and only leaves the reference-parity 'highest' when a variant wins by
    >10% (changed numerics need a decisive speedup); an fft winner skips
    the sweep entirely (the FFT path is f32 regardless)."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.01, "vmap": 0.05, "fft": 0.03},
    )
    monkeypatch.setattr(at, "pick_global_attn_impl", lambda *a, **k: {})
    swept = []
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: swept.append(1) or {
            "highest": 0.010, "default": 0.0095, "bf16": 0.0092
        },
    )
    r = at.autotune(_cfg(), 1024, 4)
    # best (bf16, 8% faster) is under the 10% bar -> parity precision stays
    assert swept and r["TMR_XCORR_PRECISION"]["picked"] == "highest"
    assert os.environ["TMR_XCORR_PRECISION"] == "highest"

    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setenv("TMR_AUTOTUNE_FORCE", "1")
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: {"highest": 0.010, "default": 0.004, "bf16": 0.006},
    )
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_PRECISION"]["picked"] == "default"
    assert os.environ["TMR_XCORR_PRECISION"] == "default"

    # fft winner: no sweep, cache records the f32 no-op
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.03, "vmap": 0.05, "fft": 0.01},
    )
    boom = lambda *a, **k: (_ for _ in ()).throw(AssertionError("swept"))
    monkeypatch.setattr(at, "pick_xcorr_precision", boom)
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_PRECISION"]["picked"] == "highest"


def test_autotune_tune_precision_false_skips_sweep(clean_knobs, monkeypatch):
    """Training runs (main.py passes tune_precision=False) must not export
    relaxed matcher numerics: the precision sweep never runs and the knob
    is never set."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.01, "vmap": 0.05, "fft": 0.03},
    )
    monkeypatch.setattr(at, "pick_global_attn_impl", lambda *a, **k: {})
    boom = lambda *a, **k: (_ for _ in ()).throw(AssertionError("swept"))
    monkeypatch.setattr(at, "pick_xcorr_precision", boom)
    r = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert "TMR_XCORR_PRECISION" not in r
    assert "TMR_XCORR_PRECISION" not in os.environ


def test_autotune_cached_precision_is_impl_specific(clean_knobs, monkeypatch):
    """A cached relaxed-precision winner was measured under one impl; a
    later run with a DIFFERENT pinned impl must re-measure instead of
    inheriting numerics whose decisive-win evidence does not transfer."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.01, "vmap": 0.05, "fft": 0.03},
    )
    monkeypatch.setattr(at, "pick_global_attn_impl", lambda *a, **k: {})
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: {"highest": 0.010, "default": 0.004, "bf16": 0.006},
    )
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_PRECISION"]["picked"] == "default"  # won on conv

    # same shapes, but the user pins a different impl: the cached 'default'
    # winner (measured on conv) must NOT be exported for vmap
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setenv("TMR_XCORR_IMPL_SMALL", "vmap")
    swept = []
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: swept.append(1) or {
            "highest": 0.010, "default": 0.0099, "bf16": 0.0098
        },
    )
    r = at.autotune(_cfg(), 1024, 4)
    assert swept, "must re-measure under the newly pinned impl"
    assert r["TMR_XCORR_PRECISION"]["picked"] == "highest"  # <10% on vmap
    assert os.environ["TMR_XCORR_PRECISION"] == "highest"

    # with the SAME impl as measured, the cached winner exports directly
    # (attention pinned: its sweep returned {} above so it was never cached)
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setenv("TMR_GLOBAL_ATTN", "blockwise")
    boom = lambda *a, **k: (_ for _ in ()).throw(AssertionError("swept"))
    monkeypatch.setattr(at, "pick_xcorr_precision", boom)
    monkeypatch.setattr(
        at, "pick_xcorr_impl", boom
    )
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_IMPL_SMALL"] == {"picked": "conv", "cached": True}


def test_autotune_cache_persists_winners_across_processes(
    clean_knobs, monkeypatch
):
    """Measured once -> cached; the next autotune at the same key exports
    the winners WITHOUT re-measuring (the 'measured winners become the
    defaults' mechanism); TMR_AUTOTUNE_FORCE re-measures."""
    calls = []
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: calls.append("x") or {"conv": 0.03, "fft": 0.01},
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: calls.append("g") or {"blockwise": 0.02,
                                              "flash": 0.01},
    )
    r1 = at.autotune(_cfg(), 1024, 4)
    assert calls == ["x", "g"]
    assert r1["TMR_GLOBAL_ATTN"]["picked"] == "flash"

    # fresh process simulation: knobs cleared, cache file remains
    for k in KNOBS:
        os.environ.pop(k, None)
    r2 = at.autotune(_cfg(), 1024, 4)
    assert calls == ["x", "g"], "cached hit must not re-measure"
    assert r2["TMR_XCORR_IMPL_SMALL"] == {"picked": "fft", "cached": True}
    assert r2["TMR_GLOBAL_ATTN"] == {"picked": "flash", "cached": True}
    assert os.environ["TMR_XCORR_IMPL_SMALL"] == "fft"
    assert os.environ["TMR_GLOBAL_ATTN"] == "flash"

    # a different shape key measures fresh
    for k in KNOBS:
        os.environ.pop(k, None)
    at.autotune(_cfg(), 1536, 1)
    assert calls == ["x", "g", "x", "g"]

    # force bypasses the cache
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setenv("TMR_AUTOTUNE_FORCE", "1")
    at.autotune(_cfg(), 1024, 4)
    assert calls == ["x", "g", "x", "g", "x", "g"]


def test_train_autotune_uses_separate_key_and_grad_sweep(
    clean_knobs, monkeypatch
):
    """train=True must (a) time the block sweeps with a gradient pass —
    the Pallas kernels' recompute backward inverts the fwd-only ranking —
    and (b) cache under a distinct key so eval winners never leak into
    training and vice versa."""
    seen_train = []
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fake_xcorr(*a, train=False, **k):
        seen_train.append(("x", train))
        return {"conv": 0.03, "fft": 0.01}

    monkeypatch.setattr(at, "pick_xcorr_impl", fake_xcorr)

    def fake_sweep(*a, train=False, **k):
        seen_train.append(("a", train))
        return ({"blockwise": 0.02, "flash": 0.01} if train
                else {"blockwise": 0.01, "flash": 0.02})

    monkeypatch.setattr(at, "pick_global_attn_impl", fake_sweep)

    r_eval = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert r_eval["TMR_GLOBAL_ATTN"]["picked"] == "blockwise"
    assert seen_train == [("x", False), ("a", False)]

    for k in KNOBS:
        os.environ.pop(k, None)
    r_train = at.autotune(_cfg(), 1024, 4, tune_precision=False, train=True)
    # the eval cache entry must NOT satisfy the train run, and every sweep
    # (xcorr included) must time with gradients
    assert seen_train[2:] == [("x", True), ("a", True)]
    assert r_train["TMR_GLOBAL_ATTN"]["picked"] == "flash"

    # both keys now cached independently
    for k in KNOBS:
        os.environ.pop(k, None)
    r2 = at.autotune(_cfg(), 1024, 4, tune_precision=False, train=True)
    assert r2["TMR_GLOBAL_ATTN"] == {"picked": "flash", "cached": True}
    for k in KNOBS:
        os.environ.pop(k, None)
    r3 = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert r3["TMR_GLOBAL_ATTN"] == {"picked": "blockwise", "cached": True}


@pytest.mark.slow
def test_block_sweep_train_mode_times_grad(clean_knobs, monkeypatch):
    """The real harness under train=True must build a differentiable step
    (value_and_grad through the block) and produce a time for every
    variant that can differentiate — on CPU every variant falls back to a
    differentiable path, so all seven global variants report. Off-TPU the
    flash/pallas gates refuse, so those entries come back ANNOTATED
    ("<impl> (fallback)"): the harness must label what it measured, never
    record a fallback timing under the requested name (ADVICE r4)."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    times = at.pick_global_attn_impl(1, 32, 16, 2, rtt=0.0, train=True)
    base = {k.replace(at.FALLBACK_SUFFIX, "") for k in times}
    assert base == set(at.GLOBAL_ATTN_VARIANTS)
    # CPU: the kernel gates refuse -> their rows must carry the annotation
    for impl in ("flash", "pallas"):
        assert impl + at.FALLBACK_SUFFIX in times and impl not in times
    assert all(t > 0 for t in times.values())


def test_the_sweep_holds_every_formulation_auto_can_answer():
    """``pick_global_attn_impl`` exports the fastest of
    ``GLOBAL_ATTN_VARIANTS`` whenever ``TMR_GLOBAL_ATTN`` is unset, which
    is when ``ops/pallas_attn.global_formulation`` decides: a formulation
    it can answer and the sweep lacks would be displaced by a slower one
    the first time ``--autotune`` runs (PERF.md section 6, PR 31, finding
    2). Each is also a legal explicit value of the knob."""
    from tmr_tpu.ops.pallas_attn import GLOBAL_FORMULATIONS

    assert "packed" in GLOBAL_FORMULATIONS
    assert set(GLOBAL_FORMULATIONS) <= set(at.GLOBAL_ATTN_VARIANTS)
    legal = at._validate_cache_obj({"k": {
        "TMR_GLOBAL_ATTN": "packed",
        "_variants_TMR_GLOBAL_ATTN": at._variants_sig("TMR_GLOBAL_ATTN"),
    }})
    assert legal["k"]["TMR_GLOBAL_ATTN"] == "packed"


def test_cached_winner_stale_when_variant_set_grows(clean_knobs, monkeypatch):
    """A cached winner is versioned by the variant set it beat
    (_variants_<knob>): growing the set (a new kernel) or a stamp-less
    legacy entry must trigger a re-sweep so new variants get their shot,
    while correctly stamped siblings stay cached."""
    calls = []
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: calls.append("x") or {"conv": 0.03, "fft": 0.01},
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: calls.append("g") or {"blockwise": 0.02,
                                              "flash": 0.01},
    )
    r1 = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert calls == ["x", "g"]

    # cached entries were stamped: a rerun re-measures nothing
    for k in KNOBS:
        os.environ.pop(k, None)
    at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert calls == ["x", "g"]

    # the global-attn variant set grows (new kernel lands): ONLY that knob
    # re-sweeps; the stamped siblings stay cached
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "GLOBAL_ATTN_VARIANTS",
        at.GLOBAL_ATTN_VARIANTS + ("newkernel",),
    )
    r3 = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert calls == ["x", "g", "g"]
    assert r3["TMR_XCORR_IMPL_SMALL"].get("cached") is True
    assert "cached" not in r3["TMR_GLOBAL_ATTN"]

    # legacy stamp-less entries (pre-versioning caches/seeds) also re-sweep
    import json
    path = os.environ["TMR_AUTOTUNE_CACHE"]
    j = json.load(open(path))
    for entry in j.values():
        for kk in list(entry):
            if kk.startswith("_variants_"):
                del entry[kk]
    json.dump(j, open(path, "w"))
    for k in KNOBS:
        os.environ.pop(k, None)
    at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert calls == ["x", "g", "g", "x", "g"]


def test_autotune_cached_hit_respects_explicit_knobs(
    clean_knobs, monkeypatch
):
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.03, "fft": 0.01}
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: {"blockwise": 0.02, "flash": 0.01},
    )
    at.autotune(_cfg(), 1024, 4)
    for k in KNOBS:
        os.environ.pop(k, None)
    # user pins the attention knob: the cached hit must not override it
    monkeypatch.setenv("TMR_GLOBAL_ATTN", "blockwise")
    r = at.autotune(_cfg(), 1024, 4)
    assert "TMR_GLOBAL_ATTN" not in r
    assert os.environ["TMR_GLOBAL_ATTN"] == "blockwise"
    assert r["TMR_XCORR_IMPL_SMALL"]["cached"] is True


def test_measured_tpu_defaults(monkeypatch):
    """VERDICT r3 #2 'measured winners become the defaults': with no knobs
    set, TPU processes default to the measured winners (windowed blocks
    ``packed``, on the benchmark's two cells: PERF.md section 6, PR 28,
    by ``window_formulation`` and no knob; TMR_XCORR_IMPL_SMALL=vmap,
    BENCH_LIVE.json); other backends keep the portable defaults; for
    the knobs that remain, explicit env always wins."""
    from tmr_tpu.ops import pallas_attn
    from tmr_tpu.ops import xcorr as xcorr_mod

    monkeypatch.delenv("TMR_XCORR_IMPL", raising=False)
    monkeypatch.delenv("TMR_XCORR_IMPL_SMALL", raising=False)
    monkeypatch.setattr(pallas_attn, "packed_window_ok", lambda *a: True)
    vit_b = ((14, 14), 12, 64, jnp.bfloat16)

    if jax.default_backend() != "tpu":  # portable default off-TPU
        assert pallas_attn.window_formulation(*vit_b) == "dense"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_attn.window_formulation(*vit_b) == "packed"
    assert pallas_attn.window_formulation((14, 14), 16, 80,
                                          jnp.bfloat16) == "packed"

    # xcorr: small-bucket default resolves to vmap on TPU. Observable via
    # the dispatch: identity-template correlation through a capacity-5
    # bucket must be exact under every conv-family impl, and the TPU
    # default must NOT be fft (fft would show rounding) — plus directly.
    feat = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 2, 8, 8)), jnp.float32
    )
    tmpl = jnp.zeros((1, 2, 5, 5), jnp.float32)
    tmpl = tmpl.at[:, :, 2, 2].set(1.0)
    thw = jnp.array([[1, 1]], jnp.int32)
    got = xcorr_mod.cross_correlation(feat, tmpl, thw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(feat))


def test_cache_accepts_measured_batch_winner(clean_knobs):
    """bench_extra's batch sweep persists TMR_BENCH_BATCH as a digit string
    (bench.py defaults its headline batch to it); non-numeric or
    non-positive values must be dropped by the cache validator."""
    at._cache_store("v5e|bench_batch|1024", {
        "TMR_BENCH_BATCH": {"picked": "8"},
    })
    assert at._cache_load()["v5e|bench_batch|1024"]["TMR_BENCH_BATCH"] == "8"

    import json
    path = os.environ["TMR_AUTOTUNE_CACHE"]
    with open(path) as f:
        obj = json.load(f)
    obj["v5e|bench_batch|1024"]["TMR_BENCH_BATCH"] = "abc"
    obj["other"] = {"TMR_BENCH_BATCH": "0"}
    with open(path, "w") as f:
        json.dump(obj, f)
    loaded = at._cache_load()
    assert "TMR_BENCH_BATCH" not in loaded.get("v5e|bench_batch|1024", {})
    assert "other" not in loaded


@pytest.mark.slow
def test_global_attn_knob_validates_and_matches(monkeypatch):
    """TMR_GLOBAL_ATTN forces the global-attention formulation at trace
    time: invalid values raise, and 'blockwise' matches the auto dispatch
    off-TPU (where the flash gate falls back to blockwise anyway)."""
    from tmr_tpu.models.vit import Block

    tokens = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 32, 32, 32)),
        jnp.bfloat16,
    )
    blk = Block(num_heads=2, window_size=0, rel_pos_size=(32, 32),
                dtype=jnp.bfloat16)
    monkeypatch.delenv("TMR_GLOBAL_ATTN", raising=False)
    params = jax.jit(blk.init)(jax.random.key(0), tokens)["params"]
    auto = blk.apply({"params": params}, tokens)

    monkeypatch.setenv("TMR_GLOBAL_ATTN", "blockwise")
    forced = blk.apply({"params": params}, tokens)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))

    monkeypatch.setenv("TMR_GLOBAL_ATTN", "spiral")
    with pytest.raises(ValueError, match="TMR_GLOBAL_ATTN"):
        blk.apply({"params": params}, tokens)


def test_autotune_seed_file_partial_sweep(clean_knobs, monkeypatch, tmp_path):
    """A committed seed file (AUTOTUNE_SEED.json) pre-covers knobs for a
    fresh machine: covered knobs export without measuring, ONLY the
    unseeded ones sweep, and a local user-cache entry for the same key
    fully supersedes the seed."""
    import json

    seed = tmp_path / "seed.json"
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    key = "|".join(str(p) for p in (
        jax.devices()[0].device_kind, 1024, 128, 4, 512, "vit_b"))
    seed.write_text(json.dumps({key: {
        "TMR_XCORR_IMPL_SMALL": "vmap", "TMR_GLOBAL_ATTN": "flash",
        # seeds carry the variant sets their winners beat (an unstamped
        # entry is treated as stale — covered by
        # test_cached_winner_stale_when_variant_set_grows)
        "_variants_TMR_XCORR_IMPL_SMALL": at._variants_sig(
            "TMR_XCORR_IMPL_SMALL"),
        "_variants_TMR_GLOBAL_ATTN": at._variants_sig("TMR_GLOBAL_ATTN"),
    }}))
    monkeypatch.setenv("TMR_AUTOTUNE_SEED", str(seed))

    calls = []
    boom = lambda tag: lambda *a, **k: calls.append(tag) or {}
    monkeypatch.setattr(at, "pick_xcorr_impl", boom("x"))
    monkeypatch.setattr(at, "pick_global_attn_impl", boom("g"))
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: calls.append("p") or {
            "highest": 0.01, "default": 0.002, "bf16": 0.003},
    )
    r = at.autotune(_cfg(), 1024, 4)
    # seeded knobs exported without their sweeps; unseeded ones measured
    assert "x" not in calls and "g" not in calls
    assert "p" in calls
    assert r["TMR_XCORR_IMPL_SMALL"] == {"picked": "vmap", "cached": True}
    assert r["TMR_GLOBAL_ATTN"] == {"picked": "flash", "cached": True}
    assert os.environ["TMR_GLOBAL_ATTN"] == "flash"
    # precision measured on the seeded vmap winner, decisive win -> default
    assert r["TMR_XCORR_PRECISION"]["picked"] == "default"

    # a local user-cache write supersedes the seed for that knob (the
    # measured run above already materialized the seeded winners into the
    # user file through its report, so the key is fully local now)
    for k in KNOBS:
        os.environ.pop(k, None)
    at._cache_store(key, {"TMR_XCORR_IMPL_SMALL": {"picked": "conv"}})
    cached = at._cache_load()[key]
    assert cached["TMR_XCORR_IMPL_SMALL"] == "conv"
    assert cached["TMR_GLOBAL_ATTN"] == "flash"

    # and with the user cache absent, the seed alone still serves
    os.environ["TMR_AUTOTUNE_CACHE"] = str(tmp_path / "fresh_cache.json")
    assert at._cache_load()[key]["TMR_XCORR_IMPL_SMALL"] == "vmap"


def test_cached_precision_dropped_when_impl_sweep_pending(
    clean_knobs, monkeypatch
):
    """Run A (impl pinned) caches a relaxed precision measured on conv.
    Run B (nothing pinned) will sweep impls fresh — the cached bf16 must
    NOT be exported ahead of that sweep: it is re-measured on whatever the
    fresh sweep picks, so relaxed numerics never outlive their pairing."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(at, "pick_global_attn_impl", lambda *a, **k: {})
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: {"highest": 0.010, "default": 0.004, "bf16": 0.003},
    )
    # run A: impl pinned to conv -> precision measured+cached under conv
    monkeypatch.setenv("TMR_XCORR_IMPL_SMALL", "conv")
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_PRECISION"]["picked"] == "bf16"

    # run B: unpinned; fresh impl sweep picks pallas. Cached bf16 must be
    # dropped and re-measured (mock shows <10% this time -> highest)
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "pick_xcorr_impl",
        lambda *a, **k: {"conv": 0.03, "vmap": 0.05, "pallas": 0.01},
    )
    reswept = []
    monkeypatch.setattr(
        at, "pick_xcorr_precision",
        lambda *a, **k: reswept.append(1) or {
            "highest": 0.010, "default": 0.0099, "bf16": 0.0098},
    )
    r = at.autotune(_cfg(), 1024, 4)
    assert r["TMR_XCORR_IMPL_SMALL"]["picked"] == "pallas"
    assert reswept, "cached precision must not be exported past a fresh sweep"
    assert r["TMR_XCORR_PRECISION"]["picked"] == "highest"
    assert os.environ["TMR_XCORR_PRECISION"] == "highest"


def test_scores_dtype_sweep_decisive_win_policy(clean_knobs, monkeypatch):
    """The TMR_GLOBAL_SCORES_DTYPE stage mirrors the xcorr precision
    policy: swept only when a folded formulation won, bf16 exported only
    on a decisive (>10%) win over the exact f32 baseline, f32 kept when
    the margin is thin or the baseline is missing, and the evidence paired
    to the formulation it was measured under."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.01})
    monkeypatch.setattr(
        at, "pick_xcorr_precision", lambda *a, **k: {"highest": 0.01})
    monkeypatch.setattr(
        at, "pick_global_attn_impl",
        lambda *a, **k: {"blockwise": 0.03, "blockfolded": 0.01},
    )

    # decisive win: bf16 exported, evidence paired to blockfolded
    monkeypatch.setattr(
        at, "pick_global_scores_dtype",
        lambda *a, **k: {"f32": 0.010, "bf16": 0.005},
    )
    report = at.autotune(_cfg(), 1024, 4)
    assert report["TMR_GLOBAL_SCORES_DTYPE"]["picked"] == "bf16"
    assert os.environ["TMR_GLOBAL_SCORES_DTYPE"] == "bf16"

    # thin margin: f32 kept
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "pick_global_scores_dtype",
        lambda *a, **k: {"f32": 0.010, "bf16": 0.0095},
    )
    monkeypatch.setenv(
        "TMR_AUTOTUNE_CACHE",
        os.environ["TMR_AUTOTUNE_CACHE"] + ".2",
    )
    report = at.autotune(_cfg(), 1024, 4)
    assert report["TMR_GLOBAL_SCORES_DTYPE"]["picked"] == "f32"
    assert os.environ["TMR_GLOBAL_SCORES_DTYPE"] == "f32"

    # fallback-annotated bf16 row (TMR_GLOBAL_ATTN gate refused mid-sweep)
    # must not be electable -> f32
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "pick_global_scores_dtype",
        lambda *a, **k: {"f32": 0.010,
                         "bf16" + at.FALLBACK_SUFFIX: 0.001},
    )
    monkeypatch.setenv(
        "TMR_AUTOTUNE_CACHE",
        os.environ["TMR_AUTOTUNE_CACHE"] + ".3",
    )
    report = at.autotune(_cfg(), 1024, 4)
    assert report["TMR_GLOBAL_SCORES_DTYPE"]["picked"] == "f32"

    # non-folded winner: stage records the no-op without sweeping
    for k in KNOBS:
        os.environ.pop(k, None)
    monkeypatch.setattr(
        at, "pick_global_attn_impl", lambda *a, **k: {"blockwise": 0.01})
    monkeypatch.setattr(
        at, "pick_global_scores_dtype",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("swept!")),
    )
    monkeypatch.setenv(
        "TMR_AUTOTUNE_CACHE",
        os.environ["TMR_AUTOTUNE_CACHE"] + ".4",
    )
    report = at.autotune(_cfg(), 1024, 4)
    assert report["TMR_GLOBAL_SCORES_DTYPE"]["picked"] == "f32"
    assert report["TMR_GLOBAL_SCORES_DTYPE"]["times"] == {}


def test_stale_winners_returns_only_stale_stamped_entries(
    clean_knobs, monkeypatch, tmp_path
):
    """stale_winners() feeds bench.py's pre-sweep bank: it must return
    exactly the cached winners whose variant stamp is stale (still-valid
    values the sweep will re-decide), skip fresh-stamped entries (those
    export normally), and respect explicit env pins."""
    import json

    class _Dev:
        device_kind = "cpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    cache = tmp_path / "cache.json"
    cache.write_text(json.dumps({
        "cpu|1024|128|4|512|vit_b": {
            "TMR_GLOBAL_ATTN": "blockfolded",
            "_variants_TMR_GLOBAL_ATTN": "old,set|old-rev",  # stale
            "TMR_XCORR_IMPL_SMALL": "vmap",
            "_variants_TMR_XCORR_IMPL_SMALL": at._variants_sig(
                "TMR_XCORR_IMPL_SMALL"),
            "TMR_XCORR_PRECISION": "bf16",
            "_variants_TMR_XCORR_PRECISION": "also,old",  # stale
        }
    }))
    monkeypatch.setenv("TMR_AUTOTUNE_CACHE", str(cache))
    out = at.stale_winners(_cfg(), 1024, 4)
    assert out == {"TMR_GLOBAL_ATTN": "blockfolded",
                   "TMR_XCORR_PRECISION": "bf16"}

    # an env pin wins over the stale entry
    monkeypatch.setenv("TMR_GLOBAL_ATTN", "blockwise")
    out = at.stale_winners(_cfg(), 1024, 4)
    assert out == {"TMR_XCORR_PRECISION": "bf16"}


def test_new_fused_variants_registered_and_rev_bumped():
    """The fused kernel and the XLA flash path must be electable sweep
    variants, and the _SWEEP_REV bump must make every pre-existing
    TMR_GLOBAL_ATTN winner stamp stale so it re-records at the next
    hardware window (the acceptance contract for registering a variant)."""
    assert "fused" in at.GLOBAL_ATTN_VARIANTS
    assert "xlaflash" in at.GLOBAL_ATTN_VARIANTS
    sig = at._variants_sig("TMR_GLOBAL_ATTN")
    assert "fused" in sig and "xlaflash" in sig
    assert sig.endswith("|" + at._SWEEP_REV)
    # the committed seed's stamps predate this revision by construction:
    # whatever they say, they must not equal the live signature
    for entry in at.seed_load().values():
        stamp = entry.get("_variants_TMR_GLOBAL_ATTN")
        if stamp is not None:
            assert stamp != sig, (
                "committed seed already stamped with the new revision — "
                "bump _SWEEP_REV when the variant set or harness changes"
            )
    # validation accepts the new variants as cached winners, and the
    # scores-dtype pairing stamp survives an 'auto' resolution (reload
    # churn fix: autotune.py _validate_cache_obj)
    kept = at._validate_cache_obj({
        "k": {"TMR_GLOBAL_ATTN": "fused", "_scores_global_impl": "auto"},
        "k2": {"TMR_GLOBAL_ATTN": "xlaflash"},
    })
    assert kept["k"]["TMR_GLOBAL_ATTN"] == "fused"
    assert kept["k"]["_scores_global_impl"] == "auto"
    assert kept["k2"]["TMR_GLOBAL_ATTN"] == "xlaflash"


def test_stale_winners_uses_vit_kind_helper():
    """stale_winners must derive the geometry family through _vit_kind —
    the single source shared with autotune()'s cache key — not an inlined
    mapping that can drift (the two keys must be identical or the banked
    wedge-fallback measurement reads the wrong cache row)."""
    import inspect

    src = inspect.getsource(at.stale_winners)
    assert "_vit_kind(" in src
    assert '"sam_vit_b"' not in src  # the old inlined dict is gone


@pytest.mark.slow
def test_block_sweep_fallback_rows_carry_structured_refusals(
    clean_knobs, monkeypatch
):
    """The real global-attention sweep harness off-TPU: gate-refused
    kernel variants come back fallback-annotated AND their rows carry the
    structured refusal causes (gate name, cause category, config) in
    LAST_SWEEP_REFUSALS — the sweep-side half of the gate_probe.json
    diagnostics (verdict r5 #1)."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    times = at._sweep_block_env(
        "TMR_GLOBAL_ATTN", ("blockwise", "pallas", "fused"),
        1, 32, 16, 2, 0.0, lambda s: None,
    )
    assert "blockwise" in times
    for impl, gate in (("pallas", "pallas_global_ok"),
                       ("fused", "pallas_fused_ok")):
        row = impl + at.FALLBACK_SUFFIX
        assert row in times and impl not in times
        causes = at.LAST_SWEEP_REFUSALS["TMR_GLOBAL_ATTN"][row]
        assert causes, f"{row} carries no structured causes"
        assert any(c["gate"] == gate for c in causes)
        for c in causes:
            assert c["schema"] == "gate_probe/v1"
            assert c["cause"]
            assert "config" in c and "device_kind" in c


def test_autotune_report_attaches_sweep_refusals(clean_knobs, monkeypatch):
    """autotune() must copy the harness's structured refusal causes into
    the report entry of any knob whose sweep produced fallback rows — the
    path bench.py's autotune_refusals JSON field reads."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cause = {"schema": "gate_probe/v1", "gate": "pallas_global_ok",
             "cause": "backend", "message": "", "exception": None,
             "config": {}, "backend": "cpu", "device_kind": "cpu"}

    def fake_global_sweep(*a, **k):
        at.LAST_SWEEP_REFUSALS["TMR_GLOBAL_ATTN"] = {
            "pallas" + at.FALLBACK_SUFFIX: [cause],
        }
        return {"blockwise": 0.03,
                "pallas" + at.FALLBACK_SUFFIX: 0.001}

    monkeypatch.setattr(at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.01})
    monkeypatch.setattr(at, "pick_global_attn_impl", fake_global_sweep)
    report = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert report["TMR_GLOBAL_ATTN"]["picked"] == "blockwise"
    ref = report["TMR_GLOBAL_ATTN"]["refusals"]
    assert ref == {"pallas" + at.FALLBACK_SUFFIX: [cause]}


# ----------------------------------------------- decoder-tail elections
def _stub_non_tail_picks(monkeypatch):
    """The tail-election tests exercise the TMR_DECODER_IMPL/TMR_QUANT
    stages only: every other sweep is stubbed (the real attention/xcorr
    microbenchmarks at the 1024 geometry are minutes of CPU work)."""
    monkeypatch.setattr(at, "measure_rtt_floor", lambda: 0.0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        at, "pick_xcorr_impl", lambda *a, **k: {"conv": 0.01}
    )
    monkeypatch.setattr(
        at, "pick_xcorr_precision", lambda *a, **k: {"highest": 0.01}
    )
    monkeypatch.setattr(
        at, "pick_global_attn_impl", lambda *a, **k: {"blockwise": 0.01}
    )
    monkeypatch.setattr(
        at, "pick_global_scores_dtype", lambda *a, **k: {"f32": 0.01}
    )


def test_decoder_tail_knobs_registered_and_rev_bumped():
    """TMR_DECODER_IMPL / TMR_QUANT / TMR_QUANT_STORAGE must be
    versioned sweep knobs with their variant sets registered, under the
    bumped "int8-storage" revision so every pre-storage winner
    re-records at the next hardware window (the stored arm joined the
    quant sweep)."""
    assert at.DECODER_IMPL_VARIANTS == ("xla", "fused")
    assert at.QUANT_VARIANTS == ("off", "int8")
    assert at.STORAGE_VARIANTS == ("off", "int8")
    assert "TMR_DECODER_IMPL" in at._VERSIONED_KNOBS
    assert "TMR_QUANT" in at._VERSIONED_KNOBS
    assert "TMR_QUANT_STORAGE" in at._VERSIONED_KNOBS
    assert at._SWEEP_REV == "int8-storage"
    # the quant knobs are revision-stamped too since the storage arm
    # joined (pre-storage winners must go stale)
    assert at._variants_sig("TMR_DECODER_IMPL").endswith(at._SWEEP_REV)
    assert at._variants_sig("TMR_QUANT").endswith(at._SWEEP_REV)
    assert at._variants_sig("TMR_QUANT_STORAGE").endswith(at._SWEEP_REV)


def test_autotune_elects_decoder_impl_then_quant(clean_knobs, monkeypatch):
    """The tail stages run AFTER the attention/xcorr stages: the impl
    sweep elects plain-min (both formulations are oracle-pinned identical
    numerics), then the quant sweep applies the decisive-win policy
    against the exact baseline and stamps which impl its evidence was
    measured under."""
    _stub_non_tail_picks(monkeypatch)
    monkeypatch.setattr(
        at, "pick_decoder_impl",
        lambda *a, **k: {"xla": 0.02, "fused": 0.01},
    )
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: {"off": 0.02, "int8": 0.01},
    )
    report = at.autotune(_cfg(), 1024, 4, tune_precision=True)
    assert report["TMR_DECODER_IMPL"]["picked"] == "fused"
    assert os.environ["TMR_DECODER_IMPL"] == "fused"
    assert report["TMR_QUANT"]["picked"] == "int8"  # 2x: decisive
    assert os.environ["TMR_QUANT"] == "int8"
    cache = at._cache_load()
    entry = cache[at._cache_key(_cfg(), 1024, 4, "vit_b", False)]
    assert entry["_quant_decoder_impl"] == "fused"


def test_quant_indecisive_win_keeps_exact(clean_knobs, monkeypatch):
    _stub_non_tail_picks(monkeypatch)
    monkeypatch.setattr(
        at, "pick_decoder_impl",
        lambda *a, **k: {"xla": 0.02, "fused": 0.01},
    )
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: {"off": 0.0100, "int8": 0.0095},
    )
    report = at.autotune(_cfg(), 1024, 4, tune_precision=True)
    assert report["TMR_QUANT"]["picked"] == "off"  # <10%: not decisive
    assert os.environ["TMR_QUANT"] == "off"


def test_quant_sweep_skipped_when_xla_wins(clean_knobs, monkeypatch):
    """int8 rides the fused formulation only: when xla wins the impl
    sweep, the quant stage records "off" WITHOUT sweeping (the no-op
    completes the cache entry so later runs skip)."""
    _stub_non_tail_picks(monkeypatch)
    monkeypatch.setattr(
        at, "pick_decoder_impl",
        lambda *a, **k: {"xla": 0.01, "fused": 0.02},
    )
    calls = []
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: calls.append(1) or {"off": 0.01},
    )
    report = at.autotune(_cfg(), 1024, 4, tune_precision=True)
    assert report["TMR_DECODER_IMPL"]["picked"] == "xla"
    assert report["TMR_QUANT"] == {"picked": "off", "times": {}}
    assert os.environ["TMR_QUANT"] == "off"
    assert not calls


def test_quant_not_swept_for_training(clean_knobs, monkeypatch):
    """tune_precision=False (the training entry): quantized weights must
    never be elected into a training program."""
    _stub_non_tail_picks(monkeypatch)
    report = at.autotune(_cfg(), 1024, 4, tune_precision=False)
    assert "TMR_QUANT" not in report
    assert "TMR_QUANT" not in os.environ


def test_cached_quant_dropped_when_impl_evidence_changes(
    clean_knobs, monkeypatch
):
    """A cached int8 winner's decisive-win evidence is decoder-impl-
    specific: when the active impl no longer matches the stamped
    _quant_decoder_impl (or the impl is about to re-sweep), the cached
    quant entry must be dropped and re-decided, not inherited."""
    import json

    _stub_non_tail_picks(monkeypatch)
    monkeypatch.setattr(
        at, "pick_decoder_impl",
        lambda *a, **k: {"xla": 0.01, "fused": 0.02},
    )
    calls = []
    monkeypatch.setattr(
        at, "pick_quant", lambda *a, **k: calls.append(1) or {"off": 0.01},
    )
    cache_path = os.environ["TMR_AUTOTUNE_CACHE"]
    key = at._cache_key(_cfg(), 1024, 4, "vit_b", False)
    sig_impl = at._variants_sig("TMR_DECODER_IMPL")
    sig_quant = at._variants_sig("TMR_QUANT")
    with open(cache_path, "w") as f:
        json.dump({key: {
            "TMR_QUANT": "int8",
            "_quant_decoder_impl": "fused",
            "_variants_TMR_DECODER_IMPL": sig_impl,
            "_variants_TMR_QUANT": sig_quant,
        }}, f)
    report = at.autotune(_cfg(), 1024, 4, tune_precision=True)
    # the impl sweep ran (nothing cached for it), xla won -> the stale
    # int8 entry was dropped, and the no-op "off" recorded in its place
    assert os.environ["TMR_QUANT"] == "off"
    assert report["TMR_QUANT"]["picked"] == "off"


def test_tail_sweeps_skipped_for_no_boxreg_models(clean_knobs, monkeypatch):
    """Single-stack (box-regression-ablated) models stay on the module
    path: no TMR_DECODER_IMPL/TMR_QUANT sweep, nothing exported."""
    _stub_non_tail_picks(monkeypatch)
    cfg = _cfg()
    cfg.ablation_no_box_regression = True
    report = at.autotune(cfg, 1024, 4, tune_precision=True)
    assert "TMR_DECODER_IMPL" not in report
    assert "TMR_DECODER_IMPL" not in os.environ
    assert "TMR_QUANT" not in os.environ


@pytest.mark.slow
def test_pick_decoder_impl_real_microbenchmark(monkeypatch, tmp_path):
    """The real _sweep_tail_env harness at a tiny geometry: both
    formulations time cleanly (no fallback annotation — the fused gate
    passes at this shape), through the SAME stage program bench.py and
    profile_breakdown measure."""
    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    times = at.pick_decoder_impl(1, 8, 16, 1, 3, rtt=0.0)
    assert set(times) == {"xla", "fused"}
    assert all(v > 0 for v in times.values())
    for k in KNOBS:
        os.environ.pop(k, None)


def test_pick_quant_sums_decoder_and_xcorr_stages(monkeypatch):
    """With emb_dim given, pick_quant's evidence is the SUM of the two
    surfaces the export flips (decoder tail + matcher correlation); a
    fallback annotation in EITHER stage poisons the combined row, the
    tail stage's refusal causes survive the xcorr sweep's clear, and the
    stored arm ("int8+store", swept via TMR_QUANT_STORAGE) reuses the
    int8 correlation timing (storage never touches the matcher)."""
    def tail_sweep(env_var, *a, **k):
        if env_var == "TMR_QUANT_STORAGE":
            at.LAST_SWEEP_REFUSALS.setdefault(env_var, {}).clear()
            return {"int8": 0.007}
        at.LAST_SWEEP_REFUSALS.setdefault("TMR_QUANT", {}).update(
            {"int8" + at.FALLBACK_SUFFIX: [{"gate": "quant_ok"}]}
        )
        return {"off": 0.010, "int8" + at.FALLBACK_SUFFIX: 0.008}

    monkeypatch.setattr(at, "_sweep_tail_env", tail_sweep)
    monkeypatch.setattr(
        at, "_sweep_xcorr_env",
        lambda env_var, *a, **k: (
            at.LAST_SWEEP_REFUSALS.setdefault(env_var, {}).clear()
            or {"off": 0.004, "int8": 0.003}
        ),
    )
    times = at.pick_quant(1, 8, 16, 1, 3, emb_dim=16, rtt=0.0)
    assert times == {"off": 0.014,
                     "int8" + at.FALLBACK_SUFFIX: pytest.approx(0.011),
                     "int8+store": pytest.approx(0.010)}
    assert at._electable(times) == {"off": 0.014,
                                    "int8+store": pytest.approx(0.010)}
    # the decoder stage's structured causes were merged back
    assert at.LAST_SWEEP_REFUSALS["TMR_QUANT"][
        "int8" + at.FALLBACK_SUFFIX
    ] == [{"gate": "quant_ok"}]


@pytest.mark.slow
def test_pick_quant_annotates_refused_rows(monkeypatch):
    """A quant sweep run while the fused gate refuses (kill-switch) must
    record the int8 row annotated as a fallback with its structured
    causes — quantized timings never masquerade as exact-path evidence."""
    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("TMR_DECODER_IMPL", "fused")
    monkeypatch.setenv("TMR_NO_FUSED_HEADS", "1")
    from tmr_tpu.ops import fused_heads as fh

    fh._OK_CACHE.clear()
    try:
        times = at.pick_quant(1, 8, 16, 1, 3, rtt=0.0)
        # every row fell back (impl gate refused under both TMR_QUANT
        # values), so each is annotated and none is electable
        assert times
        assert all(k.endswith(at.FALLBACK_SUFFIX) for k in times)
        assert at._electable(times) == {}
        refusals = at.LAST_SWEEP_REFUSALS.get("TMR_QUANT", {})
        assert any(refusals.values())
    finally:
        fh._OK_CACHE.clear()
        for k in KNOBS:
            os.environ.pop(k, None)

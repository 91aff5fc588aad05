#!/usr/bin/env python
"""Pick formulation winners from FULL-PROGRAM bench A/Bs (VERDICT r4 #4).

The autotune sweep times one isolated stage per formulation; round 4 showed
that granularity can disagree with the production program (the sweep
crowned a formulation that a one-stage profile measured slower than the
one it displaced). Per the verdict, the resolution is to record BOTH
granularities and let the FULL-PROGRAM number decide: a battery benches
the complete fused eval program under env-pinned formulation combos plus
the autotuned headline (bench.py's JSON records); this script reads those
records and, when an env-pinned combo beats the autotuned headline
decisively (>3% img/s), pins its knobs into AUTOTUNE_SEED.json so every
later process (including the driver's round-end bench) defaults to the
full-program winner instead of re-running the one-block sweep ranking.

Offline: operates purely on the battery's JSON outputs.
Prints one JSON summary line; exit 0 = seed updated, 3 = no update needed
(headline already optimal or no valid records), 1 = error.

Usage: python scripts/pick_full_program.py [bench1.json bench2.json ...]
(defaults to the autotuned headline's record in the repo root).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_FILES = (
    "bench_live.json",      # autotuned headline (sweep-ranked winners)
)
#: knobs a full-program winner may pin (the matcher's small-bucket
#: formulation; batch is handled by bench_extra's own sweep)
PINNABLE = ("TMR_XCORR_IMPL_SMALL",)
#: decisive-win margin: below this the sweep ranking stands (same
#: philosophy as the precision stage's >10% bar, scaled to whole-program
#: variance)
MARGIN = 1.03


def _load(path):
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or "error" in rec or not rec.get("value"):
        return None
    return rec


def _pinned(rec) -> dict:
    """The knobs this record ran with that were EXTERNALLY pinned (set in
    the env before launch), as opposed to autotune-exported: bench.py's
    "knobs" field reports the env at trace time, which includes the sweep's
    own exports — a knob is a pin only when it does NOT also appear in the
    "autotuned" report."""
    auto = rec.get("autotuned", {})
    return {
        k: v for k, v in rec.get("knobs", {}).items()
        if k in PINNABLE and k not in auto
    }


def main(argv=None) -> int:
    files = (argv if argv else sys.argv[1:]) or [
        os.path.join(REPO, f) for f in DEFAULT_FILES
    ]
    records = {}
    for p in files:
        rec = _load(p)
        if rec is not None:
            records[os.path.basename(p)] = rec
    if not records:
        print(json.dumps({"updated": False,
                          "reason": "no valid bench records"}))
        return 3
    # the baseline is the autotuned headline (no externally pinned
    # formulation knobs); every record with pins is a full-program A/B row
    baseline = None
    for name in ("bench_live.json", "BENCH_LIVE.json"):
        if name in records and not _pinned(records[name]):
            baseline = records[name]
            break
    best_name, best = max(records.items(), key=lambda kv: kv[1]["value"])
    summary = {
        "candidates": {
            n: {"img_per_sec": r["value"], "pinned": _pinned(r)}
            for n, r in records.items()
        },
        "best": best_name,
    }
    pinned = _pinned(best)
    if not pinned:
        summary.update(updated=False,
                       reason="autotuned headline is already the best")
        print(json.dumps(summary))
        return 3
    if baseline is None:
        # no valid unpinned headline to compare against: refusing is the
        # only safe call — pinning without the margin check would commit a
        # combo that was never shown to beat the autotuned program
        summary.update(
            updated=False,
            reason="no valid autotuned baseline record; not pinning",
        )
        print(json.dumps(summary))
        return 3
    if best["value"] < baseline["value"] * MARGIN:
        summary.update(
            updated=False,
            reason=f"best pinned combo {best['value']} not a decisive win "
                   f"over autotuned {baseline['value']} (margin {MARGIN})",
        )
        print(json.dumps(summary))
        return 3

    # pin into the committed seed under the headline's autotune key, with
    # fresh variant stamps so the entry loads as a valid cached hit
    from tmr_tpu.utils.autotune import (
        SEED_PATH,
        _variants_sig,
        seed_load,
        seed_store,
    )

    seed = seed_load()
    # headline config key: matches autotune()'s key for the bench program
    # (device kind | image | up_hw | batch | emb | vit kind). Update ONLY
    # entries matching the winning record's image size AND batch — a
    # batch-4 A/B must not overwrite a batch-8 entry's winners, nor a
    # 256-px dry run a 1024 entry. New keys are created only when the
    # record carries device_kind + image_size + batch (bench.py emits
    # all three); fabricating any of them would poison the seed.
    batch = best.get("batch")
    size = best.get("image_size")

    def _key_matches(k: str) -> bool:
        # positional comparison — substring matching would collide with
        # the other pipe-delimited fields (emb=512 is in every key,
        # up_hw=128 in the 1024 entry)
        parts = k.split("|")
        if len(parts) != 6 or parts[5] != "vit_b":
            return False
        return (
            (size is None or parts[1] == str(size))
            and (batch is None or parts[3] == str(batch))
        )

    keys = [k for k in seed if _key_matches(k)]
    if not keys:
        kind = best.get("device_kind")
        if not kind or batch is None or size is None:
            summary.update(
                updated=False,
                reason="no matching seed entry and the record lacks "
                       "device_kind/image_size/batch to build one",
            )
            print(json.dumps(summary))
            return 3
        # up_hw = 2x the 16-px patch grid (feature_upsample, bench preset);
        # emb 512 = the flagship preset — both fixed for the bench program
        keys = [f"{kind}|{size}|{2 * (size // 16)}|{batch}|512|vit_b"]
    updated = {}
    for key in keys:
        entry = dict(seed.get(key, {}))
        from tmr_tpu.utils.autotune import _VERSIONED_KNOBS

        for k, v in pinned.items():
            entry[k] = str(v)
            if k in _VERSIONED_KNOBS:
                # every versioned knob needs a fresh stamp or the loader
                # drops the pin as stale on the very next run
                entry[f"_variants_{k}"] = _variants_sig(k)
        # full-program A/Bs supersede the one-stage sweep for a
        # formulation knob: left at its autotuned value by the winner, it
        # is also full-program-endorsed (it was part of the winning run)
        auto = best.get("autotuned", {})
        for k in PINNABLE:
            if k not in pinned and k in auto:
                entry[k] = auto[k]
                entry[f"_variants_{k}"] = _variants_sig(k)
        entry["_full_program_ab"] = json.dumps(
            {n: r["value"] for n, r in records.items()}, sort_keys=True
        )
        seed[key] = entry
        updated[key] = {k: entry[k] for k in PINNABLE if k in entry}
    seed_store(seed)
    summary.update(
        updated=True,
        seed=os.environ.get("TMR_AUTOTUNE_SEED", SEED_PATH),
        entries=updated,
    )
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())

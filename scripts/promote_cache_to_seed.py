#!/usr/bin/env python
"""Promote freshly measured autotune winners from the user cache into the
committed seed (AUTOTUNE_SEED.json).

Why: a sweep stores its winners in ``<repo>/.tmr_cache/autotune.json``
— git-ignored, so it does not survive a fresh checkout. A run from the
committed tree starts from AUTOTUNE_SEED.json, so winners must reach it (and
be committed) to spare that run a full re-sweep. ``scripts/pick_full_program.py`` already writes the seed on a
DECISIVE full-program win; this script covers the other outcome — the
sweep ran, its winners stand (no pinned combo beat them), and they carry
CURRENT variant stamps that the committed seed lacks.

Policy: only knob entries whose ``_variants_<knob>`` stamp in the cache
matches the CURRENT sweep signature are promoted (a stale cached winner
must re-sweep, not get laundered into the seed); existing seed values are
overwritten only by stamped-fresh cache values. Prints one JSON summary
line; rc 0 = seed updated, 3 = nothing to promote, 1 = error.

Offline. Usage: python scripts/promote_cache_to_seed.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    from tmr_tpu.utils.autotune import (
        _VERSIONED_KNOBS,
        _load_validated,
        _variants_sig,
        CACHE_PATH,
        seed_load,
        seed_store,
    )

    cache_path = os.environ.get("TMR_AUTOTUNE_CACHE", CACHE_PATH)
    cache = _load_validated(cache_path)
    if not cache:
        print(json.dumps({"updated": False, "reason": "empty user cache"}))
        return 3
    seed = seed_load()

    #: knobs a full-program A/B (scripts/pick_full_program.py) may have
    #: pinned — its whole-program evidence outranks the one-block sweep,
    #: so promotion must not overwrite them in an entry carrying the
    #: _full_program_ab marker WHILE the pin's own stamp is still current.
    #: Once a _SWEEP_REV bump stales the pin, runtime drops it and
    #: re-sweeps anyway, so the fresh sweep winner must promote or every
    #: fresh container re-sweeps forever.
    FULL_PROGRAM_KNOBS = ("TMR_GLOBAL_ATTN",)

    promoted = {}
    for key, entry in cache.items():
        out = dict(seed.get(key, {}))
        changed = {}
        for knob in _VERSIONED_KNOBS:
            if (
                knob in FULL_PROGRAM_KNOBS
                and "_full_program_ab" in out
                and out.get(f"_variants_{knob}") == _variants_sig(knob)
            ):
                continue
            stamp = entry.get(f"_variants_{knob}")
            if knob in entry and stamp == _variants_sig(knob):
                if (out.get(knob), out.get(f"_variants_{knob}")) != (
                    entry[knob], stamp
                ):
                    out[knob] = entry[knob]
                    out[f"_variants_{knob}"] = stamp
                    changed[knob] = entry[knob]
                    if (
                        knob in FULL_PROGRAM_KNOBS
                        and "_full_program_ab" in out
                    ):
                        # the stale pin just got replaced by a SWEEP
                        # winner: drop the marker, or the sweep pick would
                        # inherit pin-level protection it never earned
                        del out["_full_program_ab"]
        # _precision_impl is the impl pairing TMR_XCORR_PRECISION's
        # decisive win was validated under — it moves ONLY with its owner
        # (a lone stale pairing would vouch for numerics on the wrong impl)
        if "TMR_XCORR_PRECISION" in changed and "_precision_impl" in entry:
            if out.get("_precision_impl") != entry["_precision_impl"]:
                out["_precision_impl"] = entry["_precision_impl"]
                changed["_precision_impl"] = entry["_precision_impl"]
        # same ownership rule for the scores-dtype <-> global formulation
        # pairing: it moves only with its owner knob
        if ("TMR_GLOBAL_SCORES_DTYPE" in changed
                and "_scores_global_impl" in entry):
            if out.get("_scores_global_impl") != entry["_scores_global_impl"]:
                out["_scores_global_impl"] = entry["_scores_global_impl"]
                changed["_scores_global_impl"] = entry["_scores_global_impl"]
        # the measured throughput-optimal batch is an independent
        # measurement: rides alone
        if (
            "TMR_BENCH_BATCH" in entry
            and out.get("TMR_BENCH_BATCH") != entry["TMR_BENCH_BATCH"]
        ):
            out["TMR_BENCH_BATCH"] = entry["TMR_BENCH_BATCH"]
            changed["TMR_BENCH_BATCH"] = entry["TMR_BENCH_BATCH"]
        if changed:
            seed[key] = out
            promoted[key] = changed

    if not promoted:
        print(json.dumps({"updated": False,
                          "reason": "no stamped-fresh winners to promote"}))
        return 3
    seed_store(seed)
    from tmr_tpu.utils.autotune import SEED_PATH

    print(json.dumps({
        "updated": True,
        "seed": os.environ.get("TMR_AUTOTUNE_SEED", SEED_PATH),
        "promoted": promoted,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

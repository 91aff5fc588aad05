"""Test env: force CPU JAX with 8 virtual devices (SURVEY.md §4).

Must run before any `import jax` — pytest imports conftest first. The 8
virtual devices stand in for a TPU slice so every sharding / collective path
(the DDP + mapper/reducer replacements) is exercised in CI without hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache for the suite (the same
# utils/cache.enable_compilation_cache every CLI already calls: at
# JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache; failures
# degrade to a warning). The tier-1 run sits within seconds of its hard timeout and
# most of that wall is XLA recompiling the same tiny-geometry programs
# every run — a warm cache cuts repeat runs far below the limit.
from tmr_tpu.utils.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


# ---------------------------------------------------------------------
# Tier-1 runtime budget guard: the verify command runs the suite under a
# hard `timeout 870`, and the suite already consumes most of it — a new
# test that quietly adds a minute fails EVERY future session with an
# opaque timeout instead of a diagnosis. The guard records per-test
# durations and, when the session's wall clock projects past the budget,
# warns on stderr (non-fatal) naming the slowest tests so the costly
# addition is attributable.

import time as _time

#: the tier-1 hard timeout (ROADMAP.md verify command) and the fraction
#: of it that triggers the warning — at 92% a normal run variance (~5%)
#: can already push past the limit
_TIER1_BUDGET_S = 870.0
_TIER1_WARN_FRACTION = 0.92

_SESSION_T0 = _time.time()
_TEST_DURATIONS: dict = {}


def pytest_runtest_logreport(report):
    if report.duration:
        _TEST_DURATIONS[report.nodeid] = (
            _TEST_DURATIONS.get(report.nodeid, 0.0) + report.duration
        )


def pytest_sessionfinish(session, exitstatus):
    import sys

    total = _time.time() - _SESSION_T0
    if total < _TIER1_WARN_FRACTION * _TIER1_BUDGET_S:
        return
    slowest = sorted(_TEST_DURATIONS.items(), key=lambda kv: -kv[1])[:10]
    lines = [
        f"\n[tier1-budget] WARNING: suite wall {total:.0f}s is "
        f">= {_TIER1_WARN_FRACTION:.0%} of the {_TIER1_BUDGET_S:.0f}s "
        "tier-1 timeout — slow-mark or shrink the heaviest tests "
        "before the next one times the whole suite out.",
        "[tier1-budget] slowest tests this session:",
    ]
    lines += [f"[tier1-budget]   {d:7.1f}s  {nodeid}"
              for nodeid, d in slowest]
    print("\n".join(lines), file=sys.stderr, flush=True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute test (real-width compiles, depth-32 goldens, "
        "e2e training); excluded from the fast dev loop",
    )
    config.addinivalue_line(
        "markers",
        "fast: auto-applied complement of slow — `pytest -m fast` is the "
        "sub-2-minute dev loop, the full (unmarked) run is CI",
    )


def pytest_collection_modifyitems(config, items):
    """Every test not marked slow is fast: `-m fast` and `-m "not slow"`
    select the identical set, so the dev loop works with either spelling
    (VERDICT r4 #8 asks for `pytest -m fast` under 120s)."""
    import pytest

    for item in items:
        if item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.fast)

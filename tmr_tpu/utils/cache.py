"""Process-level JAX set-up shared by the CLIs: device selection and the
persistent XLA compilation cache.

First compiles of the ViT-H/B programs cost tens of seconds to minutes;
the jax persistent cache makes every later process that sees the same
directory reuse them. Enabled uniformly by the CLIs and scripts that
compile programs (main.py, bench.py, demo.py, extract_feature.py,
chip_smoke.py, the scripts/ drivers) — library code never mutates global
jax config.

Where the cache lives is decided from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and this module
sets no directory (only the thresholds, so that every program is cached).
Otherwise the cache goes to one fixed path inside the checkout,
``<repo>/.jax_cache`` (git-ignored) — the path is part of the cache key's
lookup, so it never depends on ``~``, a temp name, a pid or a time.
``JAX_ENABLE_COMPILATION_CACHE=false`` is JAX's own opt-out.
Failures to enable (read-only checkout) degrade to a warning + None
instead of raising, so the uniform call sites never turn a run into a
crash over a cache nicety.
"""

from __future__ import annotations

import os
import warnings

#: the checkout's root: tmr_tpu/utils/cache.py -> three levels up
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")

#: where the repo's own measured state lives by default (autotune cache,
#: live winner bank): beside the compile cache, inside the checkout
STATE_DIR = os.path.join(REPO_ROOT, ".tmr_cache")


def select_device(device: str) -> None:
    """Hold the process to the ``--device`` a CLI was given, or exit.

    ``cpu`` pins JAX to the CPU platform (call before any device access).
    ``tpu`` is checked, not assumed: when the default backend is anything
    else the process exits with an error — a run that was asked for the
    chip never carries on without one."""
    import jax

    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
        return
    if device != "tpu":
        raise SystemExit(f"--device {device!r}: expected 'tpu' or 'cpu'")
    try:
        backend = jax.default_backend()
    except RuntimeError as e:  # JAX_PLATFORMS names a platform not there
        raise SystemExit(f"--device tpu: no TPU backend: {e}")
    if backend != "tpu":
        raise SystemExit(
            f"--device tpu: the default JAX backend is {backend!r}, not "
            "'tpu' (no chip attached, or JAX_PLATFORMS holds the process "
            "elsewhere); pass --device cpu to run on the CPU"
        )


def enable_compilation_cache() -> str | None:
    """Turn on the persistent compilation cache (idempotent).

    Returns the cache directory in use, or None when enabling failed —
    failures warn instead of raising so library/CLI callers can enable
    unconditionally.
    """
    try:
        import jax

        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = DEFAULT_DIR
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        # cache every program regardless of size/compile time
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    except Exception as e:  # cache is a nicety; never a crash
        warnings.warn(
            f"persistent compilation cache disabled: {type(e).__name__}: {e}"
        )
        return None
    return path


def persistent_cache_dir() -> str | None:
    """The directory JAX's persistent compilation cache uses in this
    process, or None: none configured (``enable_compilation_cache`` not
    called and ``JAX_COMPILATION_CACHE_DIR`` unset), or the cache switched
    off (``JAX_ENABLE_COMPILATION_CACHE=false``). What the repo keeps
    beside the compiled programs (``diagnostics.run_outside_trace``: the
    results of the gates' self-checks) goes there and nowhere else."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None

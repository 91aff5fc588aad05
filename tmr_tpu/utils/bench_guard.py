"""Shared watchdog + error funnel for the benchmark entry points.

bench.py and scripts/bench_extra.py share one contract with the driver:
stdout carries EXACTLY ONE JSON line, success or not (an early driver
round recorded a raw jax.devices() traceback, ``parsed: null``); this helper is the single implementation both scripts
run under so a fix to it can never land in one and miss the other.

Guarantees:
- a daemon-timer watchdog (survives the main thread being stuck inside a
  native PJRT call) emits the error record and ``os._exit(2)``s on overrun;
- the run callback receives a zero-arg ``cancel()`` and MUST call it
  immediately before printing its success line, so a run finishing near
  the alarm can't print success AND have the timer append a second record;
- any exception — including SystemExit raised beyond argparse — funnels to
  ``emit_error`` with exit code 1; only KeyboardInterrupt re-raises;
- a malformed alarm env value falls back to the default instead of
  crashing outside the guard.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional

WATCHDOG_MSG = "watchdog: no result after {alarm}s"


def run_guarded(
    run_fn: Callable[[Callable[[], None]], Optional[int]],
    emit_error: Callable[[str], None],
    alarm_env: str = "TMR_BENCH_ALARM",
    default_alarm: int = 3300,
) -> int:
    """Run ``run_fn(cancel)`` under the one-JSON-line contract; returns the
    process exit code (run_fn's return, 0 when None, 1 on funneled error)."""
    try:
        alarm = int(os.environ.get(alarm_env, default_alarm))
    except ValueError:
        alarm = default_alarm

    watchdog = None
    if alarm > 0:
        def fire():
            # an emitter may return an explicit exit code (bench.py
            # returns 0 when it printed a banked preliminary MEASUREMENT
            # instead of an outage record); default stays 2
            code = emit_error(WATCHDOG_MSG.format(alarm=alarm))
            os._exit(2 if code is None else int(code))

        watchdog = threading.Timer(alarm, fire)
        watchdog.daemon = True
        watchdog.start()

    def cancel():
        if watchdog is not None:
            watchdog.cancel()

    try:
        rc = run_fn(cancel)
        return 0 if rc is None else int(rc)
    except BaseException as e:  # noqa: BLE001 — the JSON line IS the contract
        if isinstance(e, KeyboardInterrupt):
            raise
        code = emit_error(f"{type(e).__name__}: {e}")
        return 1 if code is None else int(code)
    finally:
        cancel()

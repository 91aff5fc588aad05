"""Continuous in-production autotune (ROADMAP item 5): shadow-elected
formulation winners with per-device-generation winner banks.

The offline sweep (utils/autotune.py) can only re-elect winners at rare
hardware windows; this module re-elects them from LIVE traffic instead:

- **Winner banks** — one validated ``winner_bank/v1`` file keyed by
  ``(device_kind, knob, geometry)`` with each entry stamped by the sweep
  revision it was measured under (the offline ``_variants_sig`` /
  ``_SWEEP_REV`` staleness discipline), so v5e / v6e / CPU each carry
  their OWN elections and a harness revision bump makes every
  pre-revision entry stale (falls back to the offline cache) rather than
  electable. Writes go through ``atomicio.atomic_write`` — a promotion
  racing an offline sweep sees old or new, never a torn file.

- **Shadow measurement** — :class:`LiveTuner` samples a fraction of
  served batches (``TMR_LIVE_TUNE_SAMPLE``), re-executes each sample
  through the incumbent AND one candidate formulation OFF the critical
  path (a dedicated daemon thread; the serve pipeline only enqueues),
  under a device-seconds budget (``TMR_LIVE_TUNE_BUDGET``). A
  candidate's result must pass the oracle check against the incumbent
  before its timing counts — a refusal disqualifies the arm and is a
  recorded decision, never a silent drop.

- **Promotion / demotion** — the offline decisive-win policy
  (``_decisive_pick``: >10% win, ``win_ratio`` 0.9) applied per sample:
  ``TMR_LIVE_TUNE_WINS`` CONSECUTIVE decisive wins promote the
  candidate (bank entry hot-swapped, affected ``Predictor._compiled``
  keys invalidated — no restart); any ``HealthWatch`` /
  ``FleetHealthWatch`` demote-kind anomaly (:data:`DEMOTE_ANOMALIES`)
  or oracle refusal rolls back to the incumbent with the cause
  recorded. Every decision lands in a replayable log
  (:func:`replay_decisions` re-derives the same elections from the
  recorded shadow measurements).

- **Fleet-wide** — workers count decisive wins/refusals into their
  engine metrics registry (``live_tune.win.<knob>=<arm>``); the beats
  fold them coordinator-side (``state()["fleet_metrics"]``), where
  ``ServeFleet.live_tune_pass`` aggregates across workers and pushes
  the election back over the lease protocol's beat replies so the
  fleet converges on one winner per device generation.

Everything is OFF by default: ``TMR_LIVE_TUNE=0`` (unset) keeps serving
bitwise-identical — the engine holds ``_tuner = None`` and pays one
``is None`` check per batch; scripts/live_tune_probe.py pins it.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from tmr_tpu.diagnostics import WINNER_BANK_SCHEMA, validate_winner_bank
from tmr_tpu.utils.cache import STATE_DIR

#: anomaly kinds that demote a live promotion (the HealthWatch /
#: FleetHealthWatch vocabulary subset that reads "the formulation made
#: things worse"): single-engine MFU/latency regressions plus their
#: fleet-wide counterparts. Closed — a new demote trigger is a
#: deliberate addition here, not an incidental anomaly rename.
DEMOTE_ANOMALIES = (
    "mfu_drop",
    "fleet_mfu_drop",
    "latency_regression",
    "worker_outlier_latency",
)

#: default winner-bank location, next to the offline autotune cache
BANK_PATH = os.path.join(STATE_DIR, "winner_bank.json")

#: ``Predictor._compiled`` program kinds each live-tunable knob can
#: change: None = every program embeds the formulation (matcher,
#: quant numerics), a tuple = only those kinds re-trace. The
#: promotion's invalidation scope — too narrow would serve a stale
#: formulation, too wide only costs recompiles.
KNOB_PROGRAM_KINDS: Dict[str, Optional[Tuple[str, ...]]] = {
    "TMR_XCORR_IMPL_SMALL": None,
    "TMR_QUANT": None,
    "TMR_QUANT_STORAGE": None,
    "TMR_DECODER_IMPL": (
        "single", "multi", "multi_batched", "heads",
        "gallery", "gallery_heads",
    ),
}


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def live_tune_enabled() -> bool:
    """The master switch (``TMR_LIVE_TUNE``): unset/0 = continuous
    autotune fully off — ``ServeEngine.attach_live_tuner`` refuses, no
    sampling, no bank writes, serving stays bitwise-identical."""
    return os.environ.get("TMR_LIVE_TUNE", "") not in ("", "0")


def default_sample() -> float:
    """Sampled fraction of served batches (``TMR_LIVE_TUNE_SAMPLE``).
    The default 0.002 keeps shadow work (incumbent + candidate per
    sample = 2x) well under 1% of steady-state device seconds."""
    return max(min(_env_float("TMR_LIVE_TUNE_SAMPLE", 0.002), 1.0), 0.0)


def default_budget_s() -> float:
    """Device-seconds token budget for shadow execution per tuner
    (``TMR_LIVE_TUNE_BUDGET``): once spent, sampling stops (recorded)
    until a promotion/demotion resets the ledger."""
    return max(_env_float("TMR_LIVE_TUNE_BUDGET", 2.0), 0.0)


def default_wins() -> int:
    """Consecutive decisive wins required to promote
    (``TMR_LIVE_TUNE_WINS``)."""
    return max(_env_int("TMR_LIVE_TUNE_WINS", 3), 1)


# ------------------------------------------------------------ winner bank
def bank_path() -> str:
    """Bank file location: ``TMR_LIVE_TUNE_BANK`` override, else
    ``<repo>/.tmr_cache/winner_bank.json``."""
    return os.environ.get("TMR_LIVE_TUNE_BANK") or BANK_PATH


def bank_key(device_kind: str, knob: str, geometry: str) -> str:
    """The per-(device generation, program knob, geometry) bank key —
    one definition so writer, reader, and tests can never drift."""
    return f"{device_kind}|{knob}|{geometry}"


def _sweep_rev() -> str:
    from tmr_tpu.utils.autotune import _SWEEP_REV

    return _SWEEP_REV


def _winner_ok(knob: str, value: str) -> bool:
    """A bank winner must be a value the formulation gate ladder knows:
    for knobs with an offline variant set, membership in that set (a
    FALLBACK_SUFFIX-annotated label is never electable — same contract
    as the offline ``_electable`` filter); for other knobs any
    non-empty plain string."""
    from tmr_tpu.utils import autotune as _at

    if not isinstance(value, str) or not value or \
            value.endswith(_at.FALLBACK_SUFFIX):
        return False
    sets = {
        "TMR_XCORR_IMPL_SMALL": set(_at.XCORR_VARIANTS) | {"auto"},
        "TMR_DECODER_IMPL": set(_at.DECODER_IMPL_VARIANTS) | {"auto"},
        "TMR_QUANT": set(_at.QUANT_VARIANTS) | {"auto"},
        "TMR_QUANT_STORAGE": set(_at.STORAGE_VARIANTS),
    }
    allowed = sets.get(knob)
    return True if allowed is None else value in allowed


def load_bank(path: Optional[str] = None,
              device_kind: Optional[str] = None) -> Dict[str, dict]:
    """Validated bank entries from disk: ``{bank key: entry}``.

    Best-effort all the way down (a foreign/hand-edited file degrades
    to "no bank", never a crash), with two hard isolation rules:

    - a ``device_kind`` filter returns ONLY that generation's entries —
      a v5e election can never leak into a v6e (or CPU) process;
    - an entry whose ``sweep_rev`` predates the current harness
      revision is dropped (stale — the consumer falls back to the
      offline cache), exactly the offline ``_variants_sig`` staleness
      discipline.
    """
    path = path or bank_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if validate_winner_bank(doc):
        return {}
    rev = _sweep_rev()
    out: Dict[str, dict] = {}
    for key, entry in doc["entries"].items():
        if entry.get("sweep_rev") != rev:
            continue  # stale harness revision: never electable
        if device_kind is not None and \
                entry.get("device_kind") != device_kind:
            continue
        if key != bank_key(entry.get("device_kind", ""),
                           entry.get("knob", ""),
                           str(entry.get("geometry", ""))):
            continue  # key/entry mismatch: a hand-edit, drop it
        if not _winner_ok(entry.get("knob", ""),
                          entry.get("winner", "")):
            continue
        out[key] = dict(entry)
    return out


def store_bank(entries: Dict[str, dict],
               path: Optional[str] = None) -> bool:
    """Atomically persist the full entry map as one ``winner_bank/v1``
    document. Best-effort like every autotune cache write (the elected
    winner is already live in-process; the bank is the cross-process
    memory)."""
    from tmr_tpu.utils.atomicio import atomic_write

    path = path or bank_path()
    doc = {
        "schema": WINNER_BANK_SCHEMA,
        "sweep_rev": _sweep_rev(),
        "ts": time.time(),
        "entries": entries,
    }

    def _write(f):
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")

    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        atomic_write(path, _write)
    except OSError:
        return False
    return True


def make_entry(device_kind: str, knob: str, geometry: str, winner: str,
               *, source: str, wins: int = 0,
               device_s_per_item: Optional[Dict[str, float]] = None
               ) -> dict:
    """One bank entry in the validated shape."""
    entry = {
        "device_kind": str(device_kind),
        "knob": str(knob),
        "geometry": str(geometry),
        "winner": str(winner),
        "sweep_rev": _sweep_rev(),
        "source": str(source),
        "wins": int(wins),
        "ts": time.time(),
    }
    if device_s_per_item:
        entry["device_s_per_item"] = {
            k: float(v) for k, v in device_s_per_item.items()
        }
    return entry


def seed_bank_from_cache(device_kind: str,
                         path: Optional[str] = None) -> Dict[str, dict]:
    """Seed bank entries for one device generation from the offline
    autotune cache: every non-stale formulation winner the offline
    sweep recorded for this generation becomes an ``offline``-source
    entry (geometry = the cache key's shape suffix). Entries already in
    the bank for the same key are NOT overwritten — a live election
    always outranks its own seed. Returns the merged entry map (also
    persisted when anything new landed)."""
    from tmr_tpu.utils import autotune as _at

    bank = load_bank(path)
    added = False
    prefix = f"{device_kind}|"
    for cache_key, knobs in _at._cache_load().items():
        if not cache_key.startswith(prefix):
            continue
        geometry = cache_key[len(prefix):]
        for knob in _at._VERSIONED_KNOBS:
            winner = knobs.get(knob)
            if winner is None or not _winner_ok(knob, winner):
                continue
            if knobs.get(f"_variants_{knob}") != _at._variants_sig(knob):
                continue  # stale offline winner: not seedable
            key = bank_key(device_kind, knob, geometry)
            if key in bank:
                continue
            bank[key] = make_entry(device_kind, knob, geometry, winner,
                                   source="offline")
            added = True
    if added:
        store_bank(bank, path)
    return bank


def device_generation() -> str:
    """The winner bank's device-generation key for THIS process:
    devtime's peak table identity (``TPU v5e`` / ``TPU v6e`` / ...)
    when resolvable, else the backend name — CPU processes bank under
    ``cpu``, never under a TPU generation."""
    try:
        from tmr_tpu.obs import devtime

        peak = devtime.platform_peak()
        kind = peak.get("device_kind")
        if kind:
            return str(kind)
    except Exception:
        pass
    try:
        import jax

        return str(jax.default_backend())
    except Exception:
        return "cpu"


# -------------------------------------------------------- compiled swap
def apply_winner(predictor: Any, knob: str, value: str) -> int:
    """Hot-swap one formulation winner into the running process: export
    the env knob (programs read it at trace time) and invalidate the
    affected ``Predictor._compiled`` entries so the next call
    re-traces under the new formulation — no restart. Returns the
    number of dropped programs (0 for predictors without the hook,
    e.g. the numpy fleet stub)."""
    os.environ[knob] = str(value)
    inv = getattr(predictor, "invalidate_compiled", None)
    if not callable(inv):
        return 0
    return int(inv(KNOB_PROGRAM_KINDS.get(knob)))


def default_oracle(base: Any, cand: Any) -> bool:
    """Result agreement check used when the caller supplies no oracle:
    detection dicts must match exactly (the serve exactness contract —
    a candidate formulation that changes results is REFUSED regardless
    of its timing; knobs with documented ULP exceptions supply their
    own tolerance oracle)."""
    import numpy as np

    if isinstance(base, dict) and isinstance(cand, dict):
        keys = [k for k in base if k != "degrade_steps"]
        if any(k not in cand for k in keys):
            return False
        return all(
            np.array_equal(np.asarray(base[k]), np.asarray(cand[k]))
            for k in keys
        )
    return bool(np.array_equal(np.asarray(base), np.asarray(cand)))


# ------------------------------------------------------------- the tuner
class LiveTuner:
    """Shadow-measuring election loop for ONE formulation knob.

    ``runner(arm, payload)`` executes the sampled payload through the
    formulation ``arm`` and returns ``(result, device_s)`` — the
    engine-side runner re-executes the batch through the candidate
    program (devtime-measured); probes inject deterministic stubs.
    ``payload`` is opaque to the tuner (the engine passes the batch's
    host inputs).

    The serve pipeline calls :meth:`offer` per completed batch — a
    sampling decision plus a bounded non-blocking enqueue; the shadow
    execution itself runs on this tuner's daemon thread, off the
    critical path, under the device-seconds budget.
    """

    def __init__(self, knob: str, arms: Sequence[str], incumbent: str,
                 *, runner: Callable[[str, Any], Tuple[Any, float]],
                 oracle: Optional[Callable[[Any, Any], bool]] = None,
                 device_kind: Optional[str] = None, geometry: str = "",
                 sample: Optional[float] = None,
                 budget_s: Optional[float] = None,
                 wins_needed: Optional[int] = None,
                 win_ratio: float = 0.9,
                 bank_file: Optional[str] = None,
                 apply_fn: Optional[Callable[[str, str], Any]] = None,
                 metrics: Optional[Any] = None,
                 queue_depth: int = 4):
        self.knob = str(knob)
        self.incumbent = str(incumbent)
        self.arms = [str(a) for a in arms if str(a) != self.incumbent]
        self._runner = runner
        self._oracle = oracle or default_oracle
        self.device_kind = device_kind or device_generation()
        self.geometry = str(geometry)
        self.sample = default_sample() if sample is None \
            else max(min(float(sample), 1.0), 0.0)
        self.budget_s = default_budget_s() if budget_s is None \
            else float(budget_s)
        self.wins_needed = default_wins() if wins_needed is None \
            else max(int(wins_needed), 1)
        self.win_ratio = float(win_ratio)
        self.bank_file = bank_file
        self._apply_fn = apply_fn
        self._metrics = metrics
        self._stride = int(round(1.0 / self.sample)) if self.sample > 0 \
            else 0
        self._lock = threading.Lock()
        self._q: "queue.Queue" = queue.Queue(
            maxsize=max(int(queue_depth), 1)
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # election state (all under self._lock)
        self._arm_i = 0
        self._wins: Dict[str, int] = {}
        self._disqualified: set = set()
        self._prev_incumbent: Optional[str] = None
        self._promoted: Optional[str] = None
        self.decisions: List[dict] = []
        self._counters: Dict[str, float] = {
            "offers": 0, "sampled": 0, "shadow_runs": 0, "dropped": 0,
            "refusals": 0, "promotions": 0, "demotions": 0,
            "budget_stops": 0, "items": 0,
            "shadow_device_s": 0.0, "incumbent_device_s": 0.0,
            "incumbent_items": 0,
        }

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "LiveTuner":
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._shadow_loop,
                    name=f"live-tune-{self.knob}", daemon=True,
                )
                self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            self._q.put(None)
            t.join(timeout=timeout)

    def drain(self, timeout: float = 5.0) -> None:
        """Block until every enqueued sample has been shadow-measured
        (probe/test synchronization — production never calls it)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._q.empty() and not self._busy():
                return
            time.sleep(0.005)

    def _busy(self) -> bool:
        with self._lock:
            return bool(self._counters.get("_inflight"))

    # --------------------------------------------------------------- offers
    def offer(self, payload: Any, base_result: Any,
              items: int = 1) -> bool:
        """One completed serve batch: count it, decide sampling
        (deterministic stride — every ``1/sample``-th offer), and
        enqueue for shadow measurement when within budget. Never
        blocks; a full queue drops the sample (counted)."""
        with self._lock:
            self._counters["offers"] += 1
            self._counters["items"] += max(int(items), 1)
            if self._stride <= 0 or self._stop.is_set():
                return False
            if int(self._counters["offers"] - 1) % self._stride:
                return False
            if self._counters["shadow_device_s"] >= self.budget_s:
                self._counters["budget_stops"] += 1
                return False
            self._counters["sampled"] += 1
        try:
            self._q.put_nowait((payload, base_result,
                                max(int(items), 1)))
        except queue.Full:
            with self._lock:
                self._counters["dropped"] += 1
            return False
        return True

    # --------------------------------------------------------------- shadow
    def _shadow_loop(self) -> None:
        while not self._stop.is_set():
            item = self._q.get()
            if item is None:
                return
            with self._lock:
                self._counters["_inflight"] = 1
            try:
                self._shadow_one(*item)
            except Exception:
                pass  # a shadow failure must never hurt serving
            finally:
                with self._lock:
                    self._counters.pop("_inflight", None)

    def _next_arm(self) -> Optional[str]:
        with self._lock:
            live = [a for a in self.arms
                    if a not in self._disqualified
                    and a != self.incumbent]
            if not live:
                return None
            arm = live[self._arm_i % len(live)]
            self._arm_i += 1
            return arm

    def _shadow_one(self, payload: Any, base_result: Any,
                    items: int) -> None:
        arm = self._next_arm()
        if arm is None:
            return
        # incumbent first: symmetric measurement (same runner, same
        # payload, same synchronous timing) — comparing a candidate's
        # blocking time against the pipeline's async dispatch time
        # would systematically flatter the pipeline
        base_out, base_s = self._runner(self.incumbent, payload)
        cand_out, cand_s = self._runner(arm, payload)
        with self._lock:
            self._counters["shadow_runs"] += 1
            self._counters["shadow_device_s"] += float(base_s) + \
                float(cand_s)
            self._counters["incumbent_device_s"] += float(base_s)
            self._counters["incumbent_items"] += items
        ok = False
        try:
            # the gate/oracle ladder: the candidate's RESULT must match
            # the incumbent's before its TIMING counts
            ok = bool(self._oracle(base_out, cand_out)) and (
                base_result is None or
                bool(self._oracle(base_result, base_out))
            )
        except Exception:
            ok = False
        per_item = max(items, 1)
        if not ok:
            self._refuse(arm, base_s / per_item, cand_s / per_item,
                         items)
            return
        win = cand_s < self.win_ratio * base_s
        with self._lock:
            if win:
                self._wins[arm] = self._wins.get(arm, 0) + 1
            else:
                # decisive wins are CONSECUTIVE: one non-win resets
                # the arm (the replayable policy — see
                # replay_decisions)
                self._wins[arm] = 0
            wins = self._wins[arm]
            self._record("shadow", arm, win=win, wins=wins,
                         base_s_per_item=base_s / per_item,
                         cand_s_per_item=cand_s / per_item,
                         items=items)
            decisive = win and wins >= self.wins_needed \
                and self._promoted is None
        if decisive:
            self.promote(arm)

    # ------------------------------------------------------------ decisions
    def _record(self, event: str, arm: str, **fields) -> None:
        """Append one decision (caller holds ``self._lock``)."""
        self.decisions.append({
            "event": event, "knob": self.knob, "arm": arm,
            "ts": time.time(), **fields,
        })

    def _count_metric(self, name: str) -> None:
        if self._metrics is not None:
            try:
                self._metrics.counter(name).inc()
            except Exception:
                pass

    def _refuse(self, arm: str, base_s: float, cand_s: float,
                items: int) -> None:
        with self._lock:
            self._counters["refusals"] += 1
            self._disqualified.add(arm)
            self._wins[arm] = 0
            self._record("refusal", arm, base_s_per_item=base_s,
                         cand_s_per_item=cand_s, items=items)
            demote = self._promoted == arm
        self._count_metric(f"live_tune.refusal.{self.knob}={arm}")
        if demote:
            self.demote("oracle_refusal")

    def promote(self, arm: str) -> None:
        """Hot-swap ``arm`` in as the serving formulation: bank entry
        written (atomic), affected compiled programs invalidated via
        ``apply_fn``, decision recorded."""
        with self._lock:
            if self._promoted is not None or arm == self.incumbent:
                return
            self._prev_incumbent = self.incumbent
            self._promoted = arm
            self.incumbent = arm
            self._counters["promotions"] += 1
            wins = self._wins.get(arm, 0)
            self._record("promote", arm, wins=wins,
                         previous=self._prev_incumbent)
        self._count_metric(f"live_tune.win.{self.knob}={arm}")
        self._write_bank(arm, source="live", wins=wins)
        if self._apply_fn is not None:
            try:
                self._apply_fn(self.knob, arm)
            except Exception:
                pass

    def demote(self, cause: str, evidence: Optional[dict] = None) -> None:
        """Roll back the live promotion to its incumbent, cause
        recorded. A no-op when nothing is promoted (anomalies unrelated
        to a live election must not thrash the bank)."""
        with self._lock:
            if self._promoted is None:
                return
            arm, self._promoted = self._promoted, None
            prev = self._prev_incumbent or arm
            self._prev_incumbent = None
            self.incumbent = prev
            self._disqualified.add(arm)
            self._wins[arm] = 0
            self._counters["demotions"] += 1
            rec_evidence = dict(evidence or {})
            self._record("demote", arm, cause=str(cause),
                         restored=prev, evidence=rec_evidence)
        self._count_metric(f"live_tune.demotion.{self.knob}={arm}")
        self._write_bank(prev, source="live", wins=0)
        if self._apply_fn is not None:
            try:
                self._apply_fn(self.knob, prev)
            except Exception:
                pass

    def observe_anomalies(self, records: Sequence[dict]) -> None:
        """HealthWatch/FleetHealthWatch listener hook: any demote-kind
        anomaly rolls a live promotion back (first one wins; the rest
        of the pass is moot once demoted)."""
        for rec in records or ():
            if not isinstance(rec, dict):
                continue
            kind = rec.get("anomaly")
            if kind in DEMOTE_ANOMALIES:
                self.demote(kind, evidence=rec.get("evidence") or {})
                return

    def _write_bank(self, winner: str, *, source: str,
                    wins: int) -> None:
        try:
            bank = load_bank(self.bank_file)
            key = bank_key(self.device_kind, self.knob, self.geometry)
            with self._lock:
                per_item = {}
                n = self._counters["incumbent_items"]
                if n:
                    per_item["incumbent"] = (
                        self._counters["incumbent_device_s"] / n
                    )
            bank[key] = make_entry(
                self.device_kind, self.knob, self.geometry, winner,
                source=source, wins=wins,
                device_s_per_item=per_item or None,
            )
            store_bank(bank, self.bank_file)
        except Exception:
            pass  # bank persistence is best-effort, elections are live

    # -------------------------------------------------------------- report
    def counters(self) -> Dict[str, float]:
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if not k.startswith("_")}

    def shadow_fraction(self) -> Optional[float]:
        """Shadow device seconds as a fraction of the ESTIMATED
        steady-state serve device seconds (mean incumbent per-item cost
        x every item served) — the <1% acceptance pin's measurement."""
        with self._lock:
            n = self._counters["incumbent_items"]
            items = self._counters["items"]
            shadow = self._counters["shadow_device_s"]
            if not n or not items:
                return None
            served_est = (self._counters["incumbent_device_s"] / n) \
                * items
            return shadow / served_est if served_est > 0 else None

    def report(self) -> dict:
        """The tuner's slice of a ``live_tune_report/v1`` document
        (the probe wraps it with its checks section)."""
        with self._lock:
            return {
                "knob": self.knob,
                "device_kind": self.device_kind,
                "geometry": self.geometry,
                "incumbent": self.incumbent,
                "promoted": self._promoted,
                "arms": list(self.arms),
                "disqualified": sorted(self._disqualified),
                "sample": self.sample,
                "budget_s": self.budget_s,
                "wins_needed": self.wins_needed,
                "win_ratio": self.win_ratio,
                "counters": {k: v for k, v in self._counters.items()
                             if not k.startswith("_")},
                "decisions": [dict(d) for d in self.decisions],
            }


def replay_decisions(decisions: Sequence[dict], *, wins_needed: int,
                     win_ratio: float = 0.9) -> List[Tuple[str, str]]:
    """Pure re-election over a recorded decision log: feed the shadow
    measurements (and the externally-triggered refusal/demote inputs)
    through the same consecutive-decisive-win policy and return the
    ``(event, arm)`` sequence it reaches. A log whose recorded
    promote/demote events match this replay is internally consistent —
    the election was a function of its measurements, not of a race."""
    wins: Dict[str, int] = {}
    disqualified: set = set()
    promoted: Optional[str] = None
    out: List[Tuple[str, str]] = []
    for rec in decisions or ():
        event, arm = rec.get("event"), rec.get("arm")
        if event == "refusal":
            disqualified.add(arm)
            wins[arm] = 0
            if promoted == arm:
                out.append(("demote", arm))
                promoted = None
            continue
        if event == "demote":
            # anomaly-triggered: an input to the policy, echoed —
            # but only legal against the live promotion
            if promoted == arm:
                out.append(("demote", arm))
                promoted = None
                disqualified.add(arm)
            continue
        if event != "shadow" or arm in disqualified:
            continue
        base = rec.get("base_s_per_item")
        cand = rec.get("cand_s_per_item")
        win = (cand < win_ratio * base) \
            if isinstance(base, (int, float)) and \
            isinstance(cand, (int, float)) else bool(rec.get("win"))
        wins[arm] = wins.get(arm, 0) + 1 if win else 0
        if win and wins[arm] >= wins_needed and promoted is None:
            out.append(("promote", arm))
            promoted = arm
    return out


def recorded_elections(decisions: Sequence[dict]
                       ) -> List[Tuple[str, str]]:
    """The promote/demote events a decision log actually recorded, in
    order — what :func:`replay_decisions` must reproduce."""
    return [(rec["event"], rec.get("arm"))
            for rec in decisions or ()
            if rec.get("event") in ("promote", "demote")]

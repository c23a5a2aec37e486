"""Stall watchdog: flag a run that stops completing steps.

A silent hang — a wedged collective, a lost PJRT device, a prefetch thread
blocked on a dying filesystem — looks exactly like a very slow step from the
driver's point of view. :class:`StallWatchdog` keeps a rolling estimate of the
step time and raises a WARNING (plus callback hooks) when no step completes
within ``k x`` that estimate. It never kills the run: the existing failure
machinery (``Optimizer.set_retry_times`` checkpoint-resume) owns recovery; the
watchdog's job is to make the stall visible the moment it starts instead of
after the batch-queue timeout, and a callback may choose to escalate.

Designed for tests: the clock is injectable and :meth:`check` is a pure
function of (clock, recorded steps), so a fake clock exercises every stall
transition without a single ``sleep``. The monitor thread is just
``while not stop: wait(poll); check()``.
"""

from __future__ import annotations

import collections
import logging
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional

log = logging.getLogger("bigdl_tpu.obs")

__all__ = ["MonitorBase", "StallWatchdog"]


class MonitorBase:
    """Shared poll-loop chassis for watchdog-style monitors (this module's
    :class:`StallWatchdog`, the serving tier's
    :class:`~bigdl_tpu.serving.resilience.ServingSupervisor`): a daemon
    thread calls ``check()`` every ``poll_interval_s`` until stopped. The
    contract that keeps every subclass testable is that ``check()`` is a
    PURE function of (injected clock, recorded state) — tests drive it
    directly with a fake clock and never need the thread."""

    def __init__(self, poll_interval_s: float):
        self.poll_interval_s = float(poll_interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def check(self):
        raise NotImplementedError

    def start(self, name: Optional[str] = None) -> "MonitorBase":
        """Start the daemon poll thread (idempotent while alive). Subclasses
        with per-run state to reset (``StallWatchdog``) override and call
        :meth:`_spawn` themselves; stateless monitors (``FleetMonitor``,
        ``ServingSupervisor``) inherit this directly."""
        self._spawn(name or f"bigdl-{type(self).__name__.lower()}")
        return self

    def _spawn(self, name: str) -> None:
        """(Re)start the daemon poll thread; idempotent while it is alive."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._poll, name=name, daemon=True
            )
            self._thread.start()

    def _poll(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            self.check()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=2 * self.poll_interval_s + 1.0)
        self._thread = None


class StallWatchdog(MonitorBase):
    """Monitor that flags missing step completions.

    Args:
        k: stall threshold as a multiple of the rolling step-time estimate
           (median of the last ``window`` steps).
        min_timeout_s: floor on the stall deadline — sub-millisecond steps must
           not make a 10ms GC pause page someone.
        window: rolling window length for the step-time estimate.
        poll_interval_s: how often the monitor thread re-checks.
        on_stall: optional callback ``fn(info: dict)`` invoked once per stall
           (re-armed when the next step completes). More via
           :meth:`add_callback`.
        first_step_timeout_s: optional deadline for the FIRST step after
           :meth:`start` (covers a hung compile); ``None`` disarms the
           watchdog until the first step completes, since a cold XLA compile
           can legitimately take minutes.
        clock: injectable monotonic clock (tests pass a fake).
    """

    def __init__(
        self,
        k: float = 10.0,
        min_timeout_s: float = 5.0,
        window: int = 32,
        poll_interval_s: float = 1.0,
        on_stall: Optional[Callable[[Dict], None]] = None,
        first_step_timeout_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        super().__init__(poll_interval_s)
        self.k = float(k)
        self.min_timeout_s = float(min_timeout_s)
        self.first_step_timeout_s = first_step_timeout_s
        self._clock = clock
        self._durations: collections.deque = collections.deque(maxlen=window)
        self._callbacks: List[Callable[[Dict], None]] = []  # guarded-by: _lock
        if on_stall is not None:
            self._callbacks.append(on_stall)
        # RLock: check() reads estimate_s() while holding the lock
        self._lock = threading.RLock()
        self._last_step_at: Optional[float] = None
        self._started_at: Optional[float] = None
        self._steps = 0
        self._stalled = False
        self.stall_count = 0

    # ------------------------------------------------------------- recording
    def notify_step(self, duration_s: float) -> None:
        """One step completed; re-arms a flagged stall."""
        with self._lock:
            self._durations.append(float(duration_s))
            self._last_step_at = self._clock()
            self._steps += 1
            self._stalled = False

    def add_callback(self, fn: Callable[[Dict], None]) -> "StallWatchdog":
        with self._lock:
            self._callbacks.append(fn)
        return self

    def remove_callback(self, fn: Callable[[Dict], None]) -> "StallWatchdog":
        """Detach a callback registered with ``add_callback`` (no-op if
        absent) — consumers that re-point to a new watchdog must deregister
        from the old one or it pins them alive for its whole lifetime."""
        with self._lock:
            try:
                self._callbacks.remove(fn)
            except ValueError:
                pass
        return self

    # ------------------------------------------------------------- estimates
    def estimate_s(self) -> Optional[float]:
        """Rolling step-time estimate (median — robust to the odd
        checkpoint/validation-lengthened step)."""
        with self._lock:
            if not self._durations:
                return None
            return statistics.median(self._durations)

    def deadline_s(self) -> Optional[float]:
        """Current stall deadline, or None while disarmed."""
        est = self.estimate_s()
        if est is None:
            return self.first_step_timeout_s  # may be None = disarmed
        return max(self.k * est, self.min_timeout_s)

    # --------------------------------------------------------------- checking
    def check(self) -> Optional[Dict]:
        """Pure stall test against the injected clock; returns the stall-info
        dict the first time a stall is detected, else None. Called by the
        monitor thread, and directly by tests (no thread, no sleep)."""
        with self._lock:
            ref = (
                self._last_step_at
                if self._last_step_at is not None
                else self._started_at
            )
            already = self._stalled
        if ref is None or already:
            return None
        deadline = self.deadline_s()
        if deadline is None:
            return None
        waited = self._clock() - ref
        if waited <= deadline:
            return None
        with self._lock:
            if self._stalled:  # raced with another checker
                return None
            self._stalled = True
            self.stall_count += 1
            info = {
                "waited_s": round(waited, 6),
                "deadline_s": round(deadline, 6),
                "step_estimate_s": self.estimate_s(),
                "steps_completed": self._steps,
            }
        log.warning(
            "stall watchdog: no step completed for %.1fs "
            "(deadline %.1fs = max(%g x %.4gs median step, %.1fs floor)); "
            "the run may be wedged — see the telemetry stream / retry "
            "machinery",
            info["waited_s"], info["deadline_s"], self.k,
            info["step_estimate_s"] or float("nan"), self.min_timeout_s,
        )
        with self._lock:
            callbacks = list(self._callbacks)
        for cb in callbacks:  # fire OUTSIDE the lock: hooks run arbitrary code
            try:
                cb(info)
            except Exception:  # a broken hook must not take down monitoring
                log.exception("stall watchdog callback failed")
        return info

    # ---------------------------------------------------------------- thread
    def start(self) -> "StallWatchdog":
        """Start (or restart) the daemon monitor thread for a NEW run.

        Resets per-run state: a reused watchdog (one Telemetry across two
        fits, or fit then predict) must not read the previous run's last
        step against the idle gap between runs — that would flag a spurious
        stall the moment run 2 begins. Step-time history is also cleared,
        returning to disarmed-until-first-step so run 2's cold compile is
        not judged by run 1's steady-state median."""
        with self._lock:
            self._started_at = self._clock()
            self._last_step_at = None
            self._durations.clear()
            self._stalled = False
        self._spawn("bigdl-stall-watchdog")
        return self

"""Failure detection: hang watchdog + emergency checkpointing.

SURVEY.md §5 records the reference's posture: "a dead rank hangs the
gather/all_reduce forever; no timeout is configured"
(``src/Part 2a/main.py:152`` sets none) — failure detection is entirely
absent.  This module is the beyond-reference replacement, shaped for how
TPU/SPMD programs actually fail:

  * A wedged collective (peer host died, ICI link down) never returns — so
    detection must come from OUTSIDE the blocked call.  :class:`Watchdog`
    arms a monitor thread around each step; if the step doesn't complete
    within the deadline it dumps the attached flight recorder
    (``tpudp.obs`` — the span timeline naming the wedged region), runs
    the registered callbacks (e.g. log + dump state) and can terminate
    the process so a cluster scheduler restarts it (with
    ``--checkpoint-dir`` resume, that is elastic recovery in the
    "restart from last epoch" sense).
  * Per-step health checks that ARE observable in SPMD: a non-finite loss
    (diverged or corrupted replica) fails fast via :func:`check_finite`.

The watchdog is cooperative and zero-overhead on the hot path: arming is
two monotonic-clock reads and an Event set/clear; no thread is spawned per
step.  Every armed region carries a NAME (``arm("train_epoch")``,
``wd.step(name="decode")``), so a timeout explains itself: the
:class:`StepHangError` message and the flight-record dump both say which
region was armed, when, and what last completed — a watchdog that kills
without explaining is exactly the observability hole PR 11 closed.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable


class StepHangError(RuntimeError):
    """Raised in the main thread when a hang was detected and the watchdog
    was configured not to kill the process.  ``hang`` carries the
    detection context (region name, arm timestamp, last-completed span)
    when the watchdog recorded one."""

    def __init__(self, message: str, hang: dict | None = None):
        super().__init__(message)
        self.hang = hang or {}


class Watchdog:
    """Detects training steps that exceed a wall-clock deadline.

    Two usage styles:

    *Heartbeat* (what the Trainer uses — covers EVERY blocking host call in
    the monitored region, including multi-step fused log windows, the
    first-step XLA compile, ragged-window fetches, and eval)::

        wd = Watchdog(timeout_s=600, on_hang=[dump_fn], kill=True)
        wd.start(); wd.arm("train_epoch")
        for batch in loader:
            state, loss = train_step(state, *batch)
            wd.beat()             # progress! push the deadline out
        wd.disarm(); wd.stop()

    The deadline is ``timeout_s`` after the LAST beat, so the timeout must
    exceed the slowest legitimate gap between beats (for the fused Trainer:
    one full ``log_every``-step window plus the first-step compile).

    *Scoped* — arm a deadline around one specific blocking region::

        with wd.step(name="window_barrier"):
            jax.block_until_ready(state.params)

    A scope may carry its own deadline (``wd.step(timeout_s=5.0)``) so one
    watchdog can guard regions with very different legitimate durations —
    the serve engine wraps each blocking device call this way
    (``tpudp.serve.Engine(watchdog=..., step_timeout_s=...)``) with a much
    tighter budget than a training step's, naming each region after the
    device call it guards (``decode``, ``prefill``, ``fused_decode``...).

    ``kill=True`` (default) hard-exits the process on a hang — the correct
    behavior for a wedged collective, which no Python exception can unwind;
    the launcher/scheduler restarts the job and ``--checkpoint-dir``
    resumes it.  ``kill=False`` records the hang and raises
    :class:`StepHangError` at the next ``beat()``/``step()`` boundary
    (useful in tests), with the armed region and arm time in the message.

    ``flight`` (a :class:`tpudp.obs.FlightRecorder`, usually attached by
    the engine/trainer that owns the watchdog) is dumped by the monitor
    thread the moment a hang is detected — BEFORE the callbacks and the
    kill — so even a hard-exit leaves a black box whose span timeline
    names the wedged region.  ``last_hang`` keeps the same context for
    the in-process (kill=False) paths.
    """

    def __init__(
        self,
        timeout_s: float = 300.0,
        *,
        on_hang: list[Callable[[], None]] | None = None,
        kill: bool = True,
        poll_s: float | None = None,
        flight=None,
    ):
        self.timeout_s = timeout_s
        self.on_hang = list(on_hang or [])
        self.kill = kill
        self.poll_s = poll_s if poll_s is not None else min(timeout_s / 4, 1.0)
        self.flight = flight  # tpudp.obs.FlightRecorder or None
        self.last_hang: dict | None = None
        self._armed = False
        self._deadline: float | None = None
        self._region: tuple[str, float] | None = None  # (name, armed_at)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._hang_seen = threading.Event()
        self._thread: threading.Thread | None = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "Watchdog":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._monitor, daemon=True, name="tpudp-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- heartbeat style ------------------------------------------------
    def arm(self, name: str = "heartbeat") -> None:
        """Begin continuous monitoring: a hang fires if no :meth:`beat`
        arrives within ``timeout_s``.  ``name`` labels the armed region
        for the hang report.  Re-arming after a handled hang
        (kill=False) clears the recorded hang so the watchdog is
        reusable."""
        self._hang_seen.clear()
        with self._lock:
            self._armed = True
            self._region = (name, time.monotonic())
            self._deadline = time.monotonic() + self.timeout_s

    def beat(self) -> None:
        """Record progress; pushes the deadline ``timeout_s`` into the
        future.  Raises :class:`StepHangError` (kill=False mode) if a hang
        was detected since the last beat.  A no-op unless :meth:`arm` is
        active, so components that beat unconditionally (Trainer epoch/eval
        loops) never start monitoring by accident."""
        if not self._armed:
            return
        if self._hang_seen.is_set() and not self.kill:
            raise StepHangError(self._hang_message(), self.last_hang)
        with self._lock:
            self._deadline = time.monotonic() + self.timeout_s

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
            self._deadline = None
            self._region = None

    def acknowledge(self) -> bool:
        """kill=False mode: clear a recorded hang after the caller has
        CONTAINED it (retired/requeued the affected work), so the next
        scoped :meth:`step` proceeds instead of re-raising a hang that was
        already handled.  Returns whether a hang had been recorded.  The
        serve engine calls this from its step-failure containment;
        kill=True watchdogs never reach here (the process is gone)."""
        seen = self._hang_seen.is_set()
        self._hang_seen.clear()
        return seen

    # -- hang context ----------------------------------------------------
    def _hang_message(self) -> str:
        """One line that explains the kill: armed region, arm timestamp,
        and the last span the attached recorder saw complete."""
        hang = self.last_hang or {}
        region = hang.get("region", "unarmed")
        msg = (f"no progress within {hang.get('timeout_s', self.timeout_s)}s"
               f" in armed region '{region}'")
        armed_at = hang.get("armed_for_s")
        if armed_at is not None:
            msg += f" (armed {armed_at:.3f}s before detection)"
        last = hang.get("last_span")
        if last:
            msg += (f"; last completed span: {last.get('name')!r}"
                    f" at +{last.get('t0', 0):.3f}s")
        return msg

    def _capture_hang(self) -> dict:
        with self._lock:
            region = self._region
        name, armed_at = region if region is not None else ("unarmed", None)
        now = time.monotonic()
        hang = {"region": name, "timeout_s": self.timeout_s,
                "detected_at_monotonic": now,
                "armed_at_monotonic": armed_at,
                "armed_for_s": (now - armed_at
                                if armed_at is not None else None),
                "last_span": None}
        if self.flight is not None:
            try:
                hang["last_span"] = self.flight.recorder.last_span()
            except Exception:
                pass
        return hang

    # -- hot path ------------------------------------------------------
    class _Step:
        def __init__(self, wd: "Watchdog", timeout_s: float | None = None,
                     name: str = "step"):
            self.wd = wd
            self.timeout_s = wd.timeout_s if timeout_s is None else timeout_s
            self.name = name
            self._saved: tuple = (None, None)

        def __enter__(self):
            wd = self.wd
            if wd._hang_seen.is_set() and not wd.kill:
                raise StepHangError(
                    "a previous step exceeded its deadline — "
                    + wd._hang_message(), wd.last_hang)
            with wd._lock:
                self._saved = (wd._deadline, wd._region)
                wd._deadline = time.monotonic() + self.timeout_s
                wd._region = (self.name, time.monotonic())
            return self

        def __exit__(self, *exc):
            wd = self.wd
            with wd._lock:
                # restore the enclosing (heartbeat) deadline/region, so
                # a scoped guard inside an armed epoch hands monitoring
                # back instead of silencing it
                deadline, region = self._saved
                if wd._armed and deadline is not None:
                    wd._deadline = time.monotonic() + wd.timeout_s
                    wd._region = region
                else:
                    wd._deadline = None
                    wd._region = None
            return False

    def step(self, timeout_s: float | None = None,
             name: str = "step") -> "_Step":
        """Scoped deadline; ``timeout_s`` overrides the default for this
        one region (a serving decode step's budget is not a training
        step's); ``name`` labels the region in hang reports."""
        if timeout_s is not None and timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        return Watchdog._Step(self, timeout_s, name)

    # -- monitor -------------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_s):
            with self._lock:
                deadline = self._deadline
            if deadline is not None and time.monotonic() > deadline:
                self.last_hang = self._capture_hang()
                self._hang_seen.set()
                if self.flight is not None:
                    # Black box FIRST: the callbacks may be the kill path
                    # (emergency state dump can itself hang on a wedged
                    # device), and kill=True never returns — the span
                    # timeline must already be on disk.
                    try:
                        self.flight.dump(
                            "watchdog_timeout_"
                            + str(self.last_hang.get("region")),
                            extra=self.last_hang)
                    except Exception:
                        pass
                for cb in self.on_hang:
                    try:
                        cb()
                    except Exception:
                        pass
                if self.kill:
                    # A wedged XLA collective cannot be interrupted from
                    # Python; exit so the scheduler restarts + resumes.
                    os._exit(42)
                with self._lock:  # avoid re-firing until re-armed
                    self._deadline = None


def check_finite(loss_value: float, step: int | None = None, *,
                 what: str = "training loss",
                 context: str | None = None) -> float:
    """Fail-fast divergence/corruption check (cheap; call at log windows
    where the host already synchronized).  ``what``/``context`` label the
    failure site — eval losses run through here too (a NaN eval must fail
    loudly with epoch + iteration context, not report garbage accuracy)."""
    import math

    if not math.isfinite(loss_value):
        where = f" at step {step}" if step is not None else ""
        if context:
            where += f" ({context})"
        raise FloatingPointError(
            f"non-finite {what}{where}: {loss_value!r} — diverged "
            "or corrupted replica")
    return loss_value

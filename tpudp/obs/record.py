"""The span/event recorder at the bottom of ``tpudp.obs``.

Telemetry in this repo has to survive its own static analysis: the PR 8
linter forbids host syncs on the scheduler hot paths, and the same
discipline applies to the instrumentation itself — a recorder that
allocates, locks, or syncs per token would be the regression it exists
to observe.  So the core is a **preallocated monotonic-clock ring**:

  * :meth:`Recorder.begin` / :meth:`Recorder.end` — the allocation-free
    hot-path API.  ``begin`` writes (name, t0) into the next
    preallocated ring record and returns an integer token; ``end``
    stamps t1 into that record iff the ring has not lapped it.  Two
    ``time.monotonic()`` reads and a few attribute stores per span; no
    container growth, no device touch.  :meth:`Recorder.piece` splits
    an open span's time by what the program was doing meanwhile (the
    serve engine's ``exposed`` span by its phases) at one more clock
    read a piece.  The ``obs-in-hot-path`` lint rule pins exactly this
    API as the only one allowed inside the designated hot paths.
  * :meth:`Recorder.event` / :meth:`Recorder.span` — the convenient
    (allocating) API for everything OFF the hot path: request
    admission/retirement, recovery decisions, checkpoint writes.
    Events carry a ``**fields`` dict; ``span`` is a context manager.
  * :meth:`Recorder.count` — host-side named counters (a plain
    ``Counter``).

The ring holds the last ``capacity`` records per recorder — old
telemetry is dropped, never compacted; that bounded-loss contract is
what makes the recorder safe to leave on in production and is exactly
what the flight recorder (``tpudp/obs/flight.py``) wants: the last N
spans before a fault ARE the black box.  What must NOT be lost when the
ring laps are the totals: ``end`` also adds every closed span to a
cumulative per-name ``(count, total_s)`` (and a pieced span's parts) that
:meth:`Recorder.summary` returns — monotone counters a window delta or a Prometheus ``rate()``
can be taken of.

Timestamps are ``time.monotonic()`` (immune to wall-clock steps); each
recorder stamps a ``(monotonic, wall)`` anchor pair at construction so
exports can place the timeline in wall time.  While a JAX profiler
session is capturing, every ``begin``/``end`` span is ALSO a
``jax.profiler.TraceAnnotation`` named ``tpudp.<recorder>.<span>``, which
puts the program's spans on the profiler's clock, on the host plane
beside the device's operations.  The recorder asks the profiler itself
(``TraceAnnotation.is_enabled()``, tens of nanoseconds) and only once
jax is already imported by someone else: this module never imports it.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import time

#: Disabled-recorder token: ``end()`` treats it as a no-op.
NO_SPAN = -1

_RECORDER_IDS = itertools.count()


class _Rec:
    """One preallocated ring slot, reused in place (never reallocated —
    the hot path only stores into existing attributes)."""

    __slots__ = ("seq", "kind", "name", "t0", "tp", "t1", "fields", "ann")

    def __init__(self):
        self.seq = -1       # ring generation; -1 = never written
        self.kind = ""      # "span" | "event"
        self.name = ""
        self.t0 = 0.0
        self.tp = 0.0       # start of the span's piece not yet charged
        self.t1 = -1.0      # -1.0 = span still open
        self.fields = None  # dict for events / tagged spans, else None
        self.ann = None     # the open TraceAnnotation under a profiler


class Recorder:
    """Bounded span/event/counter recorder — one per engine/trainer.

    ``enabled=False`` turns every method into an O(1) no-op (the
    overhead-guard test pins the enabled path's cost too).  ``capacity``
    bounds the ring; the newest ``capacity`` records win.
    """

    __slots__ = ("name", "enabled", "capacity", "counters",
                 "anchor_monotonic", "anchor_wall",
                 "_ring", "_seq", "_last_done", "_id", "_totals",
                 "_parts", "_annotation", "_trace_prefix")

    def __init__(self, name: str = "", capacity: int = 4096,
                 enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self.enabled = enabled
        self.capacity = capacity
        self.counters: collections.Counter = collections.Counter()
        self.anchor_monotonic = time.monotonic()
        self.anchor_wall = time.time()
        self._ring = [_Rec() for _ in range(capacity)]
        self._seq = 0
        self._last_done = NO_SPAN
        self._id = next(_RECORDER_IDS)
        self._totals: dict[str, list] = {}  # name -> [count, total_s]
        self._parts: dict[str, dict] = {}   # name -> {part: seconds}
        self._annotation = None  # jax.profiler.TraceAnnotation, once seen
        self._trace_prefix = f"tpudp.{name}." if name else "tpudp."

    # -- hot-path API (allocation-free; sanctioned by obs-in-hot-path) --

    def begin(self, name: str) -> int:
        """Open a span; returns the token :meth:`end` closes.  Safe on
        the designated scheduler/step hot paths: two attribute stores
        and one clock read, no allocation beyond the returned int."""
        if not self.enabled:
            return NO_SPAN
        seq = self._seq
        rec = self._ring[seq % self.capacity]
        rec.seq = seq
        rec.kind = "span"
        rec.name = name
        rec.fields = None
        rec.t1 = -1.0
        ann = self._annotation or self._find_annotation()
        if ann is not None and ann.is_enabled():
            # a profiler session is capturing: the span also goes on the
            # trace's clock (allocates; only while a trace is taken)
            rec.ann = ann(self._trace_prefix + name)
            rec.ann.__enter__()
        else:
            rec.ann = None
        rec.t0 = rec.tp = time.monotonic()
        self._seq = seq + 1
        return seq

    def _find_annotation(self):
        """``jax.profiler.TraceAnnotation`` if jax is ALREADY imported
        (a dict lookup until then — a process that never imports jax
        never pays for it, and this module never imports it)."""
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        self._annotation = getattr(profiler, "TraceAnnotation", None)
        return self._annotation

    def end(self, token: int, part: str | None = None) -> None:
        """Close the span ``begin`` opened; ``part`` takes the span's last
        piece (see :meth:`piece`).  A token the ring has since lapped (or
        :data:`NO_SPAN`) is silently dropped — bounded loss, never an
        error, never a stall."""
        if token < 0 or not self.enabled:
            return
        rec = self._ring[token % self.capacity]
        if rec.seq == token:
            t1 = rec.t1 = time.monotonic()
            self._last_done = token
            if rec.ann is not None:
                rec.ann.__exit__(None, None, None)
                rec.ann = None
            self._charge(rec, part, t1)[0] += 1

    def piece(self, token: int, part: str) -> None:
        """Charge the open span ``token``'s time since its last piece (or
        its begin) to ``part``: a span whose time is split by what the
        program was doing while it ran.  Each piece adds to the span's
        total as it is charged, so a name's parts always sum to its
        ``total_s``; the count grows when the span ends.  Hot-path safe
        like :meth:`end`: one clock read and a few stores."""
        if token < 0 or not self.enabled:
            return
        rec = self._ring[token % self.capacity]
        if rec.seq == token:
            self._charge(rec, part, time.monotonic())

    def _charge(self, rec: _Rec, part: str | None, t: float) -> list:
        """Add ``rec``'s piece up to ``t`` to its totals (and to ``part``'s
        share of them); returns the totals' ``[count, total_s]``."""
        total = self._totals.get(rec.name)
        if total is None:
            total = self._totals[rec.name] = [0, 0.0]
        dt = t - rec.tp
        total[1] += dt
        if part is not None:
            parts = self._parts.get(rec.name)
            if parts is None:
                parts = self._parts[rec.name] = {}
            parts[part] = parts.get(part, 0.0) + dt
        rec.tp = t
        return total

    def count(self, name: str, n: int = 1) -> None:
        """Bump a host-side named counter (Counter add — hot-path safe)."""
        if self.enabled:
            self.counters[name] += n

    # -- off-hot-path API ----------------------------------------------

    def event(self, name: str, **fields) -> None:
        """Record a point event with arbitrary JSON-able fields.  The
        convenient/allocating API: request lifecycle, recovery
        decisions, checkpoint writes — anything not on a designated hot
        path (the obs-in-hot-path rule rejects it there)."""
        if not self.enabled:
            return
        seq = self._seq
        rec = self._ring[seq % self.capacity]
        rec.seq = seq
        rec.kind = "event"
        rec.name = name
        rec.fields = fields or None
        rec.ann = None
        rec.t0 = time.monotonic()
        rec.t1 = rec.t0
        self._seq = seq + 1
        self._last_done = seq

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Context-manager span with tags — the allocating twin of
        ``begin``/``end`` for off-hot-path regions."""
        token = self.begin(name)
        if token >= 0 and fields:
            self._ring[token % self.capacity].fields = fields
        try:
            yield token
        finally:
            self.end(token)

    # -- reads ----------------------------------------------------------

    def __len__(self) -> int:
        return min(self._seq, self.capacity)

    def _record_dict(self, rec: _Rec) -> dict:
        out = {"seq": rec.seq, "kind": rec.kind, "name": rec.name,
               "t0": rec.t0 - self.anchor_monotonic}
        if rec.kind == "span":
            out["dur"] = (rec.t1 - rec.t0) if rec.t1 >= 0.0 else None
        if rec.fields:
            out["fields"] = dict(rec.fields)
        return out

    def snapshot(self) -> list[dict]:
        """The ring's surviving records, oldest first, as plain dicts
        (relative-seconds timestamps).  Tolerates concurrent writers
        (the watchdog's monitor thread snapshots while the scheduler
        records): a record overwritten mid-read is skipped, never a
        crash — the flight recorder prefers a dropped span to a hang."""
        out = []
        top = self._seq
        for seq in range(max(0, top - self.capacity), top):
            rec = self._ring[seq % self.capacity]
            try:
                if rec.seq != seq:
                    continue  # lapped by a concurrent writer
                out.append(self._record_dict(rec))
            except Exception:
                continue
        return out

    def last_span(self) -> dict | None:
        """The most recently COMPLETED record (the watchdog's "last
        thing that finished before the hang")."""
        token = self._last_done
        if token < 0:
            return None
        rec = self._ring[token % self.capacity]
        if rec.seq != token:
            return None
        return self._record_dict(rec)

    def summary(self) -> dict:
        """Per-span-name cumulative totals of every span closed since
        construction (or :meth:`clear`), lapped out of the ring or not:
        ``{name: {"count": n, "total_s": s}}``.  Monotone, so two
        snapshots' difference is a window's time and the Prometheus
        exposition of it can be ``rate()``d — the rollup ``metrics()``
        snapshots embed.  A span charged in pieces (:meth:`piece`) also
        has ``"parts"``: ``{part: seconds}``, summing to its total."""
        out = {name: {"count": n, "total_s": s}
               for name, (n, s) in list(self._totals.items())}
        for name, parts in list(self._parts.items()):
            out[name]["parts"] = dict(parts)
        return out

    def clear(self) -> None:
        self._seq = 0
        self._last_done = NO_SPAN
        for rec in self._ring:
            rec.seq = -1
            rec.ann = None
        self.counters.clear()
        self._totals.clear()
        self._parts.clear()

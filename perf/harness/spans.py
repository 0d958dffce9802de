"""The harness's own spans, around its calls into each layer.

Recorded on the host clock in memory; while the profiler runs each span
is also a ``jax.profiler.TraceAnnotation``, which puts it on the trace's
clock beside the device's operations so that idle gaps can be attributed
to what the host was doing."""

from __future__ import annotations

import time


class _Span:
    __slots__ = ("rec", "name", "t0", "ann")

    def __init__(self, rec, name):
        self.rec = rec
        self.name = name
        self.ann = None

    def __enter__(self):
        if self.rec.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec.rows.append((self.name, self.t0, time.perf_counter()))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Spans:
    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = False  # set while the profiler runs

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def durations(self, name: str, lo: float, hi: float) -> list[float]:
        """Durations of the spans called ``name`` that ended in
        ``[lo, hi]``."""
        return [t1 - t0 for n, t0, t1 in self.rows
                if n == name and lo <= t1 <= hi]

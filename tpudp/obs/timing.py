"""Dispatch-honest timing helpers (the ``tpudp.obs`` home of the old
``tpudp/utils/timing.py`` — that module now re-exports from here so
existing imports keep working).

The reference brackets ``time.time()`` around eager torch calls
(``src/Part 2a/main.py:87-98``).  Under JAX async dispatch a naive
bracket measures dispatch, not compute — every timer here waits on the
measured value with ``jax.block_until_ready`` before reading the clock
(SURVEY.md §7 "timing honesty" hard part).
"""

from __future__ import annotations

import time


class StepTimer:
    """Accumulates wall time across steps with block_until_ready edges."""

    def __init__(self):
        self.total = 0.0
        self.count = 0
        self._t0 = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, *block_on) -> float:
        import jax

        jax.block_until_ready(block_on)
        dt = time.perf_counter() - self._t0
        self.total += dt
        self.count += 1
        return dt

    @property
    def mean(self) -> float:
        return self.total / max(self.count, 1)

    def reset(self) -> None:
        self.total, self.count = 0.0, 0

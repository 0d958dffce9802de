"""Background-thread batch prefetching.

The reference overlaps host-side data work with compute via torch DataLoader
worker processes (``num_workers=2``, ``src/Part 2a/main.py:39-44``).  Under
JAX async dispatch the device is already busy while Python prepares the next
batch, but the *host* augmentation (gather + crop/flip + normalize) still
runs serially with step dispatch; a single daemon thread with a small queue
hides it entirely.  Threads suffice (no worker processes): the heavy lifting
is numpy/native C++ code that releases the GIL.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator


class Prefetcher:
    """Wraps any loader (iterable of batches, with ``set_epoch``/``__len__``)
    and prepares up to ``depth`` batches ahead on a daemon thread.  Batch
    order and content are identical to the wrapped loader's.

    ``place`` (or :meth:`set_place`, which the Trainer calls with its
    input-sharding device_put) additionally runs on the worker thread, so
    host→device transfers START ``depth`` batches ahead of consumption
    instead of at step-dispatch time — device-side prefetch.  Matters most
    when the H2D link is slow relative to the step (~3 MB of CIFAR batch
    per step against a few-ms VGG step); JAX dispatch is thread-safe and
    transfers overlap compute."""

    _DONE = object()

    def __init__(self, loader, depth: int = 2, place=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.loader = loader
        self.depth = depth
        self.place = place
        self._lock = threading.Lock()
        self._live: list[tuple[threading.Event, threading.Thread]] = []

    def close(self, timeout: float = 5.0) -> None:
        """Stop every live worker thread and wait for it to exit.

        Abandoning iteration mid-epoch normally stops the worker via the
        generator's ``finally`` (GC-driven), but a consumer that merely
        drops the iterator without closing it — a supervisor restarting
        the pipeline, a relaunched soak worker — must be able to
        GUARANTEE no ``tpudp-prefetch`` thread survives and no ``put`` is
        left blocked.  Idempotent; the Prefetcher remains iterable after
        close (a new ``__iter__`` spawns a fresh worker)."""
        with self._lock:
            live = list(self._live)
        for stop, _t in live:
            stop.set()
        for _stop, t in live:
            t.join(timeout)
        with self._lock:
            self._live = [e for e in self._live if e[1].is_alive()]

    def set_place(self, fn) -> None:
        """Install/replace the batch-placement hook (applies to batches
        queued after this call; the Trainer installs it before iterating)."""
        self.place = fn

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator:
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def put(item) -> bool:
            """Bounded put that aborts when the consumer is gone."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker() -> None:
            try:
                for batch in self.loader:
                    if self.place is not None:
                        batch = self.place(batch)
                    if not put(batch):
                        return
                put(self._DONE)
            except BaseException as e:  # re-raise on the consumer side
                put(e)

        t = threading.Thread(target=worker, daemon=True, name="tpudp-prefetch")
        with self._lock:
            self._live.append((stop, t))
        t.start()
        try:
            while True:
                item = q.get()
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            with self._lock:
                self._live = [e for e in self._live if e[0] is not stop]

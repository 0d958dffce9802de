"""Deterministic fault injection for the TRAINING stack — the trainer
analogue of ``tpudp/serve/faults.py``, and the resilience layer's test
fixtures plus the kill/resume soak's building blocks.

The supervisor's recovery claims (divergence rollback, in-process step
retry, loader containment, checkpoint-corruption fallback —
``tpudp/resilience.py``) are only worth anything if they are exercised by
REPRODUCIBLE faults: which batch is poisoned, which device call raises,
which checkpoint byte flips is fixed by constructor arguments, so a
failing soak seed replays exactly.

Three injection seams:

  * **Batch corruption** — :class:`CorruptingLoader` wraps any loader and
    poisons specific batch DRAWS (a global monotonically increasing draw
    counter): ``nan_at`` yields NaN images (NaN grads -> NaN params ->
    the ``check_finite`` window check fires — the divergence scenario),
    ``spike_at`` scales images by ``spike_scale`` (a finite loss spike
    for the trailing-median detector).  One-shot by construction: a
    rollback's deterministic replay re-draws batches under NEW counter
    values, so the poison never re-fires and the replay is clean —
    exactly how a transient production fault behaves.
  * **Step faults** — :class:`RaisingStep` and :class:`StallingStep` are
    ``Trainer(step_fault_hook=...)`` callables invoked as
    ``hook(kind, index)`` immediately before each jitted device call
    (``kind`` in ``{"train", "eval"}``; ``index`` is the trainer's
    monotonically increasing device-call counter, so a retried step gets
    a NEW index and a one-shot fault stays one-shot).  Raising simulates
    a device-step failure (XLA error, preempted TPU); sleeping simulates
    a wedged step for the watchdog to catch.
  * **Loader faults** — :class:`RaisingLoader` raises from the data
    pipeline at a specific draw, standing in for a dying loader /
    ``Prefetcher`` worker; the supervisor must restart the pipeline at
    the exact batch offset with host-RNG replay.

Plus :func:`corrupt_checkpoint`: deterministic on-disk corruption (byte
flip / truncation / manifest tamper) driving the verified-restore
fallback tests and the soak's corrupt-checkpoint phase.

Used by ``tests/test_resilience.py`` and the kill/resume soak referee
``benchmarks/resilience_bench.py``.
"""

from __future__ import annotations

import os
import time

import numpy as np


class InjectedTrainingFault(RuntimeError):
    """Raised by the injectors below — typed so tests can tell an
    injected failure from an organic one."""


class _LoaderWrapper:
    """Forwards the loader protocol (set_epoch/__len__/set_place) so a
    wrapped loader still composes with the Trainer and the Prefetcher."""

    def __init__(self, loader):
        self.loader = loader

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def set_place(self, fn) -> None:
        if hasattr(self.loader, "set_place"):
            self.loader.set_place(fn)

    def __len__(self) -> int:
        return len(self.loader)


class CorruptingLoader(_LoaderWrapper):
    """Poisons specific batch draws.  ``nan_at``/``spike_at`` are
    collections of GLOBAL draw indices (0-based, counted across epochs and
    across pipeline restarts — the counter only moves forward, so a
    replayed batch is drawn under a new index and the fault is one-shot).

    ``fired`` records ``(kind, draw_index)`` for the soak's accounting:
    every fired fault must have a matching recovery in the event log."""

    def __init__(self, loader, *, nan_at=(), spike_at=(),
                 spike_scale: float = 1e4):
        super().__init__(loader)
        self.nan_at = set(nan_at)
        self.spike_at = set(spike_at)
        self.spike_scale = spike_scale
        self.draws = 0
        self.fired: list[tuple[str, int]] = []

    def __iter__(self):
        for images, labels, weights in self.loader:
            i = self.draws
            self.draws += 1
            if i in self.nan_at:
                self.fired.append(("nan", i))
                images = np.asarray(images) * np.float32(np.nan)
            elif i in self.spike_at:
                self.fired.append(("spike", i))
                images = np.asarray(images) * np.float32(self.spike_scale)
            yield images, labels, weights


class RaisingLoader(_LoaderWrapper):
    """Raises :class:`InjectedTrainingFault` instead of yielding the
    draws in ``fail_at`` (global draw indices; the failed draw is counted,
    so the restarted pipeline's replay passes it under a new index —
    one-shot, like a worker that died once)."""

    def __init__(self, loader, fail_at=()):
        super().__init__(loader)
        self.fail_at = set(fail_at)
        self.draws = 0
        self.fired: list[tuple[str, int]] = []

    def __iter__(self):
        for batch in self.loader:
            i = self.draws
            self.draws += 1
            if i in self.fail_at:
                self.fired.append(("loader", i))
                raise InjectedTrainingFault(
                    f"injected loader failure at draw {i}")
            yield batch


class RaisingStep:
    """Step-raise hook: raises :class:`InjectedTrainingFault` when the
    trainer's device-call ``index`` is in ``fail_at`` (optionally
    restricted to one ``kind``).  The hook runs before the device call,
    so the injected failure lands exactly where a real one would: inside
    the supervisor's step-recovery region.  ``persist_from`` instead
    fails EVERY call from that index on — the permanent-fault case the
    same-step escalation budget exists for."""

    def __init__(self, fail_at=(), kind: str | None = None,
                 persist_from: int | None = None):
        self.fail_at = set(fail_at)
        self.kind = kind
        self.persist_from = persist_from
        self.fired: list[tuple[str, int]] = []

    def __call__(self, kind: str, index: int) -> None:
        hit = index in self.fail_at or (
            self.persist_from is not None and index >= self.persist_from)
        if hit and (self.kind is None or kind == self.kind):
            self.fired.append((kind, index))
            raise InjectedTrainingFault(
                f"injected step fault at {kind} call {index}")


class StallingStep:
    """Step-stall hook: sleeps ``delay_s`` before the configured device
    calls — a deterministic stand-in for a wedged TPU step, used to
    exercise heartbeat-watchdog hang recovery (the sleep happens between
    two ``beat()`` calls, so a ``kill=False`` watchdog surfaces
    ``StepHangError`` at the next beat)."""

    def __init__(self, stall_at, delay_s: float, kind: str | None = None):
        self.stall_at = set(stall_at)
        self.delay_s = delay_s
        self.kind = kind
        self.fired: list[tuple[str, int]] = []

    def __call__(self, kind: str, index: int) -> None:
        if index in self.stall_at and (self.kind is None
                                       or kind == self.kind):
            self.fired.append((kind, index))
            time.sleep(self.delay_s)


def corrupt_checkpoint(path: str | os.PathLike, mode: str = "flip") -> str:
    """Deterministically corrupt the checkpoint at ``path``; returns the
    file touched.  Modes:

    * ``"flip"`` — XOR-flips one byte in the middle of the largest data
      file (silent bit rot: orbax may restore cleanly, the manifest
      checksum catches it; or orbax's own framing fails — either way the
      verified-restore fallback must engage)
    * ``"flip_shard"`` — same flip, but targeted at the largest file
      under the checkpoint's ``d/`` subtree — the OCDBT payload domain
      where a MULTI-HOST save's shard bytes live (the largest file
      overall in that layout is often process metadata whose flip orbax
      shrugs off).  This is "one host's shard rotted": the per-host
      crc32 shard manifests must catch it, on the saved geometry and on
      the reassembled view after an elastic restore.  Falls back to the
      plain flip when no ``d/`` subtree exists (single-host layouts).
    * ``"truncate"`` — cuts the largest file in half (torn write)
    * ``"manifest"`` — tampers a checksum in the sidecar manifest (the
      paranoid case: manifest and data disagree)
    """
    path = os.path.abspath(os.fspath(path))
    if mode == "manifest":
        import json

        from tpudp.utils.checkpoint import manifest_path

        mpath = manifest_path(path)
        with open(mpath) as f:
            manifest = json.load(f)
        leaves = manifest.get("leaves", {})
        for key in sorted(leaves):
            if "crc32" in leaves[key]:
                leaves[key]["crc32"] ^= 0x1
                break
        else:
            raise ValueError(f"no checksummed leaf in {mpath}")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        return mpath
    if mode not in ("flip", "flip_shard", "truncate"):
        raise ValueError(f"unknown corruption mode {mode!r}")
    walk_root = path
    if mode == "flip_shard" and os.path.isdir(os.path.join(path, "d")):
        walk_root = os.path.join(path, "d")
    files = []
    for dirpath, _dirs, names in os.walk(walk_root):
        for name in names:
            p = os.path.join(dirpath, name)
            files.append((os.path.getsize(p), p))
    if not files:
        raise ValueError(f"no files under checkpoint dir {walk_root}")
    _, target = max(files)  # largest file = the biggest leaf's payload
    size = os.path.getsize(target)
    if mode == "truncate":
        with open(target, "r+b") as f:
            f.truncate(max(size // 2, 1))
        return target
    with open(target, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    return target

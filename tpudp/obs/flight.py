"""Fault-triggered flight recorder: the last N spans/events/counters,
persisted the moment something goes wrong.

The runtime's failure paths all share one shape: a detector fires
(watchdog timeout, step-failure containment, NaN/spike rollback, vote
timeout) and the process either recovers or dies — and before this
module, either way the timeline that LED there was gone.  A
:class:`FlightRecorder` wraps a :class:`tpudp.obs.Recorder` and, on
demand, dumps its ring plus context to a per-host
``flightrec-<host>-<seq>-<reason>.json`` under a configured directory —
the black box the resilience soak and serve watchdog kills can be
debugged from.

Activation is by DIRECTORY: ``directory=None`` resolves through the
``TPUDP_FLIGHT_DIR`` environment variable, and when neither is set
every ``dump()`` is a no-op — so the recorder can be wired
unconditionally through the engine/trainer/watchdog without any
default-path behavior change.

Multi-host: each host dumps LOCALLY (a dump must never require a dead
peer), and :func:`coordinated_merge` — called only from points every
live host reaches together, e.g. after a coordinated recovery — has
rank 0 merge the per-host files into one ``flightrec-merged.json``
after a ``gather_host_values`` round confirms how many dumps each host
banked.  The gather rides the existing checkpoint-protocol seam and
sits outside every hot path.
"""

from __future__ import annotations

import json
import os
import time

from tpudp.obs.record import Recorder

#: Environment default for the dump directory (CLI flags/constructor
#: arguments override).  Unset + no explicit directory = dumps disabled.
FLIGHT_DIR_ENV = "TPUDP_FLIGHT_DIR"


def resolve_flight_dir(directory: str | None) -> str | None:
    """Explicit directory, else the ``TPUDP_FLIGHT_DIR`` env default,
    else None (dumping disabled)."""
    if directory:
        return directory
    return os.environ.get(FLIGHT_DIR_ENV) or None


def _host_index() -> int:
    """This process's host index without forcing a jax backend: jax is
    consulted only if it is already imported and initialized (the dump
    path may run while the device is wedged — it must never trigger
    distributed init itself)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            pass
    return 0


class FlightRecorder:
    """Dumps a :class:`Recorder`'s ring to per-host JSON files.

    One instance per engine/trainer; ``dump()`` is safe from any thread
    (the watchdog's monitor thread calls it right before killing the
    process) and never raises — a broken disk must not mask the fault
    being recorded.
    """

    def __init__(self, recorder: Recorder, directory: str | None = None,
                 component: str = ""):
        self.recorder = recorder
        self.directory = resolve_flight_dir(directory)
        self.component = component or recorder.name or "tpudp"
        self._dumped = 0

    @property
    def enabled(self) -> bool:
        return self.directory is not None

    @property
    def dumps(self) -> int:
        """Dumps successfully written by THIS instance."""
        return self._dumped

    def dump(self, reason: str, extra: dict | None = None) -> str | None:
        """Persist the black box: ring snapshot + counters + context.
        Returns the written path, or None when disabled or the write
        failed (best-effort by contract)."""
        if self.directory is None:
            return None
        try:
            host = _host_index()
            rec = self.recorder
            payload = {
                "kind": "tpudp_flight_record",
                "component": self.component,
                "reason": reason,
                "host": host,
                "seq": self._dumped,
                "wall_time": time.time(),
                "anchor_wall": rec.anchor_wall,
                "counters": dict(rec.counters),
                "last_span": rec.last_span(),
                "spans": rec.snapshot(),
            }
            if extra:
                payload["extra"] = extra
            os.makedirs(self.directory, exist_ok=True)
            safe = "".join(c if c.isalnum() or c in "-_" else "-"
                           for c in reason)[:48]
            path = os.path.join(
                self.directory,
                f"flightrec-{self.component}-h{host}-"
                f"{self._dumped:03d}-{safe}.json")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True,
                          default=str)
                f.write("\n")
            os.replace(tmp, path)
            self._dumped += 1
            return path
        except Exception:
            return None  # best-effort: never mask the fault being recorded


def _dump_paths(directory: str) -> list[str]:
    """Sorted flight-record files under ``directory`` (sorted so every
    host walks the same order — the merge below is a coordination-
    adjacent path)."""
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return []
    return [os.path.join(directory, n) for n in names
            if n.startswith("flightrec-") and n.endswith(".json")
            and "merged" not in n]


def merge_dumps(directory: str) -> str | None:
    """Merge every per-host flight record under ``directory`` into
    ``flightrec-merged.json`` (records sorted by host then sequence).
    Pure file I/O — callable post-mortem on a dead pod's shared dir."""
    paths = _dump_paths(directory)
    if not paths:
        return None
    records = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as f:
                records.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            records.append({"kind": "tpudp_flight_record",
                            "error": f"unreadable dump {p}"})
    records.sort(key=lambda r: (r.get("host", 0), r.get("seq", 0)))
    out = os.path.join(directory, "flightrec-merged.json")
    tmp = out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"kind": "tpudp_flight_record_merged",
                   "merged": len(records), "records": records}, f,
                  indent=1, sort_keys=True, default=str)
        f.write("\n")
    os.replace(tmp, out)
    return out


def coordinated_merge(directory: str | None) -> str | None:
    """Rank 0 merges the per-host dumps, after a ``gather_host_values``
    round confirms every live host's dump count (the existing
    cross-host seam from the checkpoint protocol — every host must call
    this together, from a point all of them reach, e.g. after a
    coordinated recovery; NEVER from a path where a peer may be dead).
    Single-process: plain local merge.  Returns rank 0's merged path
    (None elsewhere / when disabled)."""
    directory = resolve_flight_dir(directory)
    if directory is None:
        return None
    import jax

    if jax.process_count() > 1:
        from tpudp.utils.checkpoint import gather_host_values

        gather_host_values(len(_dump_paths(directory)))
    if jax.process_index() == 0:
        return merge_dumps(directory)
    return None

"""Exporter: Chrome/Perfetto ``trace_event`` JSON and its inverse.

A recorder ring is only useful if something can read it:

  * :func:`to_chrome_trace` — the Chrome ``trace_event`` JSON format
    (the ``traceEvents`` array), loadable by Perfetto
    (https://ui.perfetto.dev) and ``chrome://tracing``.  Spans become
    complete ("X") events with microsecond timestamps, point events
    become instant ("i") events, counters become one counter ("C")
    sample.  ``pid`` is the host index, ``tid`` the component name —
    multi-host merges lay out one track per host.
  * :func:`spans_from_chrome_trace` — the inverse mapping back to
    recorder-snapshot dicts; :func:`to_chrome_trace` ∘
    :func:`spans_from_chrome_trace` is the identity on (name, kind,
    t0, dur, fields), which the schema round-trip test pins so the
    export can never drift from what Perfetto parses.
"""

from __future__ import annotations

from tpudp.obs.record import Recorder

_US = 1e6


def to_chrome_trace(recorder: Recorder, *, pid: int = 0,
                    tid: str | None = None) -> dict:
    """Recorder ring → Chrome ``trace_event`` JSON object."""
    tid = tid if tid is not None else (recorder.name or "tpudp")
    events = []
    for rec in recorder.snapshot():
        ts = rec["t0"] * _US
        base = {"name": rec["name"], "pid": pid, "tid": tid,
                "cat": "tpudp"}
        if rec.get("fields"):
            base["args"] = rec["fields"]
        if rec["kind"] == "span":
            dur = rec.get("dur")
            events.append({**base, "ph": "X", "ts": ts,
                           "dur": (dur if dur is not None else 0.0) * _US,
                           **({"args": {**base.get("args", {}),
                                        "open": True}}
                              if dur is None else {})})
        else:
            events.append({**base, "ph": "i", "ts": ts, "s": "t"})
    for name, value in sorted(recorder.counters.items()):
        events.append({"name": name, "ph": "C", "pid": pid, "tid": tid,
                       "cat": "tpudp", "ts": 0.0,
                       "args": {"value": value}})
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "component": recorder.name,
            "anchor_wall": recorder.anchor_wall,
        },
    }


def spans_from_chrome_trace(trace: dict) -> list[dict]:
    """Chrome trace object → recorder-snapshot-shaped dicts (the
    round-trip inverse; counter samples are skipped — they come back
    through the counters dict, not the ring)."""
    out = []
    for ev in trace.get("traceEvents", []):
        ph = ev.get("ph")
        if ph == "X":
            args = dict(ev.get("args") or {})
            open_span = bool(args.pop("open", False))
            rec = {"kind": "span", "name": ev["name"],
                   "t0": ev["ts"] / _US,
                   "dur": None if open_span else ev.get("dur", 0.0) / _US}
            if args:
                rec["fields"] = args
            out.append(rec)
        elif ph == "i":
            rec = {"kind": "event", "name": ev["name"],
                   "t0": ev["ts"] / _US}
            if ev.get("args"):
                rec["fields"] = dict(ev["args"])
            out.append(rec)
    return out

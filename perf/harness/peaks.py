"""The one table of peaks, keyed by ``device_kind``."""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perf/harness/peaks.json "
            f"(known: {sorted(k for k in table if not k.startswith('_'))}); "
            "add its published peaks with their source")
    return table[device_kind]

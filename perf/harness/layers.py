"""What the program's own instrumentation gives the per-layer readers.

Two sources, both reached without an edit to the harness:

  * ``run.window["engine_stats"]``: the window's delta of every numeric
    key of ``Engine.metrics()["stats"]``.  The engine's phase spans land
    there as cumulative seconds (``step_s``, ``fetch_wait_s``, ...,
    from ``tpudp.obs.Recorder``'s totals) and its request stamps as
    ``first_tokens`` / ``ttft_s`` / ``ttft_queue_s`` /
    ``ttft_prefill_wait_s``;
  * ``run.trace_form["devices"]``: per chip, every operation's label,
    which starts with the HLO instruction's name.  The attention kernels
    carry a ``name=`` on their ``pallas_call``, so their instructions are
    ``flash_fwd.12``, ``paged_decode.3``, ...

A program without the spans, the stamps or the names (the parent of the
PR that brought them; ``Engine(obs=False)``) leaves nothing to read, and
every function here then returns None.
"""

from __future__ import annotations

import re

from perf.harness.trace import WINDOW_SPAN


def engine_seconds(run, *keys: str) -> tuple | None:
    """``(steps, seconds of each key)`` over the measured window, or None
    where the engine reported no step or lacks one of the counters."""
    st = run.window.get("engine_stats")
    if not st or not st.get("steps") or any(k not in st for k in keys):
        return None
    return (st["steps"], *(st[k] for k in keys))


def kernel_ms(run, kernel: str, steps_key: str) -> float | None:
    """Device milliseconds a step inside the Mosaic calls whose HLO
    instruction is called ``kernel`` (``kernel`` or ``kernel.<n>``): the
    durations of those operations that touch ``perf.traced_window``,
    mean over the chips, over ``run.traced[steps_key]``.  The same
    operations and the same window as the reduction's ``kernel_s``, so
    the kernels of one cell sum to its ``kernel_share`` x busy time."""
    form, traced = run.trace_form, run.traced
    if not form or not traced or not traced.get(steps_key):
        return None
    windows = [h for h in form.get("host", []) if h[0] == WINDOW_SPAN]
    if not windows or not form.get("devices"):
        return None
    w0 = windows[-1][1]
    w1 = w0 + windows[-1][2]
    named = re.compile(re.escape(kernel) + r"(\.\d+)? ")
    total = 0.0
    calls = 0
    for ops in form["devices"].values():
        for label, start, dur, is_kernel, _op in ops:
            if (is_kernel and start < w1 and start + dur > w0
                    and named.match(label)):
                total += dur
                calls += 1
    if not calls:
        return None
    return 1e3 * total / len(form["devices"]) / traced[steps_key]

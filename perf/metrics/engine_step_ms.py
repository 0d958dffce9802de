"""Median wall time of one ``Engine.step()`` call in the measured
window (the harness's ``perf.engine_step`` spans, host clock)."""

from statistics import median


def read(run):
    steps = run.window.get("step_s")
    if not steps:
        return None
    return 1e3 * median(steps)

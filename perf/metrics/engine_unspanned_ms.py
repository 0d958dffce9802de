"""Milliseconds of one ``Engine.step()`` in none of its phase spans:
``step``'s time outside its direct children (``stats["step_self_s"]``, which
the engine computes because only it knows how its spans nest) over the steps
of the measured window.  Near zero when the spans account for the step."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "step_self_s")
    if got is None:
        return None
    steps, self_s = got
    return 1e3 * self_s / steps

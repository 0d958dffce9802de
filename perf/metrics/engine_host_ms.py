"""Host milliseconds of one ``Engine.step()``: the engine's ``step``
span less the time it blocks on the device's results (``fetch``,
``first_token_wait``), over the steps of the measured window — what a
step would take if the device took no time.  Admission, the scheduler's
own bookkeeping, page allocation, the dispatches and the commit loop
are in it; so is host work that overlaps device time today."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "step_s", "fetch_wait_s", "first_token_wait_s")
    if got is None:
        return None
    steps, step_s, fetch_s, first_s = got
    return 1e3 * (step_s - fetch_s - first_s) / steps

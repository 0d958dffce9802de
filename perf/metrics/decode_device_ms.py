"""Device-busy milliseconds per engine step (one batched decode plus at
most one prefill chunk): the union of the device's operation intervals
in the traced window over the ``Engine.step()`` calls made in it (those
that drain the last first tokens after the window's end included: the
profiler runs until they are done)."""


def read(run):
    if run.trace is None or not run.traced \
            or not run.traced.get("steps_with_drain"):
        return None
    return 1e3 * run.trace["busy_s"] / run.traced["steps_with_drain"]

"""Milliseconds of one ``Engine.step()`` in which the engine knew that none
of its device work was in flight: the ``exposed`` span's seconds
(``stats["exposed_s"]``) over the steps of the measured window.  The span
opens when a blocking fetch of the newest work returns or a readiness check
finds it done, and closes when the next program is enqueued, so it is a
lower bound on the device's idle time: the host's share of it."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "exposed_s")
    if got is None:
        return None
    steps, exposed_s = got
    return 1e3 * exposed_s / steps

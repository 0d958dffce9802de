"""Milliseconds of one ``Engine.step()`` in which the device waited while a
program was being handed to it: the part of the ``exposed`` span inside the
engine's dispatch spans (``stats["exposed_dispatch_s"]``) over the steps of
the measured window.  The rest of ``engine_exposed_ms`` is the scheduler's
own Python."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "exposed_dispatch_s")
    if got is None:
        return None
    steps, dispatch_s = got
    return 1e3 * dispatch_s / steps

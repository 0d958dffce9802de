"""Milliseconds of one ``Engine.step()`` the host spends blocked on the
device: the ``fetch`` span (the token fetch after the decode dispatch)
plus ``first_token_wait`` (the fetch of a prompt's first token), over
the steps of the measured window — the device time the host actually
waits for.  With ``engine_host_ms`` it makes up the engine's ``step``
span."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "fetch_wait_s", "first_token_wait_s")
    if got is None:
        return None
    steps, fetch_s, first_s = got
    return 1e3 * (fetch_s + first_s) / steps

"""Milliseconds a step, averaged over ALL steps of the measured window,
in the blocking fetch of a prompt's first token (``first_token_wait``):
the sync that keeps the step's decode from being queued behind the
prompt's last chunk.  Only steps that finish a prompt pay it."""

from perf.harness.layers import engine_seconds


def read(run):
    got = engine_seconds(run, "first_token_wait_s")
    if got is None:
        return None
    steps, first_s = got
    return 1e3 * first_s / steps

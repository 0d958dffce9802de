"""Device milliseconds a engine step inside the paged single-token decode attention kernel:
the Mosaic calls whose HLO instruction is named ``paged_decode`` (the
``name=`` on its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "paged_decode", "steps_with_drain")

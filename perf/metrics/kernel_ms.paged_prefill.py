"""Device milliseconds a engine step inside the paged window kernel (a prompt chunk's attention):
the Mosaic calls whose HLO instruction is named ``paged_prefill`` (the
``name=`` on its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "paged_prefill", "steps_with_drain")

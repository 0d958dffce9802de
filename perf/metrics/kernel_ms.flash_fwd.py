"""Device milliseconds a train step inside the flash-attention forward kernel:
the Mosaic calls whose HLO instruction is named ``flash_fwd`` (the
``name=`` on its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "flash_fwd", "steps")

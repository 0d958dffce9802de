"""Device milliseconds a train step inside the flash-attention backward kernel for dq:
the Mosaic calls whose HLO instruction is named ``flash_bwd_dq`` (the
``name=`` on its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "flash_bwd_dq", "steps")

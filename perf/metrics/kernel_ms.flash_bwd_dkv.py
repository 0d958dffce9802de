"""Device milliseconds a train step inside the flash-attention backward kernel for dk and dv:
the Mosaic calls whose HLO instruction is named ``flash_bwd_dkv`` (the
``name=`` on its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "flash_bwd_dkv", "steps")

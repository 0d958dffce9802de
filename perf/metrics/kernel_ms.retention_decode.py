"""Device milliseconds an engine step inside the power-retention decode
kernel: the Mosaic calls whose HLO instruction is named
``retention_decode`` (``tpudp/ops/retention.py``, one a layer a decode
run) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "retention_decode", "steps_with_drain")

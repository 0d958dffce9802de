"""Device milliseconds a train step inside the expert layer's grouped
product kernel ``moe_tgmm`` (tpudp/ops/grouped_matmul.py; the ``name=`` on
its ``pallas_call``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "moe_tgmm", "steps")

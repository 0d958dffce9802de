"""Device milliseconds an engine step inside the expert layer's grouped
product kernel ``moe_gmm`` (tpudp/ops/grouped_matmul.py; the ``name=`` on
its ``pallas_call``) in the traced window: the prefill chunk's and the
decode run's calls of every expert layer, over the loop's steps."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "moe_gmm", "steps_with_drain")

"""Device milliseconds an engine step inside the paged prefill-chunk
attention kernel of the SLIDING-WINDOW layers: the Mosaic calls whose HLO
instruction is named ``paged_prefill_window`` (the full-attention layers'
calls keep ``paged_prefill``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "paged_prefill_window", "steps_with_drain")

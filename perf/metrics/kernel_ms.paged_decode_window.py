"""Device milliseconds an engine step inside the paged single-token decode
attention kernel of the SLIDING-WINDOW layers: the Mosaic calls whose HLO
instruction is named ``paged_decode_window`` (the ``name=`` a windowed
call of ``tpudp/ops/paged_attention.py`` carries; the full-attention
layers' calls keep ``paged_decode``) in the traced window."""

from perf.harness.layers import kernel_ms


def read(run):
    return kernel_ms(run, "paged_decode_window", "steps_with_drain")

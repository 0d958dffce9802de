"""Share of its roofline the ``moe_tgmm`` kernel reaches in the traced window:
calls seen x the least time one grouped product of the cell's expected
rows can take on this chip, over the calls' device seconds (the family's
``kernel_roofline_share``; counts in ``grouped_product_counts``)."""


def read(run):
    share = getattr(run.cell.family, "kernel_roofline_share", None)
    return None if share is None else share(run, "moe_tgmm")

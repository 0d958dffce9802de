"""Share of device-busy time inside Mosaic custom calls (the flash
attention forward and backward kernels) in the traced window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["kernel_s"] / run.trace["busy_s"]

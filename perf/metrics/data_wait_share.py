"""Share of the measured window the training loop spent getting its next
batch (the driver sums the spans it has as ``data_wait_s``: the harness's
``perf.next_batch`` where it runs the example's loop).  The host runs up
to a reading ahead of the device, so a small share costs the device
nothing; one near ``100% - the device's busy share`` starves it."""


def read(run):
    w = run.window
    if "data_wait_s" not in w:
        return None
    return 100.0 * w["data_wait_s"] / (w["t1"] - w["t0"])

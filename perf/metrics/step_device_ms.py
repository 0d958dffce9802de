"""Device-busy milliseconds per train step: the union of the device's
operation intervals in the traced window (mean over the chips used) over
the steps dispatched in it."""


def read(run):
    if run.trace is None or not run.traced or not run.traced.get("steps") \
            or "samples" not in run.traced:
        return None
    return 1e3 * run.trace["busy_s"] / run.traced["steps"]

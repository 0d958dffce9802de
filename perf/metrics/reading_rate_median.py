"""Median of the rates of the readings that tile the measured window (a
reading is the group of steps between two of the loop's barriers): the
end-to-end rate is all the samples over all the time, so one stalled
reading lowers it; this median does not move, and the two side by side
say whether a loss is a stall or a slower step."""

from statistics import median


def read(run):
    readings = run.window.get("readings")
    if not readings:
        return None
    return median(n / dt for dt, n in readings) / len(run.devices)

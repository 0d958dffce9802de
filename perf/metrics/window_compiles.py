"""Programs compiled (or fetched from the cache) inside the measured
window: every shape is warmed in set-up, so this must read 0."""


def read(run):
    return float(run.window_compiles)

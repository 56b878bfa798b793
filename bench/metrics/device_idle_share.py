"""Device: the share of the traced steps' span in which no operation ran on
the chip, in %: 1 - (union of the device's op intervals) / (first traced
step's start to the last one's end)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

"""Milliseconds in which an operation ran on a chip during the traced job
(union of the device's op intervals, mean over the cell's chips)."""


def read(run):
    if run.reduction is None or not run.reduction.planes:
        return None  # the trace saw no device
    return run.reduction.busy_s * 1e3

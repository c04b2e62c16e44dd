"""Share of the traced job in which no operation ran on a chip (profiler
trace, mean over the cell's chips), percent: how far the host holds the chip
back."""


def read(run):
    if run.reduction is None or not run.reduction.planes:
        return None  # the trace saw no device
    return 100.0 * run.reduction.idle_share

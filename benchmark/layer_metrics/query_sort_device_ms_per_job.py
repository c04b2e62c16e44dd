"""Reduce-side read, ordered, under a query: device time of the executables
``jit_ordered_records`` in the traced query, ms (mean over the cell's chips) —
what the chip spends ordering the partitions of the query's shuffles for its
reduce tasks' operators (three reads a task).  Left out where the trace has no
such executable."""

MODULE = "jit_ordered_records("


def read(run):
    if run.reduction is None:
        return None
    device_s = sum(s for name, s in run.reduction.module_s.items() if name.startswith(MODULE))
    return device_s * 1e3 if device_s > 0 else None

"""Reduce-side read, ordered: the program's ``read.ordered.d2h`` span (the
wait, inside ``read.ordered``, until a task's sorted records are
host-readable: the gather and the sort on the device, then their one D2H),
median over every task of the window, us.  Only ``read_batches()`` under
``key_ordering`` records it."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.ordered.d2h")

"""Reduce-side read, ordered: the program's ``read.ordered`` span (one reduce
task's ordered return: blocks located, the gather and the sort dispatched
and, for a consumer on the host, the wait for the one D2H of the sorted
records; not the consumer's use of them), median over every task of the
window, us.  Only a reader that orders on the device records it."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.ordered")

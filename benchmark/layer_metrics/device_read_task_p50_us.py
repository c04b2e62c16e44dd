"""Reduce-side read on the device: the program's ``read.device`` span (one
reduce task's ``read_device()``: blocks located, the gather dispatched, the
packed buffer and its table handed back; not the consumer's use of them),
median over every task of the window, us.  Only a reader that reads on the
device records it."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.device")

"""Reduce-side read on the device: the program's ``read.device.locate`` span
(a task's block ids resolved to rows of the received shards and the gather's
plan built on the host), a child of ``read.device``, median over every task of
the window, us.  ``device_read_task_p50_us`` less this is the dispatch."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.device.locate")

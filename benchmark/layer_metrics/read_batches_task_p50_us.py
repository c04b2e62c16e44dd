"""Reduce-side read in batches: the program's summed span ``read.batches``
(one reduce task's ``read_batches()``: the reader's own turns — issuing and
awaiting the windows, the look-ups, the hand-out of each batch — without the
consumer's turns between batches), median over every task of the window, us.
The program's share of a reduce task, beside ``read_task_p95_ms`` (the client
clock, the consumer's check included).  Only a reader that hands out batches
records it."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.batches")

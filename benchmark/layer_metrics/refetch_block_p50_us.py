"""Reduce-side read: the program's span ``read.refetch.block`` — one block
of a re-placed reduce task pulled through the pull path (the holder's store
read, the copy into the reader's buffer) — median over the window, us.  Left
out where the span was not recorded."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.refetch.block")

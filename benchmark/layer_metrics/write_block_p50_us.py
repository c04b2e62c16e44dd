"""Map-side write, one block of the buffered path: the program's span
``write.block`` — from ``open_partition`` to the end of ``close_partition``,
the stream's ``write`` calls between them included — median over the sampled
blocks (one in 199 of the process), us.  Its three children below partition
``close_partition``; what precedes them is the caller's turn."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "write.block")

"""A ``write_partition`` frame by phase: the program's span
``daemon.write_partition.ack`` — the ack's send, from its start to the frame's
end (the interval the counter ``ack_ns`` sums) — on the sampled frames, median
over the window, us.  The five phases add up to ``daemon_serve_p50_us``."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.write_partition.ack")

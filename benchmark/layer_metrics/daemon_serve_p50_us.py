"""Entry points: the daemon's side of one ``write_partition`` frame — the
program's ``daemon.write_partition`` span, from the frame header's arrival to
the ack sent — median over every block of the window, us.
``wire_write_frame_p50_us`` less this is the socket and the client.  Only the
daemon entry serves frames."""

from benchmark.inner_spans import median_seconds


def read(run):
    value = median_seconds(run, "daemon.write_partition")
    return None if value is None else value * 1e6

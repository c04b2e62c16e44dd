"""Entry-point wire: client-side time of one ``write_partition`` call (frame
sent to JSON ack read), median over every block of the window, us.  Only the
daemon entry records frames."""

from benchmark.measured import median


def read(run):
    p50 = median(run.frame_ns)
    return None if p50 is None else p50 / 1e3

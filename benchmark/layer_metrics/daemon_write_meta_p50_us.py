"""A ``write_partition`` frame by phase, the daemon's side: the program's span
``daemon.write_partition.meta`` — from the frame's begin (its fixed header has
arrived) to its JSON header received and parsed — on the frames full tracing
samples (one in nine of a connection), median over the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.write_partition.meta")

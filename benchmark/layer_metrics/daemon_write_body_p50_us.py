"""A ``write_partition`` frame by phase: the program's span
``daemon.write_partition.body`` — the body received from the socket straight
into its extent of staging — on the sampled frames, median over the window,
us.  The only phase that grows with the frame's bytes."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.write_partition.body")

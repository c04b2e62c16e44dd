"""A ``write_partition`` frame by phase: the program's span
``daemon.write_partition.admit`` — the writer's partition stream found in the
handle tables and the body's extent reserved in staging under the store's
lock — on the sampled frames, median over the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.write_partition.admit")

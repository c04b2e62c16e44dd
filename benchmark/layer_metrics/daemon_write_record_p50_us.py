"""A ``write_partition`` frame by phase: the program's span
``daemon.write_partition.record`` — the received block recorded with the store
and the ack frame built, up to the start of its send — on the sampled frames,
median over the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.write_partition.record")

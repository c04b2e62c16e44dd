"""A ``fetch_block`` frame by phase: the program's span
``daemon.fetch_block.locate`` — the request read and parsed, the exchange at
the stage boundary where it falls, every block resolved to a view of the
received shards and the reply's prefix packed — every frame, median over the
window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.fetch_block.locate")

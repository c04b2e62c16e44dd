"""A ``fetch_block`` frame by phase: the program's span
``daemon.fetch_block.send`` — the vectored send of the reply over the blocks'
views, paced by the client's drain — every frame, median over the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.fetch_block.send")

"""Plan executor: the program's ``exchange.assemble`` span (one round's send
buffer built on the host: zeros, then one slot copy per executor), median
over the rounds and chunks of the window, ms."""

from benchmark.inner_spans import median_seconds


def read(run):
    value = median_seconds(run, "exchange.assemble")
    return None if value is None else value * 1e3

"""Plan executor: the program's ``exchange.h2d`` span (the two ``device_put``
calls of a round), median over the rounds and chunks of the window, ms.  It is
the time the calls hold the submit lane, not the DMA, which is asynchronous."""

from benchmark.inner_spans import median_seconds


def read(run):
    value = median_seconds(run, "exchange.h2d")
    return None if value is None else value * 1e3

"""Plan executor: the program's ``exchange.h2d.disk`` span (the ``device_put``
of a round whose source is a mapping of the store's disk tier; a child of
``exchange.h2d``), median over those rounds of the window, ms.  The time the
put holds the submit lane reading the file's pages, not the DMA; beside
``submit_h2d_ms_per_round``, which is over every round.  Left out where no
round of the window came from the disk tier."""

from benchmark.device_path import span_p50_us


def read(run):
    value = span_p50_us(run, "exchange.h2d.disk")
    return None if value is None else value / 1e3

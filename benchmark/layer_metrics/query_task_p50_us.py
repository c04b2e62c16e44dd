"""Query runner: the program's ``query.task`` span (one reduce task of a
batch-lane query: its ordered device reads located and dispatched, its
aggregate and join operators dispatched, and the wait for its few result
rows on the host), median over every task of the window, us.  Only a query
whose stages run on the device records it."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "query.task")

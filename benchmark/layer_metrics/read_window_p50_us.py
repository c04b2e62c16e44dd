"""Reduce-side read on the host: the program's span ``read.window`` — one fetch
window of a reduce task, from before its fetch is issued to its last block
handed back (at GroupByTest width a task is one window) — median over the
window's tasks, us.  Its three children below say whose the time is."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.window")

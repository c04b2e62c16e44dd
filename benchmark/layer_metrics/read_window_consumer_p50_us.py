"""Inside ``read.window``: the program's summed span ``read.window.consumer`` —
the sum of the turns of whoever drains ``read()`` (here the benchmark's
check of every record), one event a sampled window (one in five) — median over
the window, us.  Not the program's to shorten: ``read_window_p50_us`` less
this is."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.window.consumer")

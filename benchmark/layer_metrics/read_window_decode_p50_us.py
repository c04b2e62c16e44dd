"""Inside ``read.window``: the program's summed span ``read.window.decode`` —
the sum of the deserializer's turns over the window's records (the time
inside its ``next``), one event a sampled window (one in five) — median over
the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.window.decode")

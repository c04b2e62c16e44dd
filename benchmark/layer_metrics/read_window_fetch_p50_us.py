"""Inside ``read.window``: the program's span ``read.window.fetch`` — the
time the reading thread spent on the window's fetch: issued and awaited, the
copy out of the received shards — every window (one real interval, or the
sum of the two where a window is issued ahead of consumption); median over
the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "read.window.fetch")

"""Inside ``read.window``: the longest ``read.window.fetch`` span of each
timed job (those that begin inside its ``job.read``) — the hot window's fetch,
in process the copy of its blocks out of the received shards — median over
the jobs, us.  ``read_window_fetch_p50_us`` is the same span's median over
every window; the two part where the windows' bytes are unequal.  Left out
where no such span was recorded (an untraced run, a device read)."""

import bisect

from benchmark.measured import median


def read(run):
    fetches = sorted((t0, t1 - t0) for name, t0, t1 in run.program_spans if name == "read.window.fetch")
    if not fetches:
        return None
    starts = [t0 for t0, _ in fetches]
    longest = []
    for name, lo, hi in run.spans:
        if name == "job.read":
            inside = fetches[bisect.bisect_left(starts, lo) : bisect.bisect_right(starts, hi)]
            if inside:
                longest.append(max(ns for _, ns in inside))
    value = median(longest)
    return None if value is None else value / 1e3

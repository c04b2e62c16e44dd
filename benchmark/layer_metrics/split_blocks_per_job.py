"""Map-side write: the program's ``store.block_split`` spans (one a (map,
reduce) block longer than a peer region, staged as pieces in successive
staging rounds: from its first extent taken to its record) that begin inside
each timed job's ``job.write``, median over the jobs.  A program that records
``write.task`` records this span whenever a block is split, so a window with
tasks and none of them had none: ``0.0``; left out where the window has no
``write.task`` (an untraced run, a program before either span)."""

import bisect

from benchmark.measured import median

SPAN = "store.block_split"


def read(run):
    if not any(name == "write.task" for name, _, _ in run.program_spans):
        return None
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == SPAN)
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.write"
    ]
    return median(per_job) if per_job else 0.0

"""Map-side write: the program's ``store.round_buffer.fresh`` spans (one a
staging round buffer the store's free list did not have and ``np.zeros``
allocated: pages the copies then touch for the first time) that begin inside
each timed job's ``job.write``, median over the jobs.  0 where every round of
a job comes back from the free list.  A program that records ``write.task``
records this span whenever a buffer is fresh, so a window with tasks and none
of them had none: ``0.0``; left out where the window has no ``write.task``
(an untraced run, the parent)."""

import bisect

from benchmark.measured import median

SPAN = "store.round_buffer.fresh"


def read(run):
    if not any(name == "write.task" for name, _, _ in run.program_spans):
        return None
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == SPAN)
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.write"
    ]
    return median(per_job) if per_job else 0.0

"""Map-side write: the program's ``store.spill`` spans (one a staging round
that went to the disk tier: the RAM budget of round buffers was full) that
begin inside each timed job's ``job.write``, median over the jobs.  The
denominator of ``write_spill_s_per_job``, and the first thing that moves if
the budget's arithmetic changes.  Left out where the program's spans were not
recorded (an untraced run)."""

import bisect

from benchmark.inner_spans import recorded
from benchmark.measured import median


def read(run):
    if not recorded(run):
        return None
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == "store.spill")
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.write"
    ]
    return median(per_job) if per_job else 0.0

"""Seal and plan: the program's ``store.round_put`` spans (one a completed
staging round of a multi-round job that its store put on the device when the
round became final, before the shuffle's seal: the exchange then takes the
round from there and puts nothing for it) that begin inside each timed job's
``job.write``, median over the jobs.  Every store of the cell counts: 24 of a
job's 25 rounds in ``gbt25k-jobs-1chip`` (the live round is sealed as it
stands), four stores' rounds in the four-chip cells.  A count of the
program's own: 0 where the exchange put every round — a program without the
mechanism (the parent), a store's first job, a round buffer of fresh pages.
Left out where the program's spans were not recorded (an untraced run)."""

import bisect

from benchmark.inner_spans import recorded
from benchmark.measured import median


def read(run):
    if not recorded(run):
        return None
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == "store.round_put")
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.write"
    ]
    return median(per_job) if per_job else 0.0

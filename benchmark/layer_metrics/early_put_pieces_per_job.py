"""Seal and plan: the program's ``store.piece_put`` spans (one a piece of a
single staging round larger than one piece that was put on the device behind
the writers, before the shuffle's seal) that begin inside each timed job's
``job.write``, median over the jobs.  What the seal still puts
(``store.seal_put``) is the rest of the pieces the round's used rows reach: 47
in all in ``gbt25k-devfetch-1chip``, 39 in ``ts10gb-sortedjobs-1chip``.  A
count of the program's own: 0 where the seal put the whole round — a program
without the mechanism (the parent), a staging round of one piece (the CPU
rehearsal's).  Left out where the window sealed no single round onto a device
(no ``store.seal_put``: an untraced run, a multi-round or device-staged job)."""

import bisect

from benchmark.measured import median


def read(run):
    if not any(name == "store.seal_put" for name, _, _ in run.program_spans):
        return None
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == "store.piece_put")
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.write"
    ]
    return median(per_job) if per_job else None

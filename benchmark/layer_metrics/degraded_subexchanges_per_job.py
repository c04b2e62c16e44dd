"""Plan executor: the program's ``exchange.collective.degraded`` spans (one a
collective dispatched on the shrunk mesh: a pair of senders' wave and
consumers' wave of a re-run round that carries rows) that begin inside each
timed job's ``job.exchange``, median over the jobs.  A count that the
reference's ``loss_geometry`` predicts from the layout alone.  Left out where
no such span was recorded (an untraced run, a job that lost nothing)."""

import bisect

from benchmark.measured import median

SPAN = "exchange.collective.degraded"


def read(run):
    starts = sorted(t0 for name, t0, _ in run.program_spans if name == SPAN)
    if not starts:
        return None
    per_job = [
        bisect.bisect_right(starts, hi) - bisect.bisect_left(starts, lo)
        for name, lo, hi in run.spans if name == "job.exchange"
    ]
    return median(per_job) if per_job else 0.0

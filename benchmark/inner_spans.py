"""The program's spans inside the host intervals the benchmark times from
outside: ``store.rollover`` / ``store.spill`` inside ``job.write``,
``exchange.assemble`` / ``exchange.h2d`` inside ``exchange.pipeline.submit``,
``daemon.<op>`` inside a client's frame.  They are on ``run.program_spans``
(traced runs), on the clock of the benchmark's own spans.

A program that does not record them (an untraced run; a commit before they
existed) gives ``None`` from every function here, so the metric is left out of
the line.  One that does records ``exchange.assemble`` at least once a job, so
a window with that span and none of another kind had none to record: ``0.0``.
"""

from __future__ import annotations

from typing import Optional

from benchmark.measured import median

#: recorded once a round and chunk by every program that records any of them
MARKER = "exchange.assemble"


def recorded(run) -> bool:
    return any(name == MARKER for name, _, _ in run.program_spans)


def median_seconds(run, name: str) -> Optional[float]:
    """Median seconds of the program's spans of that name in the window."""
    if not recorded(run):
        return None
    found = [(t1 - t0) / 1e9 for n, t0, t1 in run.program_spans if n == name]
    return median(found) if found else 0.0


def seconds_inside_per_job(run, name: str, outer: str = "job.write") -> Optional[float]:
    """Seconds of the program's spans of that name that fall inside each of
    the benchmark's ``outer`` spans (one a job), median over the jobs."""
    if not recorded(run):
        return None
    inner = [(t0, t1) for n, t0, t1 in run.program_spans if n == name]
    per_job = [
        sum(max(0, min(t1, hi) - max(t0, lo)) for t0, t1 in inner) / 1e9
        for n, lo, hi in run.spans if n == outer
    ]
    return median(per_job) if per_job else 0.0

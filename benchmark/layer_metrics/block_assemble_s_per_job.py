"""Reduce-side read: seconds of the program's ``read.block_assemble`` spans
(one a block staged in pieces that a reader or the pull path is handed: the
one copy that puts it together from its pieces' views) inside each timed
job's ``job.read``, median over the jobs.  ``0.0`` where the window has
``read.window`` spans and no block was put together; left out where it has
none (an untraced run, a program before either span)."""

from benchmark.inner_spans import seconds_inside_per_job


def read(run):
    if not any(name == "read.window" for name, _, _ in run.program_spans):
        return None
    return seconds_inside_per_job(run, "read.block_assemble", outer="job.read")

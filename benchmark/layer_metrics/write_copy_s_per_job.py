"""Inside ``write.task``: seconds of the summed span ``write.task.copy`` — the
copies a map task's writer times itself, the block's chunks into staging above
all (what the ``store`` family's ``copy_ns`` counts) — inside each job's
``job.write``, median over the timed jobs.  ``write_s_per_job`` less this, the
lock's wait and the commit is the per-block Python.  Left out where no such
span was recorded (an untraced run, the parent, a write that copies nothing
on the host)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "write.task.copy", outer="job.write")

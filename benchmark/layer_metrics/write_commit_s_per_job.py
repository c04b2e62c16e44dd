"""Inside ``write.task``: seconds of ``write.task.commit`` — a map task's
commit from the entry of ``MapWriter.commit`` to the commit shipped and the
resolver told — inside each job's ``job.write``, median over the timed jobs.
Paid once a task whatever it wrote: a tenth of a second a job is nothing at
13 tasks and a large part of a job of 100.  Left out where no such span was
recorded (an untraced run, the parent)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "write.task.commit", outer="job.write")

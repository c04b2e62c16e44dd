"""Inside ``write.task``: seconds of the summed span ``write.task.lock_wait``
— what a map task's ``close_partition`` / ``reserve`` calls waited for the
store's one lock (the ``store`` family's ``lock_wait_ns``) — inside each job's
``job.write``, median over the timed jobs.  The cost of writing side by side:
near nothing from one writer, the other slots' copies and rollovers from four.
Left out where no such span was recorded (an untraced run, the parent)."""

from benchmark.device_path import span_seconds_per_job


def read(run):
    return span_seconds_per_job(run, "write.task.lock_wait", outer="job.write")

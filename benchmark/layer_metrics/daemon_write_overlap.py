"""Entry points: ``write_partition`` frames the daemon has in service at once
during a map stage — seconds of the program's ``daemon.write_partition`` spans
inside the timed jobs' ``job.write`` over the seconds of those ``job.write``
spans.  1.0 = the frames are served one after another, whatever the number of
connections; 4.0 = four task slots never wait for one another or for their
own Python.  Only a cell whose tasks run side by side lists it."""

from benchmark.task_overlap import overlap


def read(run):
    return overlap(run, "daemon.write_partition", "job.write")

"""Reduce-side read, ordered, from several task slots: tasks waiting for the
chip and the link at once — seconds of the program's ``read.ordered.d2h``
spans (the wait until a task's sorted records are host-readable: its gather
and sort on the device, then its one D2H) inside the timed jobs' ``job.read``
over the seconds of those ``job.read`` spans.  1.0 = as much waiting as the
stage is long; under it the stage is paced by something else; near the slots
every slot waits for the device path.  Only a cell whose tasks run side by
side lists it."""

from benchmark.task_overlap import overlap


def read(run):
    return overlap(run, "read.ordered.d2h", "job.read")

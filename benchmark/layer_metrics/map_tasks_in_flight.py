"""Map-side write from several task slots: map tasks the program has in
flight at once during a map stage — seconds of its ``write.task`` spans (a
committed map task, creation to commit shipped) inside the timed jobs'
``job.write`` over the seconds of those ``job.write`` spans.  1.0 = the tasks
run one after another; at most the slots.  Only a cell whose tasks run side by
side lists it."""

from benchmark.task_overlap import overlap


def read(run):
    return overlap(run, "write.task", "job.write")

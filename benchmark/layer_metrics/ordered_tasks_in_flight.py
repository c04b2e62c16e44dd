"""Reduce-side read, ordered, from several task slots: ordered reads the
program has open at once during a reduce stage — seconds of its
``read.ordered`` spans (a task's locate, gather and sort dispatches and, for
``read_batches()``, the wait for its D2H) inside the timed jobs' ``job.read``
over the seconds of those ``job.read`` spans.  1.0 = the program's part of the
tasks runs one after another, whatever the number of slots; at most the slots.
What is left under the slots is the consumer's own pass between two reads.
Only a cell whose tasks run side by side lists it."""

from benchmark.task_overlap import overlap


def read(run):
    return overlap(run, "read.ordered", "job.read")

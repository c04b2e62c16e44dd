"""Entry points, seen from the cluster: the share of the task slots' time in
which no task runs — what the stage barrier, the exchange at the boundary and
the one-task tail wave of a stage leave idle.  1 - seconds of the ``task.map``
and ``task.reduce`` spans over slots x job seconds, median over the timed
jobs, %.  On the slots' and the coordinator's clocks, not the program's."""

from benchmark.task_overlap import slot_idle_share


def read(run):
    return slot_idle_share(run)

"""Entry points: ``fetch_blocks`` frames the daemon has in service at once
during a reduce stage — seconds of the program's ``daemon.fetch_block`` spans
inside the timed jobs' ``job.read`` over the seconds of those ``job.read``
spans (the fetches that waited for the stage boundary's exchange lie in
``job.exchange``, before it).  Under 1.0 the slots spend most of a reduce task
decoding, not fetching."""

from benchmark.task_overlap import overlap


def read(run):
    return overlap(run, "daemon.fetch_block", "job.read")

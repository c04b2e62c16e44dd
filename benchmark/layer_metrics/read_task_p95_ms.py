"""Reduce-side read: one reduce task's whole read (reader opened to last record
consumed; on the daemon, fetch sent to last payload decoded), 95th percentile
over every reduce task of every timed job, ms.  Per layer and not end to end:
the tail sits where two populations of tasks meet, flips between them from run
to run, and so cannot carry a bound (PERF.md section 6)."""


def read(run):
    return run.read_task_ms(0.95)

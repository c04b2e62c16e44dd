"""Map-side write, from the inside: the program's span ``write.task`` — one
map task, from its writer's creation to the end of its commit (shipped, where
the entry point ships it) — median over the window's tasks, ms.
``write_s_per_job`` is the benchmark's span round a job's tasks; this times
one of them on the program's own clock marks.  Left out where the program
records no such span (an untraced run, a commit before it existed)."""

from benchmark.measured import median
from benchmark.spans import durations


def read(run):
    p50 = median(durations(run.program_spans, "write.task"))
    return None if p50 is None else p50 * 1e3

"""Map-side write: seconds of the program's ``store.block_split`` spans inside
each timed job's ``job.write``, median over the jobs: what the blocks longer
than a peer region — their pieces' copies and the rollovers between two
pieces — hold the writer.  ``0.0`` where the window has ``write.task`` spans
and no block was split; left out where it has none (an untraced run, a
program before either span)."""

from benchmark.inner_spans import seconds_inside_per_job


def read(run):
    if not any(name == "write.task" for name, _, _ in run.program_spans):
        return None
    return seconds_inside_per_job(run, "store.block_split")

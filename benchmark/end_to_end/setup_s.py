"""Process start to the first timed job: imports, native arena, records,
manager or daemon, the verified warm-up job."""


def read(run):
    return run.setup_s

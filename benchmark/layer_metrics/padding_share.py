"""Staged rows that were slot padding, as a share of all staged rows of the
window: the program's ``exchange.pipeline.drain`` counters, padded / (used +
padded), percent."""


def read(run):
    used, padded = run.stat_delta("used_rows"), run.stat_delta("padded_rows")
    if used + padded == 0:
        return None
    return 100.0 * padded / (used + padded)

"""The connection's wait for its client before a ``fetch_block`` frame: the
program's root span ``daemon.client_turn.fetch_block`` — from the end of the
frame before on the same connection to this frame's begin, on the daemon's
clock (the client slicing the reply before, the reduce task's check, the next
request built) — every frame, median over the window, us."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.client_turn.fetch_block")

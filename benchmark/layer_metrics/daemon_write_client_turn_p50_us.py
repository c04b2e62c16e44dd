"""The connection's wait for its client before a ``write_partition`` frame: the
program's root span ``daemon.client_turn.write_partition`` — from the end of
the frame before on the same connection to this frame's begin, on the
daemon's clock — on the sampled frames, median over the window, us.  From one
synchronous client it is the client's whole side of a frame and the kernel's:
``daemon_serve_p50_us`` plus this is about ``write_s_per_job`` over the job's
frames."""

from benchmark.device_path import span_p50_us


def read(run):
    return span_p50_us(run, "daemon.client_turn.write_partition")

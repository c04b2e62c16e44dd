"""Entry points: the share of the window's ``fetch_block`` replies whose body
did not cross the socket — the program's spans
``daemon.fetch_block.send.mapped`` (one a frame whose blocks the daemon copied
into the landing its same-host client offered, PR 60) over its spans
``daemon.fetch_block.send`` (one a frame, whichever way the reply went), in
percent.  0 from a program that serves every reply over the socket (the parent
of PR 60); ``None`` where the program's spans were not recorded or the window
served no fetch frame."""

from benchmark.inner_spans import recorded


def read(run):
    if not recorded(run):
        return None
    sends = sum(1 for name, _, _ in run.program_spans if name == "daemon.fetch_block.send")
    if not sends:
        return None
    mapped = sum(1 for name, _, _ in run.program_spans if name == "daemon.fetch_block.send.mapped")
    return 100.0 * mapped / sends

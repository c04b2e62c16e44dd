"""Readers' arithmetic for a cell whose tasks run side by side (the driver
``daemon-tasks``): how many of the daemon's frames are in service at once, and
how much of the slots' time no task fills.

The program's ``daemon.<op>`` spans are on ``run.program_spans`` (traced runs);
the driver's ``job.*``, ``task.*`` and ``job.slot`` spans on ``run.spans``, on
the same clock.  Where a span of either kind is absent (an untraced run, a
program or a driver that records none) a function here gives ``None`` and the
metric is left out of the line.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark.measured import median


def _inside(spans: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of the spans that fall inside [lo, hi]."""
    return sum(max(0, min(t1, hi) - max(t0, lo)) for t0, t1 in spans)


def overlap(run, inner: str, outer: str) -> Optional[float]:
    """Seconds of the program's ``inner`` spans inside the timed jobs' ``outer``
    spans over the seconds of those ``outer`` spans: the number of frames in
    service at once, 1.0 = one after another."""
    frames = [(t0, t1) for name, t0, t1 in run.program_spans if name == inner]
    stages = [(lo, hi) for name, lo, hi in run.spans if name == outer]
    total = sum(hi - lo for lo, hi in stages)
    if not frames or total <= 0:
        return None
    return sum(_inside(frames, lo, hi) for lo, hi in stages) / total


def slot_idle_share(run) -> Optional[float]:
    """1 - seconds of ``task.map`` + ``task.reduce`` over the slots' seconds
    (the ``job.slot`` spans: one a slot over the job's interval), of each
    timed job; the median, in percent."""
    tasks = [(t0, t1) for name, t0, t1 in run.spans if name in ("task.map", "task.reduce")]
    slots_of = {}
    for name, lo, hi in run.spans:
        if name == "job.slot" and hi > lo:
            slots_of[(lo, hi)] = slots_of.get((lo, hi), 0) + 1
    if not tasks or not slots_of:
        return None
    shares = [1.0 - _inside(tasks, lo, hi) / (count * (hi - lo)) for (lo, hi), count in slots_of.items()]
    return 100.0 * median(shares)

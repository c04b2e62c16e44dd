"""What the readers of the device write, the seal's put and the device read
share: the program's spans ``store.device_stage``, ``store.seal_put``,
``read.device`` and ``read.device.locate`` (on ``run.program_spans``, traced
runs), and the executables ``jit_block_scatter`` and ``jit_block_gather`` in
the profiler's trace of the traced job.

Only a shuffle staged or read on the device records any of them, so every
function here gives ``None`` where no span or module of the name asked for was
recorded: the metric is left out of the line, never reported as zero.
"""

from __future__ import annotations

from typing import Optional

from benchmark.inner_spans import seconds_inside_per_job
from benchmark.measured import median
from benchmark.peaks import exchange_min_seconds
from benchmark.spans import durations


def span_p50_us(run, name: str) -> Optional[float]:
    """Median of the program's spans of that name in the window, us."""
    p50 = median(durations(run.program_spans, name))
    return None if p50 is None else p50 * 1e6


def span_seconds_per_job(run, name: str, outer: str) -> Optional[float]:
    """``inner_spans.seconds_inside_per_job`` for a span that only some
    shuffles record: ``None`` where the window has none of that name."""
    if not any(n == name for n, _, _ in run.program_spans):
        return None
    return seconds_inside_per_job(run, name, outer=outer)


def block_kernel_roofline(run, module: str) -> Optional[float]:
    """The least time the chip could take to move the traced job's blocks once
    (``peaks.exchange_min_seconds`` on one chip: the staged rows that carried
    payload x the row's bytes, read from HBM and written back; the zero fill
    of the destination is in neither term) over the device time of the
    executables whose module name starts with ``module``, percent.  A block
    scatter or gather never leaves its chip, so HBM bandwidth bounds it on any
    number of chips; ``used_rows`` is all chips' and ``module_s`` their mean."""
    if run.reduction is None or not run.jobs:
        return None
    device_s = sum(s for name, s in run.reduction.module_s.items() if name.startswith(module))
    if device_s <= 0:
        return None
    used_rows = run.stat_delta("used_rows") / len(run.jobs)  # every job stages the same rows
    least = exchange_min_seconds(run.device_kind, 1, used_rows / run.chips, run.row_bytes)
    return 100.0 * least / device_s

"""What one run measured, as the metric readers see it.

A reader (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) is
``read(run) -> number or None`` over this object and nothing else; ``None``
means it found nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchmark.device_trace import Reduction, load_xplane, reduce_trace
from benchmark.jobs import JobResult
from benchmark.spans import Span, program_spans


def median(values) -> Optional[float]:
    ordered = sorted(values)
    if not ordered:
        return None
    mid = len(ordered) // 2
    return ordered[mid] if len(ordered) % 2 else (ordered[mid - 1] + ordered[mid]) / 2


def percentile(values, q: float) -> Optional[float]:
    """The q-quantile by the nearest rank of the sorted sample."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = -(-q * len(ordered) // 1)  # ceiling
    return ordered[int(max(1, min(len(ordered), rank))) - 1]


@dataclass
class Run:
    chips: int
    device_kind: str
    #: seconds from process start to the first timed job
    setup_s: float
    #: map-output bytes of one job
    job_bytes: int
    #: the timed jobs, in order
    jobs: List[JobResult]
    #: the benchmark's own spans (job.write / job.exchange / job.read)
    spans: List[Span]
    #: staging rounds of every timed job (``len(meta.recv_sizes)``)
    rounds: List[int]
    #: the program's StatsAggregator for exchange.pipeline.submit / .drain,
    #: at the window's start and end
    stats_before: dict
    stats_after: dict
    #: retried + failed-over + timed-out fetches of the timed jobs
    fetch_faults: int
    #: client-side nanoseconds of every daemon write_partition call (traced runs)
    frame_ns: List[int] = field(default_factory=list)
    #: bytes of one staged row (the store's block alignment)
    row_bytes: int = 512
    #: the program's own spans in the window (traced runs), and how many its ring dropped
    program_spans: List[Span] = field(default_factory=list)
    program_dropped: int = 0
    #: index of the traced job that was reduced, and the reduction of the
    #: profiler's trace over its interval
    traced_job: Optional[int] = None
    reduction: Optional[Reduction] = None
    trace_layout: Dict[str, Dict[str, int]] = field(default_factory=dict)

    def stat_delta(self, name: str) -> int:
        return self.stats_after[name] - self.stats_before[name]

    def read_task_ms(self, q: float) -> Optional[float]:
        """The q-quantile of one reduce task's whole read over every reduce
        task of every timed job, ms."""
        value = percentile([s for j in self.jobs for s in j.read_task_s], q)
        return None if value is None else value * 1e3


def build_run(cell, window, harness, kind: str, setup_s: float) -> Run:
    run = Run(
        chips=cell.chips,
        device_kind=kind,
        setup_s=setup_s,
        job_bytes=window.job_bytes,
        jobs=window.jobs,
        spans=window.spans,
        rounds=harness.rounds[1:],  # the first is the warm-up job's
        stats_before=harness.stats_before,
        stats_after=harness.stats_after,
        fetch_faults=sum(j.faults for j in window.jobs),
        frame_ns=window.frame_ns,
        row_bytes=harness.cluster.row_bytes,
        program_spans=program_spans(harness.program_events),
        program_dropped=harness.program_dropped,
        traced_job=window.traced_job,
    )
    if harness.xplane and window.traced_ns:
        trace = load_xplane(harness.xplane)
        lo, hi = window.traced_ns
        run.trace_layout = trace.layout
        run.reduction = reduce_trace(trace, lo, hi, run.spans + run.program_spans, cell.chips)
    return run

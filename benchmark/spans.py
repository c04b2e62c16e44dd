"""The benchmark's own host spans, and the attribution of idle time to them.

Spans are (name, start_ns, end_ns) on ``time.perf_counter_ns`` — the clock the
program's tracer uses too, and one clock for every process of a host, so the
client process's spans and the harness's line up without translation.
"""

from __future__ import annotations

import bisect
import heapq
import time
from contextlib import contextmanager
from typing import Dict, Iterable, List, Tuple

Span = Tuple[str, int, int]


class SpanLog:
    """Spans kept in memory until the run ends."""

    def __init__(self) -> None:
        self.spans: List[Span] = []

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter_ns()))


def durations(spans: Iterable[Span], name: str) -> List[float]:
    """Seconds of every span of that name, in the order recorded."""
    return [(t1 - t0) / 1e9 for n, t0, t1 in spans if n == name]


def program_spans(events: Iterable[dict]) -> List[Span]:
    """Complete ("X") events of the program's tracer as spans on the same
    clock (its ``ts``/``dur`` are microseconds of ``perf_counter_ns``)."""
    out = []
    for ev in events:
        if ev.get("ph") == "X":
            t0 = int(ev["ts"] * 1e3)
            out.append((ev["name"], t0, t0 + int(ev["dur"] * 1e3)))
    return out


def innermost(spans: Iterable[Span]) -> List[Span]:
    """The spans flattened into disjoint segments, each named for the
    innermost span open in it: of the spans open at an instant, the one
    opened last.  Time under no span has no segment."""
    pending = sorted((t0, t1, name) for name, t0, t1 in spans if t1 > t0)
    segments: List[Span] = []
    open_heap: List[Tuple[int, int, str]] = []  # (-start, end, name): latest start first
    i = 0
    t = pending[0][0] if pending else 0
    while i < len(pending) or open_heap:
        while i < len(pending) and pending[i][0] <= t:
            s0, s1, name = pending[i]
            heapq.heappush(open_heap, (-s0, s1, name))
            i += 1
        while open_heap and open_heap[0][1] <= t:
            heapq.heappop(open_heap)
        if not open_heap:
            if i == len(pending):
                break
            t = pending[i][0]
            continue
        nxt = open_heap[0][1]
        if i < len(pending):
            nxt = min(nxt, pending[i][0])
        name = open_heap[0][2]
        if segments and segments[-1][0] == name and segments[-1][2] == t:
            segments[-1] = (name, segments[-1][1], nxt)
        else:
            segments.append((name, t, nxt))
        t = nxt
    return segments


def attribute(gaps: Iterable[Tuple[int, int]], segments: List[Span]) -> Dict[str, float]:
    """Seconds of the ``gaps`` (start_ns, end_ns intervals) by the segment of
    ``innermost(spans)`` they fall in.  Time under no span goes to
    ``(no span)``."""
    starts = [s[1] for s in segments]
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        covered = 0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][1] < g1:
            name, s0, s1 = segments[i]
            overlap = min(s1, g1) - max(s0, g0)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap / 1e9
                covered += overlap
            i += 1
        if g1 - g0 > covered:
            out["(no span)"] = out.get("(no span)", 0.0) + (g1 - g0 - covered) / 1e9
    return out

"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

Read with nothing but JAX (``jax.profiler.ProfileData``).  A device plane is
one chip (``/device:TPU:<n>``); its ``XLA Ops`` line holds one event per
operation run on the chip, its ``XLA Modules`` line one per executable.  The
trace's clock starts at the session; ``SYNC_NAME`` is a host annotation the
harness writes with its own ``perf_counter_ns`` as a stat, which puts device
events on the clock of the benchmark's and the program's host spans.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.spans import Span, attribute, innermost

SYNC_NAME = "bench.clock_sync"
SYNC_STAT = "perf_counter_ns"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"\}?\s([a-z][a-z0-9\-]*)\(")


def short_op_name(text: str) -> str:
    """The trace names an operation by its whole HLO line.  Keep the result's
    name, the operation's kind and a custom call's target:
    ``%_unknown_.1 custom-call tpu_custom_call``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    kind = _KIND.search(rest)
    target = _TARGET.search(rest)
    parts = [name, kind.group(1) if kind else "", target.group(1) if target else ""]
    return " ".join(p for p in parts if p)[:120]


@dataclass
class DeviceTrace:
    """Operation and module events per device plane, on the host's
    ``perf_counter_ns`` clock once ``sync`` is known."""

    ops: Dict[str, List[Span]] = field(default_factory=dict)
    modules: Dict[str, List[Span]] = field(default_factory=dict)
    #: planes and their lines with event counts, for a reader of the log
    layout: Dict[str, Dict[str, int]] = field(default_factory=dict)


def load_xplane(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw_ops: Dict[str, List[Span]] = {}
    raw_modules: Dict[str, List[Span]] = {}
    layout: Dict[str, Dict[str, int]] = {}
    sync: Optional[Tuple[float, int]] = None
    for plane in data.planes:
        lines = layout.setdefault(plane.name, {})
        device = _DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            count = 0
            for ev in line.events:
                count += 1
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    dst = raw_ops if line.name == OPS_LINE else raw_modules
                    t0 = ev.start_ns
                    name = short_op_name(ev.name) if line.name == OPS_LINE else ev.name
                    dst.setdefault(plane.name, []).append((name, t0, t0 + ev.duration_ns))
                elif not device and ev.name == SYNC_NAME and sync is None:
                    stats = dict(ev.stats)
                    if SYNC_STAT in stats:
                        sync = (ev.start_ns, int(stats[SYNC_STAT]))
            lines[line.name] = count
    if sync is None:
        raise ValueError(f"no {SYNC_NAME!r} annotation in {path}: cannot place the trace on the host clock")
    shift = sync[1] - sync[0]

    def on_host_clock(events: Dict[str, List[Span]]) -> Dict[str, List[Span]]:
        return {
            plane: sorted(((n, int(t0 + shift), int(t1 + shift)) for n, t0, t1 in evs), key=lambda e: e[1])
            for plane, evs in events.items()
        }

    return DeviceTrace(on_host_clock(raw_ops), on_host_clock(raw_modules), layout)


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The intervals clipped to [lo, hi) and merged where they touch."""
    merged: List[Interval] = []
    for t0, t1 in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if t1 <= t0:
            continue
        if merged and t0 <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], t1))
        else:
            merged.append((t0, t1))
    return merged


def complement(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    gaps, t = [], lo
    for t0, t1 in busy:
        if t0 > t:
            gaps.append((t, t0))
        t = t1
    if hi > t:
        gaps.append((t, hi))
    return gaps


@dataclass
class Reduction:
    """One traced interval reduced; seconds are means over the devices."""

    window_s: float
    busy_s: float
    idle_share: float
    #: operations by the time they took, most first: [name, seconds]
    device_ops: List[Tuple[str, float]]
    #: idle seconds by the innermost host span open at the time, most first
    idle_gaps: List[Tuple[str, float]]
    #: seconds of every module (executable) by name
    module_s: Dict[str, float]
    devices: int
    #: chips that have a plane in the trace; 0 = the trace saw no device
    planes: int


def reduce_trace(trace: DeviceTrace, lo: int, hi: int, host_spans: Iterable[Span],
                 devices: int, top: int = 10) -> Reduction:
    """Busy union, idle share, idle time by host span and the top operations
    of [lo, hi) (``perf_counter_ns``), over ``devices`` chips.  A chip with no
    plane in the trace ran nothing and counts as idle throughout."""
    if hi <= lo:
        raise ValueError("empty traced interval")
    segments = innermost(host_spans)
    window = hi - lo
    busy_ns = 0
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    planes = sorted(trace.ops)
    for plane in planes:
        busy = union(((t0, t1) for _, t0, t1 in trace.ops[plane]), lo, hi)
        busy_ns += sum(t1 - t0 for t0, t1 in busy)
        for name, seconds in attribute(complement(busy, lo, hi), segments).items():
            gap_s[name] = gap_s.get(name, 0.0) + seconds
        for name, t0, t1 in trace.ops[plane]:
            clipped = min(t1, hi) - max(t0, lo)
            if clipped > 0:
                op_s[name] = op_s.get(name, 0.0) + clipped / 1e9
    devices = max(devices, len(planes))
    for name, seconds in attribute([(lo, hi)] * (devices - len(planes)), segments).items():
        gap_s[name] = gap_s.get(name, 0.0) + seconds
    module_s: Dict[str, float] = {}
    for evs in trace.modules.values():
        for name, t0, t1 in evs:
            clipped = min(t1, hi) - max(t0, lo)
            if clipped > 0:
                module_s[name] = module_s.get(name, 0.0) + clipped / 1e9 / devices

    def ranked(table: Dict[str, float]) -> List[Tuple[str, float]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [(name, seconds / devices) for name, seconds in rows]

    busy_s = busy_ns / 1e9 / devices
    return Reduction(
        window_s=window / 1e9,
        busy_s=busy_s,
        idle_share=1.0 - busy_s / (window / 1e9),
        device_ops=ranked(op_s),
        idle_gaps=ranked(gap_s),
        module_s=module_s,
        devices=devices,
        planes=len(planes),
    )

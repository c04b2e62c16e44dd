"""Span tracer with chrome://tracing export — an aux subsystem the reference
lacks entirely (SURVEY.md section 5.1: "No tracer"; it has only per-op nanoTime
deltas in debug logs, UcxWorkerWrapper.scala:388-390).

Usage::

    from sparkucx_tpu.utils.trace import TRACER, span

    with span("exchange.superstep", shuffle_id=0):
        ...
    TRACER.export("/tmp/shuffle_trace.json")   # open in chrome://tracing / Perfetto

Disabled by default: every ``span`` is a no-op unless the tracer is enabled
(constructor, ``TRACER.enable()``, or the ``SPARKUCX_TPU_TRACE`` env var, whose
value — if not "1" — is a path auto-exported at interpreter exit).  Events are
"X" (complete) events with thread/process ids, so concurrent mapper threads,
server threads, and the collective lane out per-track in the viewer.

The obs plane (PR 14) grew this into a distributed tracer:

* Every span carries real ids — ``trace_id`` (the root fetch/superstep that
  started the causal chain), ``span_id`` (this span), ``parent_id`` (the
  enclosing span, possibly on ANOTHER executor when the context arrived over
  the wire as a FetchBlockReq/ReplicaPut trace extension).  Ids ride as
  top-level event fields so the ``args`` shape stays what it always was.
* Storage is a bounded ring (``capacity`` events, drop-oldest) with a dropped
  counter — the flight recorder.  ``recording`` keeps the ring warm even when
  full tracing is off, so a postmortem bundle always has a trace tail;
  ``enabled`` additionally lights up the env-var export path.  Both off means
  the module-level ``span()`` returns a shared no-op — no dict build, no
  generator frame — the hot submit lane's fast path.  A cluster's
  ``FlightRecorder`` turns ``recording`` on, so in a served process every
  ``span()`` is live whether or not anyone traces: sites that fire once a
  block or frame check ``TRACER.enabled`` themselves (a span under full
  tracing only, as the daemon's ``daemon.<op>``) or keep plain counters.
* ``current_context()`` exposes the innermost open span for wire pickup and
  ``activate()``/``remote_context()`` re-parent server-side work under it.

Marks to spans (PR 36): a site on a hot interval — a daemon frame, a reduce
task's fetch window — does not open a child span a phase.  It reads
``perf_counter_ns()`` at the phase boundaries while the interval runs and,
after its own span has closed, hands the ``(name, t0_ns, t1_ns)`` triples and
the parent's ``SpanCtx`` to ``Tracer.record_spans``: one call, one take of the
ring's lock, one ordinary "X" event a triple (``trace_id`` the parent's,
``parent_id`` its ``span_id``; a root each with ``parent=None``).  The events
come from the builder every span's event comes from (``_event``), so export,
``merge_events``, TRACE_PULL and the flight recorder cannot tell them apart.
A mark may carry its own ``args`` and marks of its own (PR 50): an interval
that was never a span — a map task, ``write.task`` — goes to the ring with its
phases under it in the same call.
A **summed span** is such a triple whose turns interleave with another's
record by record (a decoder against its consumer): ``t1 - t0`` is the SUM of
its turns and ``t0`` lays it end to end after the parent's last real child, so
children never overlap and time by name adds up; its ``args`` carry ``turns``.
Cost (``PERF.md`` section 6, PR 36): the process id is read once a process (and
again in a forked child), not three to four system calls a span; the
thread-local stack and executor id are plain attributes; ``span()`` is a
slotted context manager, not a generator.
"""

from __future__ import annotations

import atexit
import ctypes
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterable, List, Optional, Tuple

#: Flight-recorder ring default: bounded so long-running tracing can't OOM an
#: executor (conf ``obs.ringCapacity`` overrides per cluster).
DEFAULT_RING_CAPACITY = 8192

#: Process-scoped id generator: the pid in the top bits keeps ids distinct
#: across daemon worker processes, the counter keeps them distinct in-process
#: (the loopback cluster shares one TRACER across every virtual executor).
#: ``os.getpid()`` is a system call and a span read it three to four times;
#: the pid changes only at fork, where ``_read_pid`` runs again in the child
_PID = 0


def _read_pid() -> None:
    """Cache the pid and start the id counter under it: ``_new_id`` is the
    counter's own ``__next__``, no Python frame an id."""
    global _PID, _new_id
    _PID = os.getpid()
    _new_id = itertools.count(((_PID & 0xFFFF) << 48) | 1).__next__


_read_pid()
os.register_at_fork(after_in_child=_read_pid)

#: ``PyObject_GC_UnTrack`` of the C API (CPython; None elsewhere): takes an
#: object out of the cycle collector's lists.  An event that holds an ``args``
#: dict is a dict holding a dict, which the collector tracks for good and walks
#: at every full collection — 250 ns an event, and a traced window's ring holds
#: some 300,000: a traced 1k job's write grew by a quarter over its window
#: while the ring filled (``PERF.md`` section 6, PR 50).  An event cannot be
#: part of a cycle (nothing it holds refers back to it) and is freed by its
#: reference count when the ring drops it, so ``_event`` untracks it; one
#: without ``args`` holds atoms only and was never tracked.
try:
    _gc_untrack = ctypes.pythonapi.PyObject_GC_UnTrack
    _gc_untrack.argtypes, _gc_untrack.restype = [ctypes.py_object], None
except AttributeError:  # pragma: no cover - not CPython: events stay tracked
    _gc_untrack = None


@dataclass(slots=True)
class SpanCtx:
    """An open span's identity — what travels over the wire and what children
    parent under.  ``trace_id`` names the causal chain, ``span_id`` this span,
    ``parent_id`` the enclosing span (0 = root)."""

    trace_id: int
    span_id: int
    parent_id: int = 0
    name: str = ""
    category: str = "shuffle"
    t0: int = 0  # perf_counter_ns at open; 0 for remote/synthetic contexts
    args: Dict[str, object] = field(default_factory=dict)
    #: perf_counter_ns at close, 0 while open: with ``t0`` the bounds of the
    #: recorded event, for a site that lays marks inside them (``record_spans``)
    t1: int = 0


class _NoopSpan:
    """Shared do-nothing context manager returned by the module-level
    ``span()`` when tracing AND recording are both off: a plain object with
    empty ``__enter__``/``__exit__`` beats entering a generator-backed
    contextmanager by an order of magnitude on the hot submit lane."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


class _LiveSpan:
    """What ``Tracer.span`` returns while the tracer is active: the span is
    on its thread's stack between ``__enter__`` and ``__exit__`` and recorded
    at exit, exception or not.  Made with the tracer's own ``_stack`` and
    ``_record_span``, bound."""

    __slots__ = ("_stack_of", "_record", "_ctx", "_stack")

    def __init__(self, stack_of, record, ctx: SpanCtx) -> None:
        self._stack_of = stack_of
        self._record = record
        self._ctx = ctx

    def __enter__(self) -> SpanCtx:
        self._stack = self._stack_of()  # the entering thread's
        self._stack.append(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> bool:
        ctx = self._ctx
        self._stack.pop()
        ctx.t1 = time.perf_counter_ns()
        self._record(ctx, ctx.t1 - ctx.t0)
        return False


class _Tls(threading.local):
    """Per-thread span stack and executor id.  Class-level defaults: reading
    an attribute a thread never set is a plain lookup, not a caught
    ``AttributeError``."""

    stack: Optional[List[SpanCtx]] = None
    eid: Optional[int] = None


def _event(
    name: str, category: str, t0_ns: int, dur_ns: int, tid: int,
    trace_id: int, span_id: int, parent_id: int, args, eid,
) -> dict:
    """The one builder of a complete ("X") event: ``_record_span`` and
    ``record_spans`` both end here, so a bulk child has a span's shape."""
    ev = {
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": t0_ns / 1e3,  # microseconds, the chrome trace unit
        "dur": dur_ns / 1e3,
        "pid": _PID,
        "tid": tid,
        "uid": _new_id(),
        "trace_id": trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
    }
    if args:
        ev["args"] = args
        if _gc_untrack is not None:
            _gc_untrack(ev)  # what is stored after this is an atom
    if eid is not None:
        ev["eid"] = eid
    return ev


def _lay(out: List[dict], marks, category, args, tid, eid, trace_id: int, parent_id: int) -> None:
    """``record_spans``: one event a mark onto ``out``, a mark's own marks
    under it.  A function of the module, not a closure of the call: one that
    calls itself would be a cycle holding the call's events until the
    collector ran."""
    for mark in marks:
        trace, span_id = trace_id or _new_id(), _new_id()
        out.append(_event(
            mark[0], category, mark[1], mark[2] - mark[1], tid, trace, span_id, parent_id,
            mark[3] if len(mark) > 3 else args, eid,
        ))
        if len(mark) > 4:
            _lay(out, mark[4], category, args, tid, eid, trace, span_id)


class Tracer:
    def __init__(
        self,
        enabled: bool = False,
        recording: bool = False,
        capacity: int = DEFAULT_RING_CAPACITY,
    ) -> None:
        self.enabled = enabled
        #: flight recorder: keep the ring warm without full tracing on
        self.recording = recording
        self._events: Deque[dict] = deque(maxlen=max(1, int(capacity)))  #: guarded by self._lock
        self._dropped = 0  #: guarded by self._lock
        self._lock = threading.Lock()
        self._tls = _Tls()

    # -- switches ----------------------------------------------------------

    @property
    def active(self) -> bool:
        """Anything to do at all?  False = the no-op fast path."""
        return self.enabled or self.recording

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def set_capacity(self, capacity: int) -> None:
        """Resize the flight-recorder ring, keeping the newest events."""
        with self._lock:
            self._events = deque(self._events, maxlen=max(1, int(capacity)))

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    @property
    def dropped(self) -> int:
        """Events evicted from the ring since the last clear()."""
        with self._lock:
            return self._dropped

    # -- thread-local span stack / scopes ----------------------------------

    def _stack(self) -> List[SpanCtx]:
        st = self._tls.stack
        if st is None:
            st = self._tls.stack = []
        return st

    def current_context(self) -> Optional[SpanCtx]:
        """The innermost open span on THIS thread — what a transport packs
        into the wire trace extension.  None when no span is open."""
        st = self._tls.stack
        return st[-1] if st else None

    @contextmanager
    def executor_scope(self, executor_id: Optional[int]):
        """Attribute events on this thread to a virtual executor — the
        loopback cluster runs every executor in one process, so pid alone
        can't tell their tracks apart; ``export_merged`` maps eid -> pid."""
        prev = self._tls.eid
        self._tls.eid = executor_id
        try:
            yield
        finally:
            self._tls.eid = prev

    @contextmanager
    def activate(self, ctx: Optional[SpanCtx]):
        """Make ``ctx`` the parent for spans opened on this thread — used to
        re-parent pipelined-window awaits and server-side serve spans under
        a span opened elsewhere (another thread, or another executor via
        ``remote_context``).  No event is recorded for ``ctx`` itself."""
        if ctx is None or not self.active:
            yield
            return
        st = self._stack()
        st.append(ctx)
        try:
            yield
        finally:
            st.pop()

    @staticmethod
    def remote_context(trace_id: int, span_id: int) -> SpanCtx:
        """A synthetic ctx for a span open on ANOTHER executor (arrived as a
        wire trace extension); activate() it to parent local spans there."""
        return SpanCtx(trace_id=trace_id, span_id=span_id, name="<remote>")

    # -- span lifecycle ----------------------------------------------------

    def start_span(self, name: str, category: str = "shuffle", **args) -> Optional[SpanCtx]:
        """Open a span WITHOUT entering it on the thread-local stack — the
        explicit half of the API for spans whose open and close straddle
        threads or interleave (pipelined fetch windows).  Pair with
        ``end_span``; parent under it elsewhere via ``activate``."""
        if not (self.enabled or self.recording):
            return None
        st = self._tls.stack
        parent = st[-1] if st else None
        return SpanCtx(
            parent.trace_id if parent else _new_id(),
            _new_id(),
            parent.span_id if parent else 0,
            name,
            category,
            time.perf_counter_ns(),
            {k: jsonable(v) for k, v in args.items()} if args else {},
        )

    def end_span(self, ctx: Optional[SpanCtx], **extra_args) -> None:
        if ctx is None or not (self.enabled or self.recording):
            return
        if extra_args:
            ctx.args.update({k: jsonable(v) for k, v in extra_args.items()})
        ctx.t1 = time.perf_counter_ns()
        self._record_span(ctx, ctx.t1 - ctx.t0)

    def span(self, name: str, category: str = "shuffle", **args):
        """Time a region; nested spans nest in the viewer (same tid).  A
        context manager whose ``as`` target is the open ``SpanCtx`` (None
        while the tracer is inactive)."""
        if not (self.enabled or self.recording):
            return _NOOP_SPAN
        return _LiveSpan(self._stack, self._record_span, self.start_span(name, category, **args))

    def _record_span(self, ctx: SpanCtx, dur_ns: int) -> None:
        self._append(_event(
            ctx.name, ctx.category, ctx.t0, dur_ns, threading.get_ident() & 0xFFFFFFFF,
            ctx.trace_id, ctx.span_id, ctx.parent_id, ctx.args, self._tls.eid,
        ))

    def record_spans(
        self,
        parent: Optional[SpanCtx],
        marks: Iterable[tuple],
        category: str = "shuffle",
        args: Optional[Dict[str, object]] = None,
    ) -> None:
        """Marks to spans: one complete event a ``(name, t0_ns, t1_ns)``
        triple of ``perf_counter_ns`` readings the caller took itself, each a
        child of ``parent`` (a span that may have closed already; ``None``: a
        root each, a trace of its own), all appended under ONE take of the
        ring's lock.  For the phases of an interval too short to open a span
        a phase (module docstring); ``args`` — e.g. a summed span's ``turns`` —
        go on every event of the call.  A mark may be longer than a triple:
        ``(name, t0_ns, t1_ns, own_args)`` carries its own ``args`` in place
        of the call's, and ``(name, t0_ns, t1_ns, own_args, children)`` is an
        interval that is itself made from marks, the parent of the marks in
        ``children`` (a map task with its phases: still one call, one take of
        the lock).  The caller checks ``enabled`` or ``active`` as its parent
        span's site does; nothing is checked here."""
        new: List[dict] = []
        trace_id, parent_id = (parent.trace_id, parent.span_id) if parent is not None else (0, 0)
        _lay(new, marks, category, args, threading.get_ident() & 0xFFFFFFFF, self._tls.eid, trace_id, parent_id)
        with self._lock:
            events = self._events
            over = len(events) + len(new) - events.maxlen
            if over > 0:
                self._dropped += over  # ring full: deque drops the oldest
            events.extend(new)

    def instant(self, name: str, category: str = "shuffle", **args) -> None:
        """Zero-duration marker (commits, failures, retries)."""
        if not self.active:
            return
        parent = self.current_context()
        ev = {
            "name": name,
            "cat": category,
            "ph": "i",
            "s": "t",
            "ts": time.perf_counter_ns() / 1e3,
            "pid": _PID,
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "uid": _new_id(),
            "trace_id": parent.trace_id if parent else 0,
            "span_id": _new_id(),
            "parent_id": parent.span_id if parent else 0,
        }
        if args:
            ev["args"] = {k: jsonable(v) for k, v in args.items()}
        eid = self._tls.eid
        if eid is not None:
            ev["eid"] = eid
        self._append(ev)

    def _append(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1  # ring full: deque drops the oldest
            self._events.append(ev)

    # -- export ------------------------------------------------------------

    @property
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def tail(self, n: int) -> List[dict]:
        """The newest ``n`` ring events without copying the whole ring —
        the flight recorder's capture path runs on error paths and must
        stay cheap even with a full ring."""
        with self._lock:
            if n >= len(self._events):
                return list(self._events)
            out = list(itertools.islice(reversed(self._events), n))
        out.reverse()
        return out

    def export(self, path: str) -> int:
        """Write the chrome trace file; returns the event count."""
        events = self.events
        with open(path, "w") as f:
            f.write(json.dumps({"traceEvents": events, "displayTimeUnit": "ms"}))
        return len(events)


def merge_events(buffers: List[List[dict]]) -> List[dict]:
    """Merge per-executor event buffers into one Perfetto-ready list.

    Events that carry an ``eid`` (executor scope) get ``pid = eid`` so every
    executor lands on its own process track in the viewer; duplicates are
    dropped by event ``uid`` (the loopback cluster shares one TRACER across
    executors, so a TRACE_PULL sweep returns overlapping views)."""
    seen = set()
    merged: List[dict] = []
    for buf in buffers:
        for ev in buf:
            uid = ev.get("uid")
            if uid is not None:
                if uid in seen:
                    continue
                seen.add(uid)
            ev = dict(ev)
            if ev.get("eid") is not None:
                ev["pid"] = ev["eid"]
            merged.append(ev)
    merged.sort(key=lambda e: e.get("ts", 0))
    return merged


def jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def _from_env() -> "Tracer":
    flag = os.environ.get("SPARKUCX_TPU_TRACE", "")
    t = Tracer(enabled=bool(flag))
    if flag and flag != "1":
        atexit.register(lambda: t.events and t.export(flag))
    return t


#: Process-wide default tracer (env-gated); libraries call ``span(...)``.
TRACER = _from_env()


def span(name: str, category: str = "shuffle", **args):
    if not TRACER.active:  # hot-path guard: no kwargs dict churn, no generator
        return _NOOP_SPAN
    return TRACER.span(name, category=category, **args)


def instant(name: str, category: str = "shuffle", **args) -> None:
    if not TRACER.active:
        return
    TRACER.instant(name, category=category, **args)

"""Span tracer (utils/trace.py) — chrome-trace export and hot-path wiring.
An aux subsystem with no reference counterpart (SURVEY.md section 5.1)."""

import json
import threading

import numpy as np
import pytest

from sparkucx_tpu.utils import trace as trace_mod
from sparkucx_tpu.utils.trace import Tracer


class TestTracer:
    def test_disabled_records_nothing(self):
        t = Tracer(enabled=False)
        with t.span("x"):
            pass
        t.instant("y")
        assert t.events == []

    def test_span_event_shape(self):
        t = Tracer(enabled=True)
        with t.span("exchange.superstep", shuffle_id=3):
            pass
        [ev] = t.events
        assert ev["name"] == "exchange.superstep" and ev["ph"] == "X"
        assert ev["dur"] >= 0 and ev["args"] == {"shuffle_id": 3}

    def test_nested_and_exception_spans(self):
        t = Tracer(enabled=True)
        with pytest.raises(ValueError):
            with t.span("outer"):
                with t.span("inner"):
                    raise ValueError("boom")
        names = [e["name"] for e in t.events]
        assert names == ["inner", "outer"]  # closed innermost-first, both recorded

    def test_export_valid_chrome_trace(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("a"):
            t.instant("marker", category="debug", extra=object())
        path = tmp_path / "trace.json"
        n = t.export(str(path))
        doc = json.loads(path.read_text())
        assert n == 2 and len(doc["traceEvents"]) == 2
        marker = next(e for e in doc["traceEvents"] if e["ph"] == "i")
        assert isinstance(marker["args"]["extra"], str)  # non-JSON values stringified

    def test_thread_ids_distinguish_tracks(self):
        t = Tracer(enabled=True)

        def work():
            with t.span("w"):
                pass

        th = threading.Thread(target=work)
        th.start()
        th.join()
        with t.span("main"):
            pass
        tids = {e["tid"] for e in t.events}
        assert len(tids) == 2


class _CountingLock:
    """A lock that counts its takes (the ring's lock, for the bulk path)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.takes = 0

    def __enter__(self):
        self._lock.acquire()
        self.takes += 1

    def __exit__(self, *exc):
        self._lock.release()
        return False


class TestMarksToSpans:
    """``Tracer.record_spans``: clock marks a site took itself, appended as
    ordinary complete events under one take of the ring's lock."""

    @staticmethod
    def parent_and_children(t, eid=None):
        from time import perf_counter_ns

        with t.executor_scope(eid):
            with t.span("frame", shuffle_id=4) as ctx:
                cuts = [ctx.t0] + [perf_counter_ns() for _ in range(3)]
            cuts.append(ctx.t1)
            t.record_spans(ctx, zip(("frame.a", "frame.b", "frame.c", "frame.d"), cuts, cuts[1:]))
        return ctx, cuts

    def test_bulk_events_have_a_spans_shape(self):
        t = Tracer(enabled=True)
        self.parent_and_children(t, eid=3)
        parent, *children = t.events
        assert parent["name"] == "frame" and [c["name"] for c in children] == [
            "frame.a", "frame.b", "frame.c", "frame.d"]
        for child in children:
            # the keys ``_record_span`` writes, less the args this call gave none of
            assert set(child) == set(parent) - {"args"}
            assert all(type(child[k]) is type(parent[k]) for k in child)
            assert child["ph"] == "X" and child["cat"] == parent["cat"]
            assert (child["pid"], child["tid"], child["eid"]) == (parent["pid"], parent["tid"], 3)
        ids = [e["span_id"] for e in t.events] + [e["uid"] for e in t.events]
        assert len(set(ids)) == len(ids)

    def test_children_are_parented_and_partition_their_parent(self):
        t = Tracer(enabled=True)
        ctx, cuts = self.parent_and_children(t)
        parent, *children = t.events
        assert ctx.t1 and ctx.t1 - ctx.t0 == round(parent["dur"] * 1e3)  # the event's own bounds
        for child in children:
            assert (child["trace_id"], child["parent_id"]) == (parent["trace_id"], parent["span_id"])
        # nested in the viewer: same track, inside the parent, end to end
        assert children[0]["ts"] == parent["ts"]
        for a, b, lo, hi in zip(children, children[1:], cuts[1:], cuts[2:]):
            assert round((a["ts"] + a["dur"]) * 1e3) == round(b["ts"] * 1e3) == lo
        last = children[-1]
        assert round((last["ts"] + last["dur"]) * 1e3) == round((parent["ts"] + parent["dur"]) * 1e3)
        assert abs(sum(c["dur"] for c in children) - parent["dur"]) < 1e-3  # us

    def test_one_take_of_the_rings_lock_a_call(self):
        t = Tracer(enabled=True)
        t._lock = _CountingLock()
        ctx = t.start_span("frame")
        t.end_span(ctx)
        assert t._lock.takes == 1
        t.record_spans(ctx, [("frame.%d" % i, ctx.t0 + i, ctx.t0 + i + 1) for i in range(6)])
        assert t._lock.takes == 2 and len(t._events) == 7

    def test_no_parent_makes_a_root_each(self):
        t = Tracer(enabled=True)
        t.record_spans(None, [("turn", 1000, 3000), ("turn", 5000, 6000)])
        a, b = t.events
        assert a["parent_id"] == b["parent_id"] == 0
        assert a["trace_id"] != b["trace_id"] and (a["ts"], a["dur"]) == (1.0, 2.0)

    def test_args_go_on_every_event_of_the_call(self):
        t = Tracer(enabled=True)
        ctx = t.start_span("window")
        t.end_span(ctx)
        t.record_spans(ctx, [("window.decode", 10, 20), ("window.consumer", 20, 50)], args={"turns": 7})
        assert [e.get("args") for e in t.events] == [None, {"turns": 7}, {"turns": 7}]

    def test_a_mark_may_carry_its_own_args_and_marks_of_its_own(self):
        """PR 50: an interval that was never a span goes to the ring with its
        phases under it, in one call — ids and the trace run down the tree, a
        mark's own args take the place of the call's."""
        t = Tracer(enabled=True)
        phases = [("block.admit", 12, 13), ("block.copy", 13, 19)]
        task = ("task", 0, 100, {"map_id": 4}, [
            ("task.copy", 0, 30, {"turns": 9}), ("block", 10, 20, None, phases), ("task.commit", 90, 100)])
        t.record_spans(None, [task, ("turn", 200, 300)], args={"of": "the call"})
        by_name = {e["name"]: e for e in t.events}
        assert [e["name"] for e in t.events] == [
            "task", "task.copy", "block", "block.admit", "block.copy", "task.commit", "turn"]
        root = by_name["task"]
        assert root["parent_id"] == 0 and root["args"] == {"map_id": 4}
        for child in ("task.copy", "block", "task.commit"):
            assert by_name[child]["parent_id"] == root["span_id"]
        for phase in ("block.admit", "block.copy"):
            assert by_name[phase]["parent_id"] == by_name["block"]["span_id"]
        assert {e["trace_id"] for e in t.events if e["name"] != "turn"} == {root["trace_id"]}
        assert by_name["turn"]["trace_id"] != root["trace_id"]  # a root each, as ever
        # own args where the mark has them (None too), the call's where it is a triple
        assert by_name["task.copy"]["args"] == {"turns": 9} and "args" not in by_name["block"]
        assert by_name["task.commit"]["args"] == by_name["turn"]["args"] == {"of": "the call"}
        assert len({e["span_id"] for e in t.events}) == 7

    def test_a_full_ring_counts_what_a_bulk_call_pushes_out(self):
        t = Tracer(enabled=True, capacity=4)
        ctx = t.start_span("frame")
        t.end_span(ctx)
        t.record_spans(ctx, [("p%d" % i, i, i + 1) for i in range(6)])
        assert [e["name"] for e in t.events] == ["p2", "p3", "p4", "p5"]
        assert t.dropped == 3

    def test_export_merge_and_tail_cannot_tell_them_apart(self, tmp_path):
        t = Tracer(enabled=True)
        self.parent_and_children(t, eid=2)
        assert [e["name"] for e in t.tail(2)] == ["frame.c", "frame.d"]
        merged = trace_mod.merge_events([t.events, t.events])  # a sweep's overlapping views
        assert len(merged) == 5 and {e["pid"] for e in merged} == {2}
        path = tmp_path / "trace.json"
        assert t.export(str(path)) == 5
        assert {e["ph"] for e in json.loads(path.read_text())["traceEvents"]} == {"X"}


class TestSpanCost:
    """What PR 36 took out of every span: the pid is read once a process."""

    def test_a_span_reads_no_pid(self, monkeypatch):
        calls = []
        monkeypatch.setattr(trace_mod.os, "getpid", lambda: calls.append(1) or 4242)
        t = Tracer(enabled=True)
        with t.span("a", x=1):
            t.instant("i")
        ctx = t.start_span("b")
        t.end_span(ctx)
        t.record_spans(ctx, [("c", 1, 2)])
        assert not calls and len(t.events) == 4

    def test_the_ring_is_not_the_collectors_to_walk(self):
        """PR 50: an event with ``args`` is a dict holding a dict, which the
        cycle collector would track for good and walk at every full
        collection — a traced window's ring holds hundreds of thousands.
        ``_event`` takes it out of the collector's lists (it can be part of
        no cycle); one without ``args`` holds atoms and was never in them.
        Both are freed by their reference counts when the ring lets go."""
        import gc
        import weakref

        t = Tracer(enabled=True, capacity=4)
        with t.executor_scope(3):
            with t.span("a", shuffle_id=1):
                t.instant("i", x=1)
            ctx = t.start_span("b")
            t.end_span(ctx, blocks=2)
            t.record_spans(ctx, [("c", 1, 2, {"turns": 5}), ("d", 2, 3)])
        spans = [e for e in t.events if e["ph"] == "X"]
        assert [e["name"] for e in spans] == ["a", "b", "c", "d"]
        assert [e.get("args") for e in spans] == [{"shuffle_id": 1}, {"blocks": 2}, {"turns": 5}, None]
        assert all(e["eid"] == 3 for e in spans)
        assert not any(gc.is_tracked(e) for e in spans)

        class Held:
            pass

        # what an event holds goes when the ring drops the event, no collection needed
        held = Held()
        gone = weakref.ref(held)
        t.record_spans(None, [("e", 1, 2, {"held": held})])
        del held
        assert gone() is not None
        gc.disable()
        try:
            t.record_spans(None, [("f%d" % i, i, i + 1) for i in range(4)])  # the ring is 4 long
            assert gone() is None
        finally:
            gc.enable()

    def test_a_forked_child_reads_its_pid_again(self, monkeypatch):
        # this process's pid and id counter come back when the test ends
        monkeypatch.setattr(trace_mod, "_PID", trace_mod._PID)
        monkeypatch.setattr(trace_mod, "_new_id", trace_mod._new_id)
        monkeypatch.setattr(trace_mod.os, "getpid", lambda: 0x1ABCD)
        trace_mod._read_pid()  # what ``os.register_at_fork`` runs in the child
        t = Tracer(enabled=True)
        with t.span("in-child"):
            pass
        [ev] = t.events
        assert ev["pid"] == 0x1ABCD
        assert ev["span_id"] >> 48 == ev["uid"] >> 48 == 0xABCD

    def test_a_real_fork_runs_the_hook(self):
        """In a process of its own (a fork under pytest's threads is not for a
        test): the child's events carry the child's pid, not the cached one."""
        import os
        import subprocess
        import sys

        script = (
            "import os\n"
            "from sparkucx_tpu.utils import trace\n"
            "r, w = os.pipe()\n"
            "child = os.fork()\n"
            "if child == 0:\n"
            "    t = trace.Tracer(enabled=True)\n"
            "    with t.span('c'):\n"
            "        pass\n"
            "    ev = t.events[0]\n"
            "    os.write(w, f'{ev[\"pid\"]} {ev[\"span_id\"] >> 48}'.encode())\n"
            "    os._exit(0)\n"
            "os.waitpid(child, 0)\n"
            "print(os.read(r, 64).decode(), child, trace._PID == os.getpid())\n"
        )
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run([sys.executable, "-c", script], cwd=root, capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        ev_pid, id_bits, child, parent_kept = out.stdout.split()
        assert int(ev_pid) == int(child) and int(id_bits) == int(child) & 0xFFFF and parent_kept == "True"

    def test_span_is_no_generator_and_hands_its_context_over(self):
        t = Tracer(enabled=True)
        with t.span("a", n=1) as ctx:
            assert t.current_context() is ctx and ctx.t1 == 0
            ctx.args["rows"] = 9
        assert t.current_context() is None and ctx.t1 >= ctx.t0
        assert t.events[0]["args"] == {"n": 1, "rows": 9}
        off = Tracer(enabled=False)
        with off.span("a") as none:
            assert none is None
        assert off.span("a") is off.span("b")  # the shared no-op


class TestHotPathWiring:
    def test_exchange_emits_spans(self):
        from sparkucx_tpu.config import TpuShuffleConf
        from sparkucx_tpu.transport.tpu import TpuShuffleCluster

        trace_mod.TRACER.clear()
        trace_mod.TRACER.enable()
        try:
            conf = TpuShuffleConf(
                staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2
            )
            cluster = TpuShuffleCluster(conf, num_executors=2)
            cluster.create_shuffle(0, 2, 2)
            for m in range(2):
                t = cluster.transport(cluster.meta(0).map_owner[m])
                w = t.store.map_writer(0, m)
                for r in range(2):
                    w.write_partition(r, np.full(300, m * 2 + r, np.uint8).tobytes())
                t.commit_block(w.commit().pack())
            cluster.run_exchange(0)
            names = [e["name"] for e in trace_mod.TRACER.events]
            assert "exchange.superstep" in names
            assert "exchange.seal" in names
            assert "exchange.collective" in names
            assert "exchange.d2h" in names
            # nesting: superstep duration covers the collective
            sup = next(e for e in trace_mod.TRACER.events if e["name"] == "exchange.superstep")
            col = next(e for e in trace_mod.TRACER.events if e["name"] == "exchange.collective")
            assert sup["ts"] <= col["ts"] and sup["ts"] + sup["dur"] >= col["ts"] + col["dur"]
        finally:
            trace_mod.TRACER.disable()
            trace_mod.TRACER.clear()

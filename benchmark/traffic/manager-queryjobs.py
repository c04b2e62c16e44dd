"""Traffic driver: whole **queries** from one client thread in the harness's
own process — ``QueryRunner.run`` over a ``StageDag`` on ``TpuShuffleManager``,
with every stage after an exchange run on the chip (the runner's batch lane).

A job is one TPC-H Q18: the map tasks of its three order-key shuffles (A: each
lineitem split's partial sums, B: orders, C: lineitem's rows) written through
``get_writer``, three ``run_exchange``, then a reduce task a partition, one in
flight — three ordered device reads, ``grouped_sum_records`` /
``merge_join_records`` dispatched on the task's executor, its few result rows
brought across — and the host tail (the top ``limit`` rows; ``c_name`` looked
up here from the key).  All three shuffles stay alive until the query ends.
Sent by a Spark SQL application on an engine that keeps the shuffle and the
operators after it in device memory, running a join-and-aggregate report over
a fact table: the nightly query of a warehouse.

``benchmark/jobs.py``'s one-shuffle ``run_job`` does not fit a query of three
shuffles, so the loop is this file's own and keeps that contract: one
verified full-size warm-up query (every reduce task's rows — each surviving
order with its sum and its joined columns — equal to the reference's, and the
query's rows), then whole queries for ``--seconds`` (each task's row count and
digest, and the query's rows), ``window_start`` / ``window_end`` /
``job_done`` (before the shuffles are removed) / ``trace_start`` /
``trace_stop`` to the harness, the spans ``job.write`` (the map tasks of A, B
and C), ``job.exchange`` (the three exchanges) and ``job.read`` (the reduce
tasks and the host tail).  A job's tasks are its map tasks and its reduce
tasks; a reduce task whose rows are not the reference's, or a query whose
rows are not, is a failed task.

A program without the batch lane is refused in ``start``, **before any row is
made**.  Beyond what ``run.py`` decides ``correct`` on, a run is unsound here
when, in any query, the runner's ``query`` counters did not rise by exactly
one ``device_tasks`` a reduce task, the query's own ``records_aggregated`` (the
partial sums, and the lines that joined a surviving order) and
``result_d2h_bytes`` of the tasks' fixed result arrays (a few hundred KB a
query: nothing of the three shuffles' 1.68 GB), when an ordered read crossed
to the host (``orderedread`` ``d2h_bytes``), when a block gather of another
lowering than the platform's own ran, or when the device's ``bytes_in_use``
after a removed query stands over its level after the first by more than half
a shuffle's staging.  The line ``query:`` prints all of it.
"""

from __future__ import annotations

import gc
import json
import time
from contextlib import contextmanager
from typing import Dict, List


from benchmark.cells import load_module
from benchmark.jobs import TRACED_JOBS, JobResult, WindowResult
from benchmark.spans import SpanLog

#: the runner's ``query`` counters this driver holds every query to
COUNTED = ("device_tasks", "records_aggregated", "groups_out", "rows_joined", "result_d2h_bytes",
           "overflow_checks")
#: what the line ``query:`` prints of each store's ``write_stats()``
TIERS = ("pool_hits", "pool_misses", "pool_kept_over_budget", "pool_dropped_busy", "early_put_pieces",
         "seal_put_pieces", "early_put_dropped")
#: lanes of a result row (five 8-byte columns) and of an operator's info vector
RESULT_LANES, INFO_LANES, OPERATORS = 10, 4, 4


def require_batch_lane():
    """The program's query runner and its batch lane; exit at once on a
    program that has none — before any row is made."""
    try:
        from sparkucx_tpu.query import batch
        from sparkucx_tpu.query.runner import QueryRunner
    except ImportError:
        batch = QueryRunner = None
    if batch is None or not callable(getattr(batch, "BatchQuery", None)):
        raise SystemExit(
            "benchmark: traffic manager-queryjobs needs the query runner's batch lane "
            "(sparkucx_tpu.query.batch: RecordSplit, BatchQuery); this program has none"
        )
    return batch, QueryRunner


def q18_dag(config: dict, threshold: int):
    """The configuration's Q18 as the runner's ``StageDag``."""
    from sparkucx_tpu.query import Stage, StageDag

    parts, key, col = int(config["partitions"]), int(config["record_key_bytes"]), int(config["column_bytes"])
    groups = int(config["max_groups_per_task"])
    exchange = dict(partitions=parts, key_bytes=key)
    return StageDag([
        Stage.make("lineitem_sums", "scan"), Stage.make("orders", "scan"), Stage.make("lineitem", "scan"),
        Stage.make("A", "exchange", ["lineitem_sums"], record_bytes=2 * col, **exchange),
        Stage.make("B", "exchange", ["orders"], record_bytes=4 * col, **exchange),
        Stage.make("C", "exchange", ["lineitem"], record_bytes=2 * col, **exchange),
        # select l_orderkey from lineitem group by l_orderkey having sum(l_quantity) > :threshold
        Stage.make("large_orders", "aggregate", ["A"], value_byte=key, having="gt", threshold=threshold,
                   max_groups=groups),
        # o_orderkey in (...): build side first
        Stage.make("orders_kept", "join", ["large_orders", "B"], join_type="left_semi", max_rows=groups),
        # o_orderkey = l_orderkey, then sum(l_quantity) by the order
        Stage.make("lines_kept", "join", ["orders_kept", "C"], join_type="inner", max_rows=7 * groups),
        Stage.make("order_sums", "aggregate", ["lines_kept"], value_byte=key, max_groups=groups),
        # rows: o_orderkey, sum, o_custkey, o_totalprice, o_orderdate
        Stage.make("top", "sort", ["order_sums"], order_by=((3, "desc"), (4, "asc"), (0, "asc")),
                   limit=int(config["limit"])),
    ])


class Traffic:
    def __init__(self, cell, args) -> None:
        self.cell, self.args = cell, args
        self.manager = None

    def start(self, conf, parts: dict):
        self.batch, runner_class = require_batch_lane()
        from sparkucx_tpu.shuffle.manager import TpuShuffleManager

        t0 = time.perf_counter()
        config = self.cell.config
        self.reference = load_module("references", config["reference"])
        self.records = self.reference.make_records(config, self.args.seed)
        parts["records"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.manager = TpuShuffleManager(conf, num_executors=self.cell.chips)
        self.runner = runner_class(self.manager)
        parts["manager"] = time.perf_counter() - t0
        self.dag = q18_dag(config, self.records.threshold)
        self.inputs = self.make_inputs()
        return self.manager

    def make_inputs(self) -> Dict[str, list]:
        """The three scans' splits, each a map task's output as its
        partitioner left it."""
        split = self.batch.RecordSplit
        shuffles = self.records.shuffles
        return {scan: [split(s.records, s.bounds) for s in shuffles[name]]
                for scan, name in (("lineitem_sums", "A"), ("orders", "B"), ("lineitem", "C"))}

    # -- one query ----------------------------------------------------------------

    def counters(self) -> Dict[str, int]:
        snap = self.runner.counters()
        out = {name: int(snap[name]) for name in COUNTED}
        out["ordered_d2h_bytes"] = sum(int(row["d2h_bytes"]) for row in self.manager.cluster.ordered_read_stats())
        return out

    def run_query(self, log: SpanLog, control, full: bool) -> JobResult:
        records = self.records
        reducers, tasks = records.partitions, records.num_mappers + records.partitions
        marks: Dict[str, int] = {}

        @contextmanager
        def phases(name: str, shuffle_ids):
            if name == "release":
                marks.setdefault("end", time.perf_counter_ns())
                if shuffle_ids:
                    control("job_done", shuffle_id=shuffle_ids[-1])
                yield
                return
            with log.span("job." + name):
                yield
            if name == "read":
                marks["end"] = time.perf_counter_ns()

        before = self.counters()
        t0 = time.perf_counter_ns()
        try:
            result = self.runner.run(self.dag, self.inputs, phases=phases)
        except Exception as e:  # the query's boundary: every reduce task of it failed
            seconds = (marks.get("end", time.perf_counter_ns()) - t0) / 1e9
            print(f"the query raised: {type(e).__name__}: {e}", flush=True)
            return JobResult(seconds, tasks, reducers, 0, [])
        seconds = (marks["end"] - t0) / 1e9
        # the comparison is off the query's clock
        check = records.task_equals if full else records.task_check
        failed = [r for r, rows in enumerate(result.partitions) if not check(r, rows)]
        for r in failed[:5]:
            print(f"reduce task {r}: its rows are not the reference's", flush=True)
        wrong_rows = [self.reference.answer_row(row) for row in result.rows] != records.answer
        if wrong_rows:
            print("the query: its rows are not the reference's, or not in its order", flush=True)
        rose = {name: value - before[name] for name, value in self.counters().items()}
        want = {"device_tasks": reducers, "records_aggregated": records.records_aggregated,
                "overflow_checks": 2 * reducers, "ordered_d2h_bytes": 0,
                "result_d2h_bytes": reducers * 4 * (self.groups * RESULT_LANES + OPERATORS * INFO_LANES)}
        wrong = {name: (rose[name], value) for name, value in want.items() if rose[name] != value}
        if wrong:
            self.miscounted.append(", ".join(f"{k} rose {a}, not {b}" for k, (a, b) in wrong.items()))
        self.bytes_in_use.append(max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in self.devices))
        return JobResult(seconds, tasks, len(failed) + bool(wrong_rows), 0, result.task_seconds)

    # -- the window ---------------------------------------------------------------

    def run(self, control, parts: dict) -> WindowResult:
        cluster, records = self.manager.cluster, self.records
        self.devices = list(cluster.mesh.devices.reshape(-1))
        self.groups = int(self.cell.config["max_groups_per_task"])
        self.miscounted: List[str] = []
        self.bytes_in_use: List[int] = []
        seconds, trace = self.args.seconds, bool(self.args.trace)
        before = self.counters()
        log = SpanLog()
        out = WindowResult(job_bytes=records.total_bytes, job_blocks=records.num_blocks)
        t0 = time.perf_counter()
        out.warmup = self.run_query(log, control, full=True)
        out.warmup_s = time.perf_counter() - t0
        gc.freeze()  # the records and the reference live as long as the run
        log.spans.clear()
        control("window_start")
        t_window = time.perf_counter()
        traced = []  # (index among the jobs, start ns, end ns) of the jobs in the profiler's session
        while True:
            elapsed = time.perf_counter() - t_window
            untraced = trace and len(traced) < TRACED_JOBS
            if elapsed >= seconds and out.jobs and not untraced:
                break
            tracing = bool(untraced and out.jobs and (traced or elapsed >= seconds / 2))
            if tracing and not traced:
                control("trace_start")
            t0 = time.perf_counter_ns()
            out.jobs.append(self.run_query(log, control, full=False))
            if tracing:
                traced.append((len(out.jobs) - 1, t0, time.perf_counter_ns()))
                if len(traced) == TRACED_JOBS:
                    control("trace_stop")
        control("window_end")
        if traced:
            index, lo, hi = min(traced, key=lambda job: out.jobs[job[0]].seconds)
            out.traced_job, out.traced_ns = index, [lo, hi]
        out.spans = list(log.spans)

        after = self.counters()
        platform = self.devices[0].platform
        want = "dma" if platform == "tpu" else "xla"
        ran = sorted(set(cluster.executed_lowerings()["gather"]))
        unsound = list(self.miscounted)
        if ran != [want]:
            unsound.append(f"gather lowering {ran}, not [{want!r}]")
        level = self.bytes_in_use[0] if self.bytes_in_use else 0
        slack = int(self.cell.config["conf"]["staging_capacity_per_executor"]) // 2
        if any(b > level + slack for b in self.bytes_in_use):
            unsound.append(f"bytes_in_use after a removed query over {level} + {slack}")
        ordered = cluster.ordered_read_stats()
        print("query: " + json.dumps({
            "queries": len(out.jobs) + 1, "gather": ran, "expected": want,
            "counters": {name: after[name] - before[name] for name in after},
            "rows": len(records.rows), "records_aggregated_a_query": records.records_aggregated,
            "ordered_in_flight": [int(row["in_flight"]) for row in ordered],
            "ordered_in_flight_peak": [int(row["in_flight_peak"]) for row in ordered],
            "ordered_device_bytes_peak": [int(row["in_flight_device_bytes_peak"]) for row in ordered],
            "stores": [{name: t.store.write_stats().get(name) for name in TIERS} for t in cluster.transports],
            "bytes_in_use_after_query": self.bytes_in_use, "unsound": unsound,
        }), flush=True)
        if unsound:
            out.warmup.failed += 1  # the one way a driver has to say: not this run
        return out

    def close(self) -> None:
        if self.manager is not None:
            self.manager.stop()

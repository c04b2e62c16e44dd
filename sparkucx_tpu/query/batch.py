"""The query runner's batch lane: a StageDag over fixed-width record arrays
whose stages after an exchange run ON THE DEVICE.

The tuple lane (``runner.py``) moves Python tuples and computes every stage
with the numpy oracles.  This lane is the served path at a warehouse's sizes:

* a **scan** input is a list of splits, each an ``(n, record_bytes)``
  ``uint8`` array (or a ``RecordSplit`` whose rows the map task's partitioner
  has already grouped by reduce partition);
* an **exchange** stage is one shuffle through the manager's own calls —
  ``register_shuffle``, a map task a split (``get_writer``, one stream a
  non-empty reducer of ``FixedWidthSerializer`` batches, commit),
  ``run_exchange`` — and leaves its partitions on the device
  (``conf.keep_device_recv``); every shuffle of the query stays alive until
  the query ends, then all are unregistered;
* the stages after the exchanges are the **reduce task**, one a partition,
  one in flight: each exchange it reads is ``get_reader(sid, r, r + 1,
  deserializer=FixedWidthSerializer(w, k), key_ordering=True).read_device()``
  (the partition's records sorted by key on its executor's chip), each
  ``aggregate`` a dispatch of ``ops.relational.grouped_sum_records``, each
  ``join`` one of ``merge_join_records``; what crosses to the host is the
  task's last stage's rows and every operator's ``info``, in one small array;
* a **sort** sink is the host tail: the tasks' rows put together, ordered and
  cut to a limit with numpy.

Nothing falls back: a conf that does not keep the received shards on the
device is refused before a row is written (``BatchLaneRefusedError``), a sum
that left 63 bits raises ``QuerySumOverflowError``, an operator whose output
outgrew its stage's capacity ``QueryCapacityError``; ``RaggedBlockError`` and
``SplitBlockError`` come from the reader as they are.

Stage parameters of this lane (``Stage.make(..., **params)``):
``exchange``: ``partitions``, ``record_bytes``, ``key_bytes`` (one key width
a query); ``aggregate``: ``value_byte`` (where the summed 8-byte column
starts), ``having`` (``None`` | ``"gt"``), ``threshold``, ``max_groups``;
``join`` (inputs: build side first, then the probe side — the SQL left):
``join_type`` (``inner`` | ``left_semi``), ``max_rows``; ``sort``:
``order_by`` (``((column, "asc" | "desc"), ...)`` over the rows' 8-byte
columns as unsigned integers), ``limit``.

Spans: ``query.exchange.write`` and ``query.exchange.run`` a shuffle;
``query.task`` a reduce task (``args``: ``reduce_id``, ``records_in``,
``rows_out``) over the ordered read's own spans, ``query.aggregate`` /
``query.join`` (the dispatches) and ``query.result.d2h`` (the wait for the
task's rows).  Counters, in the runner's ``query`` family: ``device_tasks``,
``records_aggregated``, ``groups_out``, ``rows_joined``, ``result_d2h_bytes``,
``overflow_checks``.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Callable, ContextManager, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.ops.relational import (
    INFO_OVERFLOW,
    INFO_ROWS,
    INFO_TOTAL,
    grouped_sum_records,
    merge_join_records,
)
from sparkucx_tpu.query.dag import Stage, StageDag
from sparkucx_tpu.shuffle.reader import FixedWidthSerializer
from sparkucx_tpu.utils.trace import span

#: the counters this lane adds to the runner's ``query`` family
COUNTERS = ("device_tasks", "records_aggregated", "groups_out", "rows_joined",
            "result_d2h_bytes", "overflow_checks")


class BatchLaneRefusedError(TransportError):
    """A batch-lane query on a conf that cannot run it: the stages after an
    exchange read its partitions on the device, and this conf does not keep
    them there.  Raised before a shuffle is registered; nothing is computed
    on the host instead."""


class QuerySumOverflowError(ArithmeticError):
    """A grouped sum left 63 bits (or met a negative value): the device
    operator flagged it and the runner hands no wrapped sum on."""


class QueryCapacityError(RuntimeError):
    """An operator made more rows than its stage's ``max_groups`` /
    ``max_rows`` lets it hand out: run the query with more room."""


@dataclass(frozen=True)
class RecordSplit:
    """One scan split of the batch lane: ``records`` ``(n, record_bytes)``
    ``uint8``.  ``bounds`` (``partitions + 1`` row offsets): the rows are
    already grouped by reduce partition — a map task's output as its
    partitioner left it; ``None``: the exchange partitions them
    (``hash_partition``)."""

    records: np.ndarray
    bounds: Optional[np.ndarray] = None


@dataclass
class BatchResult:
    """What a batch-lane query returns."""

    #: the sink's rows: ``(n, columns)`` ``uint64``, the rows' 8-byte columns
    rows: np.ndarray
    #: every reduce task's rows, in partition order, as handed to the sink
    partitions: List[np.ndarray]
    #: seconds of every reduce task, dispatch to rows on the host
    task_seconds: List[float]


def _fmix64(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xFF51AFD7ED558CCD)
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xC4CEB9FE1A85EC53)
    return h ^ (h >> np.uint64(33))


def hash_partition(records: np.ndarray, key_bytes: int, partitions: int) -> np.ndarray:
    """The reduce partition of every record: MurmurHash3's 64-bit finalizer
    over the key's little-endian 8-byte words (the last zero-extended),
    folded word by word, mod ``partitions`` — a mixing hash of the WHOLE key.
    An 8-byte key is ``fmix64(key) mod partitions``."""
    n = len(records)
    words = -(-key_bytes // 8)
    key = np.zeros((n, words * 8), dtype=np.uint8)
    key[:, :key_bytes] = records[:, :key_bytes]
    h = np.zeros(n, dtype=np.uint64)
    for word in key.view("<u8").T:
        h = _fmix64(h ^ word)
    return (h % np.uint64(partitions)).astype(np.int64)


def is_batch_input(rows) -> bool:
    """Whether a scan input is this lane's: a sequence of record arrays or
    ``RecordSplit``s (the tuple lane's is a list of tuples)."""
    return bool(len(rows)) and all(isinstance(s, (np.ndarray, RecordSplit)) for s in rows)


@jax.jit
def pack_task_result(rows, *infos):
    """A task's rows and its operators' ``info`` vectors as ONE flat int32
    array: one transfer to the host a task."""
    return jnp.concatenate([rows.reshape(-1), *infos])


def _no_phase(name: str, shuffle_ids: Sequence[int]) -> ContextManager:
    return contextlib.nullcontext()


class BatchQuery:
    """One run of a batch-lane DAG for a ``QueryRunner``, on its manager."""

    def __init__(self, manager, dag: StageDag, inputs: Dict[str, Sequence], count, next_shuffle_id,
                 phases=None) -> None:
        self.manager = manager
        #: the runner's: ``count({counter: rise})`` and a fresh shuffle id a call
        self.count, self.next_shuffle_id = count, next_shuffle_id
        self.dag = dag
        self.inputs = inputs
        self.phases: Callable[[str, Sequence[int]], ContextManager] = phases or _no_phase
        self.exchanges = [st for st in dag.stages if st.op == "exchange"]
        #: the reduce task's body, in dag order; a ``sort`` sink is the host tail
        self.body = [st for st in dag.stages if st.op in ("exchange", "aggregate", "join")]
        #: exchange stage -> its shuffle, in the order registered (``phases`` sees the ids)
        self.sids: Dict[str, int] = {}
        self.shuffle_ids: List[int] = []
        self._check()
        self.arguments = self._operator_arguments()

    # -- what can be refused is refused before a row moves -------------------

    def _check(self) -> None:
        conf = self.manager.conf
        if not conf.keep_device_recv:
            raise BatchLaneRefusedError(
                "the batch lane's reduce tasks read each exchange on the device: "
                "conf.keep_device_recv is false, so the received shards are not kept there "
                "(set keep_device_recv=true; host_recv_mode='device' keeps no host copy beside them)"
            )
        if not self.exchanges:
            raise ValueError("a batch-lane query has at least one exchange stage")
        for st in self.dag.stages:
            if st.op == "sort" and st is not self.dag.sink:
                raise ValueError(f"stage {st.name!r}: the batch lane sorts only in its sink, on the host")
        for st in self.exchanges:
            if self.dag.by_name[st.inputs[0]].op != "scan":
                raise ValueError(f"stage {st.name!r}: a batch-lane exchange shuffles a scan's splits")
            if st.param("record_bytes") is None or st.param("key_bytes") is None:
                raise ValueError(f"stage {st.name!r}: a batch-lane exchange names record_bytes and key_bytes")
        keys = {int(st.param("key_bytes")) for st in self.exchanges}
        parts = {int(st.param("partitions", self.manager.num_executors)) for st in self.exchanges}
        if len(keys) != 1 or len(parts) != 1:
            raise ValueError(f"one key width and one partition count a query, not {sorted(keys)} / {sorted(parts)}")
        self.key_bytes, self.partitions = keys.pop(), parts.pop()
        if self.body[-1].op == "exchange":
            raise ValueError("a batch-lane query ends in an aggregate or a join (and a sort on the host)")

    # -- the map side ---------------------------------------------------------

    def _write(self, st: Stage) -> None:
        serializer = self.arguments[st.name]["deserializer"]
        splits = [s if isinstance(s, RecordSplit) else RecordSplit(s) for s in self.inputs[st.inputs[0]]]
        sid = self.next_shuffle_id()
        self.manager.register_shuffle(sid, len(splits), self.partitions)
        self.shuffle_ids.append(sid)
        self.sids[st.name] = sid
        with span("query.exchange.write", stage=st.name, shuffle_id=sid, map_tasks=len(splits)):
            for map_id, split in enumerate(splits):
                records, bounds = split.records, split.bounds
                if bounds is None:
                    part = hash_partition(records, self.key_bytes, self.partitions)
                    order = np.argsort(part, kind="stable")
                    records, bounds = records[order], np.searchsorted(part[order], np.arange(self.partitions + 1))
                writer = self.manager.get_writer(sid, map_id)
                for r in np.flatnonzero(np.diff(bounds)):
                    with writer.get_partition_writer(int(r)).open_stream() as stream:
                        stream.write(serializer.serialize(records[bounds[r] : bounds[r + 1]]))
                writer.commit_all_partitions()

    # -- the reduce task --------------------------------------------------------

    def _operator_arguments(self) -> Dict[str, dict]:
        """What each stage of the reduce task's body hands its read or its
        operator besides the records: worked out once a query, not a task."""
        out: Dict[str, dict] = {}
        for st in self.body:
            if st.op == "exchange":
                out[st.name] = dict(deserializer=FixedWidthSerializer(int(st.param("record_bytes")), self.key_bytes),
                                    key_ordering=True)
            elif st.op == "aggregate":
                threshold = int(st.param("threshold", 0))
                out[st.name] = dict(
                    threshold=np.array([threshold & 0xFFFFFFFF, threshold >> 32], np.uint32),
                    key_bytes=self.key_bytes, value_lane=int(st.param("value_byte")) // 4,
                    having=st.param("having"), out_capacity=int(st.param("max_groups")),
                )
            else:
                out[st.name] = dict(key_bytes=self.key_bytes, join_type=str(st.param("join_type", "inner")),
                                    out_capacity=int(st.param("max_rows")))
        return out

    def _task(self, reduce_id: int):
        """One reduce task, every stage after the exchanges dispatched on its
        executor's device; returns its last stage's rows as ``(k, columns)``
        ``uint64`` on the host, and the task's seconds."""
        #: stage -> (records on the device, their count, the operator that made them or None for a read)
        env: Dict[str, tuple] = {}
        operators, infos = [], []
        #: what the aggregates summed: records of reads, and the operators whose rows they took
        summed_records, summed_rows_of = 0, []
        records_in = 0
        t0 = time.perf_counter_ns()
        with span("query.task", reduce_id=reduce_id) as ctx:
            for st in self.body:
                arguments = self.arguments[st.name]
                if st.op == "exchange":
                    got = self.manager.get_reader(self.sids[st.name], reduce_id, reduce_id + 1, **arguments).read_device()
                    env[st.name] = (got.records, np.int32(got.num_records), None)
                    records_in += got.num_records
                    continue
                if st.op == "aggregate":
                    rows_in, count, made_by = env[st.inputs[0]]
                    if made_by is None:
                        summed_records += int(count)
                    else:
                        summed_rows_of.append(made_by)
                    with span("query.aggregate", stage=st.name):
                        rows, info = grouped_sum_records(rows_in, count, **arguments)
                else:
                    build, probe = env[st.inputs[0]], env[st.inputs[1]]
                    with span("query.join", stage=st.name):
                        rows, info = merge_join_records(probe[0], probe[1], build[0], build[1], **arguments)
                env[st.name] = (rows, info, len(infos))  # the operators take a count out of an info themselves
                operators.append(st)
                infos.append(info)
            packed = pack_task_result(rows, *infos)
            with span("query.result.d2h", reduce_id=reduce_id, bytes=int(packed.size) * 4):
                host = np.asarray(packed)
            cut = rows.shape[0] * rows.shape[1]
            infos = host[cut:].reshape(len(operators), -1)
            rows_out = int(infos[-1][INFO_ROWS])
            if ctx is not None:
                ctx.args.update(records_in=records_in, rows_out=rows_out)
        for st, info in zip(operators, infos):
            if info[INFO_OVERFLOW]:
                raise QuerySumOverflowError(
                    f"stage {st.name!r}, reduce task {reduce_id}: a group's sum left 63 bits "
                    "(or a value was negative); no wrapped sum is handed on"
                )
            if info[INFO_TOTAL] > info[INFO_ROWS]:
                raise QueryCapacityError(
                    f"stage {st.name!r}, reduce task {reduce_id}: {int(info[INFO_TOTAL])} rows, "
                    f"room for {int(info[INFO_ROWS])} (max_groups / max_rows)"
                )

        def handed_out(op: str) -> int:
            return sum(int(info[INFO_ROWS]) for st, info in zip(operators, infos) if st.op == op)

        self.count({
            "device_tasks": 1,
            "records_aggregated": summed_records + sum(int(infos[i][INFO_ROWS]) for i in summed_rows_of),
            "groups_out": handed_out("aggregate"),
            "rows_joined": handed_out("join"),
            "result_d2h_bytes": int(host.nbytes),
            "overflow_checks": sum(st.op == "aggregate" for st in operators),
        })
        out = np.ascontiguousarray(host[:cut].reshape(rows.shape)[:rows_out]).view("<u8")
        return out, (time.perf_counter_ns() - t0) / 1e9

    # -- the host tail ----------------------------------------------------------

    def _sink(self, partitions: List[np.ndarray]) -> np.ndarray:
        columns = partitions[0].shape[1] if partitions else 0
        rows = np.concatenate(partitions) if partitions else np.zeros((0, columns), np.uint64)
        sink = self.dag.sink
        if sink.op != "sort":
            return rows
        keys = []
        for column, direction in reversed(tuple(sink.param("order_by", ()))):
            col = rows[:, int(column)]
            keys.append(col if direction == "asc" else ~col)  # ~ reverses an unsigned order
        order = np.lexsort(keys) if keys else np.arange(len(rows))
        limit = sink.param("limit")
        return rows[order[: int(limit)] if limit is not None else order]

    def run(self) -> BatchResult:
        sids = self.shuffle_ids
        try:
            with self.phases("write", sids):
                for st in self.exchanges:
                    self._write(st)
            with self.phases("exchange", sids):
                for st in self.exchanges:
                    with span("query.exchange.run", stage=st.name, shuffle_id=self.sids[st.name]):
                        self.manager.run_exchange(self.sids[st.name])
                    self.count({"exchanges_executed": 1})
            with self.phases("read", sids):
                done = [self._task(r) for r in range(self.partitions)]
                partitions = [rows for rows, _ in done]
                rows = self._sink(partitions)
            self.count({"stages": len(self.dag.stages)})
        finally:
            with self.phases("release", sids):
                for sid in sids:
                    self.manager.unregister_shuffle(sid)
        return BatchResult(rows, partitions, [s for _, s in done])

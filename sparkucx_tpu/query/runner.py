"""QueryRunner — executes a StageDag end-to-end on the cluster, per tenant.

The runner compiles each ``exchange`` stage onto the existing manager SPI
(register / staged-store writers / one collective superstep via the
``ExchangePlan`` executor / windowed readers) and runs the per-partition
compute stages (aggregate / join / sort) on the exchanged partitions with
the deterministic numpy reference ops, so TeraSort-style (scan → exchange →
sort) and TPC-H-shaped (scan → exchange → aggregate, scan ×2 → exchange ×2 →
join) pipelines run whole, not one shuffle at a time.

Perf headline — cross-query shuffle reuse: with
``spark.shuffle.tpu.query.cacheEnabled`` the runner keys every sealed
exchange by its lineage hash (query/lineage.py) and a repeat serves straight
from the store/eviction/serve tiers: no register, no map writes, no
collective — just the windowed read.  Cached rounds stay charged to the
owning tenant's HBM quota (admission control); entries die on
input-fingerprint change or ``unregister_shuffle`` (the runner holds a
manager teardown hook, so external removals invalidate too); quota pressure
triggers the footprint-aware keep/recompute pass (largest first,
arXiv:2112.01075 — see LineageCache.plan_eviction).

Off path: with the knob off (default) every exchange executes and is
unregistered when the query finishes — no cache, no retained shuffles, no
tenant charges, byte-identical to a cache-less runner.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparkucx_tpu.core.operation import TenantQuotaExceededError
from sparkucx_tpu.obs.metrics import counter_dict_provider
from sparkucx_tpu.ops.relational import hash_owners_host, oracle_aggregate, oracle_join
from sparkucx_tpu.ops.sort import oracle_sort
from sparkucx_tpu.query import batch
from sparkucx_tpu.query.dag import Stage, StageDag
from sparkucx_tpu.query.lineage import (
    LineageCache,
    fingerprint_rows,
    lineage_key,
)
from sparkucx_tpu.shuffle.reader import serialize_records
from sparkucx_tpu.utils.trace import instant

#: Runner-allocated shuffle ids live far above hand-numbered test/benchmark
#: sids and below the tenant-translated namespace (TENANT_SID_BASE = 1<<20).
_QUERY_SID_BASE = 1 << 16
_sid_counter = itertools.count(_QUERY_SID_BASE)
_sid_lock = threading.Lock()


def _next_sid() -> int:
    with _sid_lock:
        return next(_sid_counter)


Row = Tuple[int, ...]


class QueryRunner:
    """Per-tenant DAG executor over one TpuShuffleManager.

    ``cache`` may be shared between runners (one per app on the same
    cluster): entries are app-namespaced, so tenants never see each other's
    cached shuffles, but the keep/recompute eviction pass weighs the whole
    resident footprint.
    """

    def __init__(
        self,
        manager,
        app_id: str = "default",
        tenants=None,
        cache: Optional[LineageCache] = None,
    ) -> None:
        self.manager = manager
        self.conf = manager.conf
        self.app_id = app_id
        self.tenants = tenants
        if tenants is not None and not tenants.known(app_id):
            tenants.register(app_id)
        self.cache_enabled = bool(getattr(self.conf, "query_cache_enabled", False))
        self.cache = None
        if self.cache_enabled:
            self.cache = cache if cache is not None else LineageCache(
                max_bytes=self.conf.query_cache_max_bytes
            )
            self.cache.attach(manager)
        self._counters: Dict[str, int] = {
            "queries": 0,
            "stages": 0,
            "exchanges_executed": 0,
            "exchanges_reused": 0,
            "uncached_rounds": 0,
            "stale_invalidations": 0,
            **dict.fromkeys(batch.COUNTERS, 0),  # the batch lane's (query/batch.py)
        }
        self._counters_lock = threading.Lock()
        metrics = getattr(manager.cluster, "metrics", None)
        if metrics is not None:
            metrics.register(f"query:{app_id}", counter_dict_provider("query", self._snapshot))

    def counters(self) -> Dict[str, int]:
        """The runner's ``query`` counter family as it stands (with the
        lineage cache's, where one is attached)."""
        return self._snapshot()

    def _snapshot(self) -> Dict[str, int]:
        with self._counters_lock:
            out = dict(self._counters)
        if self.cache is not None:
            out.update(self.cache.snapshot())
        return out

    def _bump(self, name: str, n: int = 1) -> None:
        self._bump_many({name: n})

    def _bump_many(self, rises: Dict[str, int]) -> None:
        with self._counters_lock:
            for name, n in rises.items():
                self._counters[name] += n

    # -- execution ----------------------------------------------------------

    def run(self, dag: StageDag, inputs: Dict[str, List[Row]], phases=None):
        """Execute the DAG; returns the sink stage's result.

        ``inputs`` maps each scan stage name to its rows ((key, value) int
        tuples).  Exchange results are lists of per-partition row lists;
        aggregate/join keep that partitioning; sort returns one flat,
        globally ordered row list.

        Scan inputs that are record arrays a split (``batch.RecordSplit`` or
        ``(n, record_bytes)`` ``uint8`` arrays) take the **batch lane**
        (``query/batch.py``): every exchange one shuffle of fixed-width
        batches left on the device, the stages after them run there a reduce
        task at a time, the result a ``batch.BatchResult``.  ``phases`` is
        that lane's only: ``phases(name, shuffle_ids)`` gives a context
        manager entered round the query's ``write``, ``exchange``, ``read``
        and ``release`` (the shuffles still registered) phases.
        """
        scans = [inputs[st.name] for st in dag.stages if st.op == "scan"]
        if any(batch.is_batch_input(rows) for rows in scans):
            if not all(batch.is_batch_input(rows) for rows in scans):
                raise ValueError("a query's scans are all record arrays (the batch lane) or all tuples")
            result = batch.BatchQuery(self.manager, dag, inputs, self._bump_many, _next_sid, phases).run()
            self._bump("queries")
            return result
        results: Dict[str, object] = {}
        fingerprints: Dict[str, str] = {}
        ephemeral: List[int] = []  #: sids to unregister when the query ends
        try:
            for st in dag.stages:
                t0 = time.perf_counter()
                if st.op == "scan":
                    rows = list(inputs[st.name])
                    fingerprints[st.name] = fingerprint_rows(serialize_records(rows))
                    results[st.name] = rows
                elif st.op == "exchange":
                    results[st.name] = self._run_exchange(
                        dag, st, results[st.inputs[0]], fingerprints, ephemeral
                    )
                elif st.op == "aggregate":
                    results[st.name] = self._run_aggregate(st, results[st.inputs[0]])
                elif st.op == "join":
                    results[st.name] = self._run_join(
                        st, results[st.inputs[0]], results[st.inputs[1]]
                    )
                else:  # sort
                    results[st.name] = self._run_sort(st, results[st.inputs[0]])
                self._bump("stages")
                ms = (time.perf_counter() - t0) * 1e3
                instant("query.stage", app=self.app_id, stage=st.name, op=st.op, ms=ms)
        finally:
            for sid in ephemeral:
                self.manager.unregister_shuffle(sid)
        self._bump("queries")
        return results[dag.sink.name]

    # -- exchange (the cacheable stage) -------------------------------------

    def _run_exchange(
        self,
        dag: StageDag,
        st: Stage,
        upstream,
        fingerprints: Dict[str, str],
        ephemeral: List[int],
    ) -> List[List[Row]]:
        rows = _flatten(upstream)
        num_reducers = int(st.param("partitions", self.manager.num_executors))
        key = lineage_key(dag, st.name, fingerprints, self.conf)

        if self.cache is not None:
            entry = self.cache.lookup(self.app_id, key)
            if entry is not None:
                # reuse: the sealed shuffle serves from store/eviction/serve
                # tiers — no register, no writes, no collective.
                self._bump("exchanges_reused")
                instant(
                    "query.cache_hit",
                    app=self.app_id,
                    stage=st.name,
                    shuffle_id=entry.shuffle_id,
                    hits=entry.hits,
                )
                return self._read_partitions(entry.shuffle_id, num_reducers)

        sid, nbytes = self._execute_exchange(rows, num_reducers)
        self._bump("exchanges_executed")

        if self.cache is None:
            ephemeral.append(sid)
        else:
            structure = dag.canonical(st.name)  # fingerprint-free
            # input changed under the same query shape: those entries can
            # never hit again — tear them down through the manager so every
            # tier (store, ServeCache, encoded-chunk pool) drops the blocks.
            for stale in self.cache.stale_entries(self.app_id, structure, key):
                self._drop_entry(stale)
                self._bump("stale_invalidations")
            if self._admit(key, sid, nbytes, structure):
                pass  # retained: serves future hits, stays tenant-charged
            else:
                self._bump("uncached_rounds")
                ephemeral.append(sid)
        return self._read_partitions(sid, num_reducers)

    def _execute_exchange(self, rows: List[Row], num_reducers: int) -> Tuple[int, int]:
        """Register / write / superstep one hash exchange; returns
        (shuffle_id, serialized map-output bytes)."""
        m = self.manager
        num_mappers = m.num_executors
        sid = _next_sid()
        m.register_shuffle(sid, num_mappers, num_reducers)
        if rows:
            keys = np.array([r[0] for r in rows], np.uint32)
            owners = hash_owners_host(keys, num_reducers)
        else:
            owners = np.zeros(0, np.int32)
        nbytes = 0
        for map_id in range(num_mappers):
            chunk = rows[map_id::num_mappers]
            chunk_owners = owners[map_id::num_mappers]
            writer = m.get_writer(sid, map_id)
            for r in range(num_reducers):
                part = [row for row, o in zip(chunk, chunk_owners) if int(o) == r]
                if not part:
                    continue
                payload = serialize_records(part)
                nbytes += len(payload)
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(payload)
            writer.commit_all_partitions()
        m.run_exchange(sid)
        return sid, nbytes

    def _read_partitions(self, sid: int, num_reducers: int) -> List[List[Row]]:
        return [
            [tuple(rec) for rec in self.manager.get_reader(sid, r, r + 1).read()]
            for r in range(num_reducers)
        ]

    # -- admission control ---------------------------------------------------

    def _admit(self, key: str, sid: int, nbytes: int, structure: str) -> bool:
        """Charge the owning tenant and (on success) retain the shuffle.
        Quota pressure triggers the footprint-aware keep/recompute pass;
        an unadmittable round stays uncached (caller unregisters it)."""
        cache = self.cache
        if cache.max_bytes and nbytes > cache.max_bytes:
            return False
        # runner-level byte budget: evict largest-first until this fits
        if cache.max_bytes:
            over = cache.cached_bytes() + nbytes - cache.max_bytes
            if over > 0:
                self._evict(cache.plan_eviction(over))
        if not self._charge(sid, nbytes):
            # tenant quota pressure: recompute the biggest residents instead
            self._evict(cache.plan_eviction(nbytes))
            if not self._charge(sid, nbytes):
                return False
        cache.admit(self.app_id, key, sid, nbytes, structure)
        return True

    def _charge(self, sid: int, nbytes: int) -> bool:
        if self.tenants is None:
            return True
        try:
            self.tenants.charge(self.app_id, sid, nbytes)  #: balanced by release
            return True
        except TenantQuotaExceededError:
            return False

    def _evict(self, doomed) -> None:
        for e in doomed:
            self._drop_entry(e)
            if self.cache is not None:
                self.cache.note_eviction()

    def _drop_entry(self, entry) -> None:
        """Tear one cached shuffle down: manager unregister drops every tier
        (store, ServeCache decoded blocks, encoded-chunk pool) and fires the
        teardown hook that removes the cache entry; then refund the tenant."""
        self.manager.unregister_shuffle(entry.shuffle_id)
        if self.tenants is not None:
            self.tenants.release(entry.app_id, entry.nbytes)

    # -- local per-partition compute stages ----------------------------------

    def _run_aggregate(self, st: Stage, parts) -> List[List[Row]]:
        aggs = tuple(st.param("aggs", ("sum",)))
        out: List[List[Row]] = []
        for part in _as_partitions(parts):
            if not part:
                out.append([])
                continue
            keys = np.array([r[0] for r in part], np.uint32)
            vals = np.array([[r[1]] for r in part])
            uniq, cols, _counts = oracle_aggregate(keys, vals, aggs)
            out.append([(int(k), _scalar(cols[i, 0])) for i, k in enumerate(uniq)])
        return out

    def _run_join(self, st: Stage, build_parts, probe_parts) -> List[List[Row]]:
        join_type = str(st.param("join_type", "inner"))
        b, p = _as_partitions(build_parts), _as_partitions(probe_parts)
        if len(b) != len(p):
            raise ValueError(
                f"stage {st.name!r}: join sides have {len(b)} vs {len(p)} partitions"
            )
        out: List[List[Row]] = []
        for bp, pp in zip(b, p):
            bk = np.array([r[0] for r in bp], np.uint32)
            bv = np.array([[r[1]] for r in bp]) if bp else np.zeros((0, 1), np.int64)
            pk = np.array([r[0] for r in pp], np.uint32)
            pv = np.array([[r[1]] for r in pp]) if pp else np.zeros((0, 1), np.int64)
            joined = oracle_join(bk, bv, pk, pv, join_type)
            keys, brows, prows = joined[0], joined[1], joined[2]
            out.append(
                [
                    (int(k), _scalar(brows[i, 0]), _scalar(prows[i, 0]))
                    for i, k in enumerate(keys)
                ]
            )
        return out

    def _run_sort(self, st: Stage, upstream) -> List[Row]:
        rows = _flatten(upstream)
        if not rows:
            return []
        keys = np.array([r[0] for r in rows], np.uint32)
        payload = np.array([r[1] for r in rows])
        sk, sp = oracle_sort(keys, payload)
        return [(int(k), _scalar(v)) for k, v in zip(sk, sp)]


def _as_partitions(result) -> List[List[Row]]:
    if result and not isinstance(result[0], list):
        return [list(result)]  # flat input: one logical partition
    return list(result) if result else [[]]


def _flatten(result) -> List[Row]:
    if result and isinstance(result[0], list):
        return [row for part in result for row in part]
    return list(result)


def _scalar(v):
    """Native int/float for numpy scalars (keeps rows codec-serializable)."""
    f = float(v)
    return int(f) if f.is_integer() else f

"""TpuShuffleManager (L7/L6) — the plugin boundary.

Counterpart of ``UcxShuffleManager`` + ``CommonUcxShuffleManager``
(compat/spark_3_0/UcxShuffleManager.scala:25-80, CommonUcxShuffleManager.scala:37-124):
the single object a host engine (Spark via the JVM shim, or ``benchmark/run.py``)
instantiates to run shuffles.  API mirrors Spark's ``ShuffleManager`` SPI —
``register_shuffle`` / ``get_writer`` / ``get_reader`` / ``unregister_shuffle`` /
``stop`` — with the fork's staged-store components wired in the same places:

* construction starts the transport asynchronously like the reference's setup
  thread (CommonUcxShuffleManager.scala:45-62); here init is synchronous because
  there is no SparkEnv to spin-wait on,
* ``get_writer`` injects the staged-store writer
  (NvkvShuffleExecutorComponents.createMapOutputWriter,
  DpuShuffleExecutorComponents.scala:52-59),
* ``get_reader`` returns the windowed fetch reader
  (UcxShuffleManager.getReader, compat/spark_3_0/UcxShuffleManager.scala:55-60),
* writer commit triggers the resolver's block registration
  (writeIndexFileAndCommit hook) and the MapperInfo transport commit,
* ``run_exchange``/``exchange_ready`` expose the superstep boundary — the piece
  with no reference counterpart because UCX pulls blocks one by one while the
  TPU plane moves them in one collective (SURVEY.md section 7 "push/pull
  mismatch").
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, List, Optional

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import ExecutorLostError
from sparkucx_tpu.core.transport import ExecutorId
from sparkucx_tpu.memory.pool import MemoryPool
from sparkucx_tpu.shuffle.reader import TpuShuffleReader, default_deserializer
from sparkucx_tpu.shuffle.resolver import TpuShuffleBlockResolver, ring_neighbors
from sparkucx_tpu.shuffle.writer import TpuShuffleMapOutputWriter
from sparkucx_tpu.transport.tpu import TpuShuffleCluster


class TpuShuffleManager:
    """Single-controller manager: owns the cluster and per-executor components.

    **Task threads.**  ``get_writer`` and ``get_reader`` of one manager may be
    called from as many threads as the executor has task slots, and the
    writers and readers they return run side by side: each is its task's own
    object (one thread a writer, one a reader), and what they share — the
    store's regions and tables, the cluster's block tables and counters — is
    taken under its owner's lock.  On the write side the slots' map tasks
    copy into one store AT ONCE: a block takes its extent of staging under
    the store's lock and is copied into it outside the lock
    (``MapWriter.close_partition``; while the shuffle has one writer open
    the block keeps the lock through its copy: nobody can wait for it),
    and ``close_partition`` returns after the block's table record, so a
    task's commit follows the last byte of its last block.
    ``register_shuffle``, ``run_exchange`` and
    ``unregister_shuffle`` are the stage boundaries and stay the caller's to
    order: every map task committed before the exchange, every reader done
    before the removal.  Nothing here bounds the number of tasks in flight
    (``docs/DEPLOYMENT.md``, "Task slots")."""

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        num_executors: Optional[int] = None,
        mesh=None,
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        self.cluster = TpuShuffleCluster(self.conf, num_executors=num_executors, mesh=mesh)
        self.pool = MemoryPool(self.conf)
        self.pool.preallocate_from_conf()
        self.resolvers: List[TpuShuffleBlockResolver] = [
            TpuShuffleBlockResolver(self.conf, t) for t in self.cluster.transports
        ]
        self._shuffle_dims: Dict[int, tuple] = {}
        self._lock = threading.Lock()
        self._stopped = False
        self._unregister_hooks: List[Callable[[int], None]] = []

    @property
    def num_executors(self) -> int:
        return self.cluster.num_executors

    # -- ShuffleManager SPI -------------------------------------------------

    def register_shuffle(
        self,
        shuffle_id: int,
        num_mappers: int,
        num_reducers: int,
        map_owner: Optional[List[ExecutorId]] = None,
    ) -> None:
        """registerShuffle (SortShuffleManager base behavior the reference
        inherits; dependency bookkeeping only)."""
        meta = self.cluster.create_shuffle(shuffle_id, num_mappers, num_reducers, map_owner)
        with self._lock:
            self._shuffle_dims[shuffle_id] = (num_mappers, num_reducers, meta)

    def get_writer(self, shuffle_id: int, map_id: int) -> TpuShuffleMapOutputWriter:
        """getWriter (compat/spark_3_0/UcxShuffleManager.scala:32-53): returns the
        staged-store map-output writer for the executor owning this map task."""
        _, num_reducers, meta = self._dims(shuffle_id)
        owner = meta.map_owner[map_id]
        transport = self.cluster.transport(owner)
        # a hook, not a wrapper assigned over the writer's own method: that
        # closure held the writer and the writer held it, so every map task's
        # handle (and through it the shuffle's staging) waited for the
        # interpreter's full collection
        return TpuShuffleMapOutputWriter(
            transport.store, transport, shuffle_id, map_id, num_reducers,
            on_commit=functools.partial(
                self.resolvers[owner].on_map_committed, shuffle_id, map_id, num_reducers
            ),
        )

    def get_reader(
        self,
        shuffle_id: int,
        start_partition: int,
        end_partition: int,
        executor_id: Optional[ExecutorId] = None,
        deserializer: Callable = default_deserializer,
        aggregator=None,
        key_ordering: bool = False,
        merge_combiners=None,
    ) -> TpuShuffleReader:
        """getReader (compat/spark_3_0/UcxShuffleManager.scala:55-60).  The reduce
        range must be owned by one executor (contiguous ownership).

        ``executor_id`` is where the engine's scheduler placed the task.  Left
        out, the task runs where the exchange delivered its partitions — the
        owner of ``start_partition`` — and borrows its blocks from that
        executor's received shards.  Any other LIVE executor may be named: a
        task re-placed there, as an engine does with the tasks of an executor
        it lost after the exchange, never received its blocks and pulls each
        from the executor that staged it (the shuffle's map owners) or, that
        one being dead, from its ring successors' replicas
        (``replication_factor``) — the same records in the same order, at a
        copy a block.  Naming a dead executor is refused with
        ``ExecutorLostError``: nothing runs there.  (Left out, the owner is
        taken even while dead: the shards a recovery produced in its name lie
        with the survivors and are read as ever; the shards it received before
        it died went with it, and their reader raises ``ExecutorLostError`` at
        its first window — re-place the task.)"""
        num_mappers, _, meta = self._dims(shuffle_id)
        owner = meta.owner_of_reduce(start_partition)
        if executor_id is None:
            executor_id = owner
        elif not self.cluster.membership.is_alive(executor_id):
            raise ExecutorLostError(
                executor_id, self.cluster.membership.epoch,
                f"no reader of shuffle {shuffle_id} can be placed on it; alive: "
                f"{self.cluster.membership.alive()}",
            )
        transport = self.cluster.transport(executor_id)

        def block_sizes(m: int, r: int) -> int:
            info = meta.mapper_infos.get(m)
            return info.partitions[r][1] if info is not None else 0

        replica_of = None
        if self.conf.replication_factor > 0:
            # failover candidates derive from the same ring the replicator
            # pushes to — no placement-metadata exchange needed
            executors = list(range(self.cluster.num_executors))
            factor = self.conf.replication_factor

            def replica_of(primary):
                return ring_neighbors(primary, executors, factor)

        holders_of = None
        if self.conf.serve_hot_threshold_fetches_per_sec > 0:
            # popularity-aware load spreading: ask the primary who else holds
            # its hot blocks (HotSetPull), so reducers rotate across holders
            holders_of = getattr(transport, "hot_holders", None)

        return TpuShuffleReader(
            transport,
            executor_id,
            shuffle_id,
            start_partition,
            end_partition,
            num_mappers,
            block_sizes,
            max_blocks_per_request=self.conf.max_blocks_per_request,
            pool=self.pool,
            deserializer=deserializer,
            aggregator=aggregator,
            key_ordering=key_ordering,
            fetch_retries=self.conf.fetch_retries,
            credit_bytes=self.conf.wire_credit_bytes,
            replica_of=replica_of,
            holders_of=holders_of,
            fetch_deadline_ms=self.conf.fetch_deadline_ms,
            fetch_backoff_ms=self.conf.fetch_backoff_ms,
            fetch_hedge_ms=self.conf.fetch_hedge_ms,
            fetch_hedge_max_ms=self.conf.fetch_hedge_max_ms,
            memory_budget=self.conf.reduce_memory_budget,
            spill_dir=self.conf.spill_dir,
            merge_combiners=merge_combiners,
            # the pull path's primary for block (m, r) is the executor that
            # staged it, its replicas that executor's ring successors
            sender_of=meta.map_owner.__getitem__,
            received_by=owner,
        )

    def add_unregister_hook(self, fn: Callable[[int], None]) -> None:
        """Subscribe to shuffle teardown.  Hooks fire after the store tiers
        dropped the shuffle, so a subscriber (the query lineage cache) observing
        the callback can trust that no tier can still serve those blocks."""
        with self._lock:
            self._unregister_hooks.append(fn)

    def unregister_shuffle(self, shuffle_id: int) -> None:
        """unregisterShuffle -> resolver.removeShuffle
        (CommonUcxShuffleManager.scala:103-106)."""
        with self._lock:
            self._shuffle_dims.pop(shuffle_id, None)
            hooks = list(self._unregister_hooks)
        for resolver in self.resolvers:
            resolver.remove_shuffle(shuffle_id)
        # cluster-level metadata (store shuffles were removed via resolvers)
        self.cluster.drop_meta(shuffle_id)
        for fn in hooks:
            fn(shuffle_id)

    def stop(self) -> None:
        """stop() closes transports/resolvers (CommonUcxShuffleManager.scala:111-124)."""
        if self._stopped:
            return
        self._stopped = True
        for resolver in self.resolvers:
            resolver.stop()
        for t in self.cluster.transports:
            t.close()
        self.pool.close()

    # -- superstep boundary -------------------------------------------------

    def run_exchange(self, shuffle_id: int) -> None:
        """Run the collective superstep once all map tasks committed."""
        self.cluster.run_exchange(shuffle_id)

    def exchange_ready(self, shuffle_id: int) -> bool:
        meta = self._dims(shuffle_id)[2]
        return len(meta.mapper_infos) == meta.num_mappers

    # ----------------------------------------------------------------------

    def _dims(self, shuffle_id: int):
        with self._lock:
            dims = self._shuffle_dims.get(shuffle_id)
        if dims is None:
            raise KeyError(f"shuffle {shuffle_id} not registered")
        return dims

    def __enter__(self) -> "TpuShuffleManager":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

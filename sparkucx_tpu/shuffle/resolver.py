"""Block resolver (L4) — map-side commit hook + local block serving.

Counterpart of ``CommonUcxShuffleBlockResolver`` + the compat resolvers
(CommonUcxShuffleBlockResolver.scala:37-77, compat/spark_3_0/UcxShuffleBlockResolver.scala:28-97)
and of the vendored ``IndexShuffleBlockResolver``'s role as the block-id ->
bytes authority (IndexShuffleBlockResolver.scala:219-262).

Responsibilities:

* after a map task commits, register its blocks with the transport so the
  peer-serving path can serve them (writeIndexFileAndCommitCommon,
  CommonUcxShuffleBlockResolver.scala:37-61),
* ``get_block_data``: serve a local block either from the *staged store / post-
  exchange shard* (``serve_from_store=True``, the reference's DPU-fetch arm) or
  straight from the store's staging memory (the direct-NVKV arm) — the
  ``spark.dpuTest.enabled`` A/B switch (UcxShuffleBlockResolver.scala:86-97),
* track shuffles for cleanup (``removeShuffle`` -> ``unregisterShuffle``,
  CommonUcxShuffleBlockResolver.scala:63-77).
"""

from __future__ import annotations

import threading
from typing import List, Sequence, Set, Tuple

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import Block, ShuffleBlockId
from sparkucx_tpu.core.operation import BlockNotFoundError, TransportError
from sparkucx_tpu.core.transport import ShuffleTransport
from sparkucx_tpu.store.hbm_store import HbmBlockStore


def ring_neighbors(executor_id, executors: Sequence, factor: int) -> List:
    """The ``factor`` ring successors of ``executor_id`` in the sorted
    executor ring — where this executor's sealed rounds are replicated
    (``spark.shuffle.tpu.replication.factor``), and therefore where a reducer
    re-resolves a block when its primary dies.  Shared by the replicator
    (transport/peer.py) and the reader's failover path so both sides derive
    the same placement from membership alone, with no placement-metadata
    exchange (the redistribution-plan determinism of arXiv:2112.01075)."""
    ring = sorted(set(executors))
    if executor_id not in ring or len(ring) < 2 or factor <= 0:
        return []
    idx = ring.index(executor_id)
    out = []
    for k in range(1, min(factor, len(ring) - 1) + 1):
        out.append(ring[(idx + k) % len(ring)])
    return out


def widened_ring_neighbors(
    executor_id, executors: Sequence, base_factor: int, hot_factor: int
) -> Tuple[List, List]:
    """Ring placement for a popularity-promoted (hot) block's replica set:
    ``(base, extra)`` where ``base`` is the fault-tolerance floor
    (``ring_neighbors`` at ``replication.factor``) and ``extra`` the
    ADDITIONAL successors a hot promotion widens onto
    (``spark.shuffle.tpu.serve.hotReplicas``, never narrower than the
    floor).  Derived from membership alone — the same determinism contract
    as :func:`ring_neighbors`, so the promoting server, its peers, and any
    reader agree on the widened set without a placement exchange."""
    base = ring_neighbors(executor_id, executors, base_factor)
    widened = ring_neighbors(executor_id, executors, max(hot_factor, base_factor))
    extra = [e for e in widened if e not in base]
    return base, extra


def degraded_plan(num_executors: int, alive: Sequence) -> Tuple[int, List, int]:
    """Deterministic placement of an ``num_executors``-wide exchange onto the
    surviving executors: ``(m, phys, waves)`` where ``m`` is the pow2 floor of
    the survivor count, ``phys`` the first ``m`` survivors in sorted order
    (the shrunk mesh, one chip each), and ``waves = ceil(n / m)`` the number
    of sub-exchange passes.  Logical executor ``l`` is processed in wave
    ``l // m`` on physical slot ``l % m`` — contiguous waves, so each wave's
    receiver regions are contiguous slices of every sender's staging.

    Shared by the exchange re-planner (transport/tpu.py) and anything that
    must agree on where a lost executor's work landed, so — like
    ``ring_neighbors`` — every party derives the same placement from
    membership alone (the redistribution-scheduling determinism of
    arXiv:2112.01075 applied to replica->staging placement)."""
    survivors = sorted(set(alive))
    if not survivors:
        raise TransportError("no surviving executors to plan a degraded exchange on")
    m = 1 << (len(survivors).bit_length() - 1)  # pow2 floor
    phys = survivors[:m]
    waves = -(-num_executors // m)
    return m, phys, waves


class _StoreBackedBlock(Block):
    """A registered Block serving lazily from the staged store — the analogue of
    the file-backed positioned-read blocks the reference registers
    (CommonUcxShuffleBlockResolver.scala:37-61 FileBackedMemoryBlock)."""

    def __init__(self, store: HbmBlockStore, shuffle_id: int, map_id: int, reduce_id: int) -> None:
        super().__init__()
        self._store = store
        self._key = (shuffle_id, map_id, reduce_id)

    def get_size(self) -> int:
        return self._store.block_length(*self._key)

    def get_block(self, dest) -> None:
        import numpy as np

        payload = self._store.read_block(*self._key)
        view = np.frombuffer(dest, dtype=np.uint8) if not isinstance(dest, np.ndarray) else dest.reshape(-1).view(np.uint8)
        view[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)


class TpuShuffleBlockResolver:
    def __init__(
        self,
        conf: TpuShuffleConf,
        transport: ShuffleTransport,
    ) -> None:
        self.conf = conf
        self.transport = transport
        self._shuffles: Set[int] = set()  #: guarded by self._lock
        self._lock = threading.Lock()

    @property
    def store(self) -> HbmBlockStore:
        """The executor's store: the transport's own as it is NOW — an
        executor that rejoined has a new one (``TpuShuffleTransport.restart``)."""
        return self.transport.store

    def on_map_committed(self, shuffle_id: int, map_id: int, num_reducers: int) -> None:
        """Register each non-empty partition with the transport for peer serving
        (the writeIndexFileAndCommit hook, CommonUcxShuffleBlockResolver.scala:37-61)."""
        with self._lock:
            self._shuffles.add(shuffle_id)
        for r in range(num_reducers):
            if self.store.block_length(shuffle_id, map_id, r) > 0:
                self.transport.register(
                    ShuffleBlockId(shuffle_id, map_id, r),
                    _StoreBackedBlock(self.store, shuffle_id, map_id, r),
                )

    def get_block_data(self, shuffle_id: int, map_id: int, reduce_id: int) -> bytes:
        """Local serving of a block (IndexShuffleBlockResolver.getBlockData role).

        ``serve_from_store`` True -> read back through the staged store (the
        reference fetches back from the DPU); False -> same memory, but callers
        that bypass the store registry hit the registered Block instead
        (UcxShuffleBlockResolver.scala:86-97 A/B).

        An unknown shuffle/map raises the typed, addressed
        :class:`BlockNotFoundError` (never a bare KeyError), so callers can
        tell "retryable: not yet committed / peer lost" from programming
        errors."""
        if self.conf.serve_from_store:
            try:
                return self.store.read_block(shuffle_id, map_id, reduce_id)
            except BlockNotFoundError:
                raise
            except TransportError as e:
                if "unknown shuffle" in str(e):
                    raise BlockNotFoundError(shuffle_id, map_id, reduce_id, str(e)) from e
                raise
        blk = None
        if hasattr(self.transport, "registered_block"):
            blk = self.transport.registered_block(ShuffleBlockId(shuffle_id, map_id, reduce_id))
        if blk is None:
            raise BlockNotFoundError(shuffle_id, map_id, reduce_id, "not registered")
        return blk.get_memory_block().to_bytes()

    def replica_executors(self, primary_executor, executors: Sequence) -> List:
        """Where a block whose primary executor died can be re-resolved: the
        primary's replication-ring successors among ``executors`` (empty at
        ``replication.factor = 0``)."""
        return ring_neighbors(primary_executor, executors, self.conf.replication_factor)

    def remove_shuffle(self, shuffle_id: int) -> None:
        """removeShuffle -> unregister all the shuffle's blocks
        (CommonUcxShuffleBlockResolver.scala:63-77)."""
        with self._lock:
            self._shuffles.discard(shuffle_id)
        self.transport.unregister_shuffle(shuffle_id)
        self.store.remove_shuffle(shuffle_id)

    def stop(self) -> None:
        with self._lock:
            doomed = list(self._shuffles)
        for sid in doomed:
            self.remove_shuffle(sid)

"""TpuShuffleTransport — the real TPU data plane (L3b).

The counterpart of ``UcxShuffleTransport`` + ``UcxWorkerWrapper`` (790 LoC of
endpoint/AM machinery, UcxShuffleTransport.scala / UcxWorkerWrapper.scala), rebuilt
around the XLA collective model instead of RDMA active messages:

* The reference *pulls*: each reduce task sends ``FetchBlockReq`` per block and the
  DPU daemon replies with bytes (UcxShuffleClient.scala:17-47).  XLA collectives
  are bulk-synchronous, so this transport *batches*: all executors stage map output
  into their HBM store, then ONE ``shuffle superstep`` — the ragged all_to_all in
  ops/exchange.py — moves every block to its consuming executor at ICI line rate.
  ``fetch_blocks_by_block_ids`` afterwards is a local slice of the received shard:
  the fetch a reducer used to wait on over the wire becomes a zero-copy lookup.
  (This is the batching layer SURVEY.md section 7 calls out as the push/pull
  bridge.)
* A *pull fallback* remains for stragglers/retries: ``fetch_block`` reads a peer's
  staged store directly (the reference's per-block AM path, ids 3/4) — in
  single-controller mode an in-process read, in multi-process mode the peer socket
  server (transport/peer.py).
* ``progress()`` maps the reference's explicit UCX polling contract
  (ShuffleTransport.scala:158-165) onto JAX async dispatch: it polls outstanding
  XLA executions (``jax.Array.is_ready``) and fires callbacks, never blocking.
* Per-op stats are kept with the same content as ``UcxStats``
  (UcxShuffleTransport.scala:36-53): submit->completion ns and received bytes.

Single-controller topology: one ``TpuShuffleCluster`` owns the executor mesh and N
``TpuShuffleTransport`` facets (one per executor), mirroring how the reference runs
one ``UcxShuffleTransport`` per Spark executor bootstrapped by the driver
(CommonUcxShuffleManager.scala:67-99).  Multi-process SPMD wires the same facets
over ``jax.distributed`` + the control plane in parallel/bootstrap.py.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import Block, BlockId, MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import (
    BlockNotFoundError,
    ExecutorLostError,
    SplitBlockError,
    OperationCallback,
    OperationResult,
    OperationStats,
    OperationStatus,
    Request,
    TransportError,
)
from sparkucx_tpu.core.transport import ExecutorId, ShuffleTransport
from sparkucx_tpu.native import LandingPool
from sparkucx_tpu.parallel.membership import ClusterMembership
from sparkucx_tpu.parallel.mesh import executor_mesh, surviving_submesh
from sparkucx_tpu.ops.exchange import bucket_send_rows, gather_rows, rebucket_slots
from sparkucx_tpu.ops.planner import PlanContext, PlanSignals, make_planner
from sparkucx_tpu.ops.sort import key_lanes_of, key_order
from sparkucx_tpu.ops.skew import (
    ExchangePlan,
    chunk_size_rows,
    pad_rows_pow2,
    piece_slices,
    reassemble_round,
    slice_subround,
)
from sparkucx_tpu.shuffle.resolver import degraded_plan, ring_neighbors
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges, ram_round_budget
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.executor import (
    build_plan_exchange,
    execute_plan,
    validate_host_recv_mode,
)
from sparkucx_tpu.obs.metrics import (
    MetricsRegistry,
    counter_dict_provider,
    labelled_counter_provider,
    stats_aggregator_provider,
    tracer_provider,
)
from sparkucx_tpu.obs.recorder import FlightRecorder
from sparkucx_tpu.utils.stats import StatsAggregator
from sparkucx_tpu.utils.trace import TRACER, instant, merge_events, span


@dataclass
class _ShuffleMeta:
    """Cluster-wide shuffle metadata — the role of the DPU daemon's committed
    offset tables plus Spark's MapOutputTracker (which the reference leans on at
    UcxShuffleReader.scala:75-76)."""

    shuffle_id: int
    num_mappers: int
    num_reducers: int
    map_owner: List[ExecutorId]                      # map task -> executor
    peer_ranges: List[Tuple[int, int]]               # reducer ownership
    mapper_infos: Dict[int, MapperInfo] = field(default_factory=dict)
    #: per-peer staging region size in bytes, stashed at create_shuffle so
    #: block-offset math (_locate_rows) and the elastic restage path never
    #: have to reach into an executor's store — which may be dead.
    region_bytes: int = 0
    # post-exchange receive state, one entry per staging round (multi-round
    # spill; a single round in the common case), each per executor.  Entries
    # are plain arrays (host_recv_mode='array'), np.memmap views ('memmap'),
    # or absent entirely ('device' — fetches slice HBM on demand):
    recv_shards: Optional[List[List[np.ndarray]]] = None  # [round][executor] uint8
    recv_sizes: Optional[List[np.ndarray]] = None         # [round] (n, n) rows j<-i
    #: memmap backing (path, bytes) to unlink on remove_shuffle ('memmap'
    #: mode); sizes are tracked so the disk budget is refunded exactly.
    #: Appended from the pipeline DRAIN worker while the main thread may be
    #: tearing the shuffle down — mutate only under the cluster's lock.
    recv_spill_paths: List[Tuple[str, int]] = field(default_factory=list)  #: guarded by self._lock
    # HBM-resident copies of the received shards (conf.keep_device_recv) —
    # the source the device-side block gather serves from:
    recv_device: Optional[List[List[object]]] = None      # [round][executor] jax.Array
    exchanged: bool = False
    #: record_bytes -> records an ordered read of ONE reduce partition is
    #: sorted at (``TpuShuffleCluster._ordered_geometry``), worked out at the
    #: shuffle's first ordered read from the sealed size matrix
    ordered_capacity: Dict[int, int] = field(default_factory=dict)  #: guarded by self._lock
    #: executors that died holding their received shards of this exchanged
    #: shuffle (``TpuShuffleCluster.drop_received_of``): the entries of
    #: ``recv_shards`` / ``recv_device`` are gone and a read addressed to
    #: them is ``ExecutorLostError``
    recv_lost: set = field(default_factory=set)
    #: executors that were dead when a recovery produced this shuffle's
    #: received shards: the shards addressed to them lie with the survivors
    #: and outlive whatever happens to that id afterwards
    recv_adopted: frozenset = frozenset()

    def owner_of_reduce(self, reduce_id: int) -> ExecutorId:
        for p, (s, e) in enumerate(self.peer_ranges):
            if s <= reduce_id < e:
                return p
        raise ValueError(f"reduce_id {reduce_id} unowned")


#: a landing shorter than this is not worth keeping a block for (the
#: allocator's own lists serve it from pages the process holds already)
LANDING_MIN_BYTES = 1 << 20
#: ``TpuShuffleCluster._landing_pool`` before the first exchange decides it
_UNDECIDED = object()


def _d2h_copies(device) -> bool:
    """Does a plain ``np.asarray`` of an array of ``device`` copy?  Asked of
    the runtime itself, with a one-word array: on a chip it does — the bytes
    cross into a NumPy array the runtime allocates for that one
    ``jax.Array`` — on the CPU backend the array already lies in host memory
    and the answer is a view.  A runtime that cannot be asked is one whose
    landing stays as it was."""
    probe = jax.device_put(np.zeros(1, dtype=np.int32), device)
    try:
        return bool(probe._single_device_array_to_np_array_did_copy()[1])
    except AttributeError:
        return False


def _start_landing(prefix) -> None:
    """Start one received prefix on its way to the host, asynchronously: the
    runtime allocates the NumPy array the bytes land in HERE, from the
    calling thread's NumPy allocator, and ``np.asarray`` later waits for it."""
    prefix.copy_to_host_async()


def _wave_piece(src: np.ndarray, lo: int, hi: int, m: int, slot_rows: int, bucketed: int) -> np.ndarray:
    """One sender's piece of a sub-exchange of the degraded re-run: rows
    ``[lo, hi)`` of its sealed round — the regions of one wave of consumers —
    in the shrunk mesh's send layout, ``m`` slots of ``bucketed // m`` rows.
    Where the slot is the region and the wave is whole those rows ARE the
    piece, and it is a view of ``src``; else it is a copy: zero regions
    appended for the consumers a short last wave lacks (``m`` does not
    divide the executors), each region moved to its slot's start
    (``rebucket_slots``: a staging size whose region is no power of two)."""
    rows = src[lo:hi]
    short = m * slot_rows - (hi - lo)
    if short:
        rows = np.pad(rows, ((0, short), (0, 0)))
    return rebucket_slots(rows, m, bucketed)


@functools.partial(jax.jit, static_argnames=("record_lanes", "key_bytes", "flat"))
def ordered_records(table, *segments, record_lanes: int, key_bytes: int, flat: bool):
    """Slot-aligned gathered segments -> a reduce task's records in key order
    (the executable ``jit_ordered_records`` of a device trace; one a shuffle
    geometry, cached by shape inside ``jax.jit``).

    ``segments``: ``(rows, lane)`` int32 buffers in which every block starts
    on a slot boundary (``TpuShuffleCluster._ordered_geometry``), so each IS a
    ``(record places, record_lanes)`` array by reshape; ``table``: (2, B)
    int32, the first record place and the record count of every block — no
    two share a slot; an entry of no records is all zeros (places no block
    covers are padding, and hold whatever the block gather left there).
    Returns the first segment's count of places — every record of the task
    fits them — sorted by the records' first ``key_bytes`` bytes, equal keys
    in the order of their places (``ops.sort.key_order``), padding last and
    zero.  ``flat``: as ONE
    row-major 1-D array, the form that crosses to the host; else
    ``(capacity, record_lanes)``, which a TPU hands out column-major (a
    strided host array if it crosses).

    What it costs, by the executable's own device trace at TeraSort's shape
    (342,784 places of 25 lanes; ``scripts/probe_ordered_passes.py``, PR 55):
    2.93 ms, of which the row gather 1.25 and the sort 0.80.  Inside the
    executable a 25-lane array lies in rows of 128 lanes, 175 MB for 34 MB of
    records, and every pass over that form costs 0.23–0.53 ms, so there are
    three and no more: the reshape that makes it (0.33, what the row gather
    reads), the key lanes taken from it once (0.23) and the reshape of the
    gathered rows to the host's form (0.23).  The padding is zeroed in that
    1-D form (0.05)."""
    lane = segments[0].shape[1]
    slot_records = lane // math.gcd(record_lanes, lane)  # record places a slot
    # segments are joined as the 128-lane rows they are: joined as records
    # they would be copied at five times their bytes (a TPU pads 25 lanes to 128)
    rows = segments[0] if len(segments) == 1 else jnp.concatenate(segments)
    records = rows.reshape(-1, record_lanes)
    capacity = segments[0].size // record_lanes
    # a block starts on a slot boundary and no two share a slot, so a place is
    # covered iff its offset in its slot is under the slot's count of records:
    # (slots, B) compares and ONE compare a place, not (places, B)
    first, count = table[0][None, :], table[1][None, :]
    base = (jnp.arange(records.shape[0] // slot_records, dtype=jnp.int32) * slot_records)[:, None]
    covered = jnp.where(first <= base, jnp.clip(first + count - base, 0, slot_records), 0).max(axis=1)
    valid = (jnp.arange(slot_records, dtype=jnp.int32)[None, :] < covered[:, None]).reshape(-1)
    order, n = key_order(records[:, : key_lanes_of(key_bytes)].T, valid, key_bytes)
    # every record of the task fits the first segment's places: the gather
    # fetches those and no more.  What an uncovered place holds is the block
    # gather's to leave unspecified, and the padding comes out zero whatever
    # it is: only output places [n, capacity) can hold it
    ordered = gather_rows(records, order[:capacity])
    if flat:  # the form the host takes, zeroed at its own 34 MB and not at the rows' 175
        ordered, n = ordered.reshape(-1), n * record_lanes
    live = jnp.arange(ordered.shape[0], dtype=jnp.int32) < n
    return jnp.where(live if flat else live[:, None], ordered, 0)


class _MeshChanged(Exception):
    """Internal abort signal: cluster membership changed under an in-flight
    exchange.  Never escapes ``run_exchange`` — it either converts into a
    degraded re-plan (elastic.enabled + replicas available) or into a typed
    ``ExecutorLostError``."""

    def __init__(self, epoch0: int, snapshot: dict) -> None:
        self.epoch0 = epoch0
        self.snapshot = snapshot
        super().__init__(f"membership epoch {epoch0} -> {snapshot['epoch']}")


class TpuShuffleCluster:
    """Owns the executor mesh, the compiled exchange, and shuffle metadata."""

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        num_executors: Optional[int] = None,
        mesh=None,
    ) -> None:
        self.conf = conf or TpuShuffleConf()
        n = num_executors or self.conf.num_executors
        # ICI-neighbour order where the backend exposes chip coords (TPU);
        # backend order otherwise (the CPU test mesh)
        self.mesh = mesh if mesh is not None else executor_mesh(n, self.conf.mesh_axis_name)
        self.num_executors = int(self.mesh.devices.size)
        devices = list(self.mesh.devices.reshape(-1))
        self.transports: List[TpuShuffleTransport] = [
            TpuShuffleTransport(self, eid, device=devices[eid]) for eid in range(self.num_executors)
        ]
        #: the exchange planner (ops/planner.py): conf.planner_mode selects
        #: the legacy-1:1 static mapping or the telemetry-fed adaptive one
        self.planner = make_planner(self.conf)
        self._meta: Dict[int, _ShuffleMeta] = {}  #: guarded by self._lock
        self._exchange_cache: Dict[Tuple[int, int, str], Callable] = {}  #: guarded by self._lock
        #: jitted received-prefix slices by bucket rows (_prefix_fn)
        self._prefix_cache: Dict[int, Callable] = {}  #: guarded by self._lock
        #: the blocks received shards land in, or None (_landing): decided
        #: at the first exchange with a host receive mode
        self._landing_pool = _UNDECIDED  #: guarded by self._lock
        self._lock = threading.RLock()
        #: aggregate per-stage pipeline/exchange timings (occupancy view)
        self.stats = StatsAggregator()
        #: bytes of received-shard spill currently on disk (host_recv_mode=
        #: 'memmap'), charged against conf.spill_disk_cap_bytes like the
        #: store's staging spill; the drain worker charges, teardown refunds
        self._recv_spill_bytes = 0  #: guarded by self._lock
        #: Device-read counters (the ``deviceread`` metrics family), one row
        #: an executor: plain ints, always on, bumped once a
        #: ``fetch_blocks_to_device`` call (a reduce task's ``read_device()``),
        #: never a block.  ``gathers`` is gather dispatches (one a staging
        #: round the task's blocks lie in), ``rows`` payload rows gathered.
        self._device_read_stats: List[Dict[str, int]] = [
            dict.fromkeys(("tasks", "blocks", "rows", "bytes", "gathers", "locate_ns"), 0)
            for _ in range(self.num_executors)
        ]  #: guarded by self._lock
        #: Ordered-read counters (the ``orderedread`` metrics family), one row
        #: an executor, bumped once a ``fetch_blocks_ordered`` /
        #: ``ordered_to_host`` call (a reduce task), never a record:
        #: ``records`` / ``bytes`` the task's own, ``capacity_records`` what
        #: they were sorted at, ``sort_dispatches`` calls of the ordering
        #: executable (one a non-empty task), ``d2h_bytes`` / ``d2h_ns`` the
        #: ordered array's one transfer to the host (``read_batches()`` only).
        #: Tasks side by side (an executor's task slots): the gauges
        #: ``in_flight`` / ``in_flight_device_bytes`` — ordered reads between
        #: their gather's dispatch and the end of their D2H (``read_batches()``)
        #: or their hand-out (``read_device()``), and what they hold on the
        #: device meanwhile: each one's gathered segments and its sorted array,
        #: by shape — and the high-water marks ``in_flight_peak`` /
        #: ``in_flight_device_bytes_peak``.  Nothing bounds them but the caller.
        self._ordered_read_stats: List[Dict[str, int]] = [
            dict.fromkeys(
                ("tasks", "records", "bytes", "capacity_records", "sort_dispatches", "d2h_bytes", "d2h_ns",
                 "in_flight", "in_flight_device_bytes", "in_flight_peak", "in_flight_device_bytes_peak"), 0
            )
            for _ in range(self.num_executors)
        ]  #: guarded by self._lock
        #: ``id`` of an ordered read's device array -> (executor, device bytes
        #: it holds in flight), from ``fetch_blocks_ordered`` until
        #: ``ordered_to_host`` / ``ordered_handed_out`` takes it out
        self._ordered_held: Dict[int, Tuple[int, int]] = {}  #: guarded by self._lock
        #: Liveness/epoch layer.  Always constructed (it is just bookkeeping);
        #: with elastic.enabled=false nothing ever reports a death through it,
        #: the epoch stays 0, and every code path below is byte-identical to
        #: the pre-elastic behavior.
        self.membership = ClusterMembership(
            range(self.num_executors), self.conf.membership_suspect_after_ms
        )
        #: degraded-mode recovery telemetry (the chaos tests and the metrics
        #: registry read this; its numbers are the ``elastic`` family of
        #: ``metrics_text()``).  Once an exchange with replication on:
        #: ``replicated_rounds`` / ``replicated_bytes`` (sealed rounds copied
        #: to a ring successor, and their unpadded block bytes),
        #: ``replicate_ns``, ``replica_copied_bytes`` (bytes copied while
        #: replicating: ``replicated_bytes`` where a byte is written once) and
        #: ``replica_landing_hits`` / ``replica_landing_misses`` (replica
        #: arrays that came from a block the landing pool kept / did not;
        #: 0 / 0 where there is no pool).  Once a recovery: ``recoveries``,
        #: ``recover_ns``, ``restaged_blocks`` / ``restaged_bytes`` (blocks of
        #: dead executors rebuilt from replicas), ``degraded_subexchanges``
        #: (collectives dispatched on the shrunk mesh) and what their submits
        #: handed the devices: ``recover_direct_bytes`` (host bytes put as
        #: views of a sealed or restaged round), ``recover_copied_bytes``
        #: (bytes through the one copy into the shrunk mesh's layout) and
        #: ``recover_zero_pieces`` (pieces made on the device).  Once a kill:
        #: ``lost_recv_shards`` / ``lost_recv_bytes``, the received shards of
        #: exchanged shuffles (host, mapped and device bytes) that died with
        #: their executor (``drop_received_of``).
        self.elastic_stats = {
            "recoveries": 0,
            "last_recovery_ms": 0.0,
            "last_epoch": 0,
            "degraded_mesh": None,
            "restaged_blocks": 0,
            "restaged_bytes": 0,
            "degraded_subexchanges": 0,
            "replicated_rounds": 0,
            "replicated_bytes": 0,
            "replicate_ns": 0,
            "replica_copied_bytes": 0,
            "replica_landing_hits": 0,
            "replica_landing_misses": 0,
            "recover_ns": 0,
            "recover_direct_bytes": 0,
            "recover_copied_bytes": 0,
            "recover_zero_pieces": 0,
            "lost_recv_shards": 0,
            "lost_recv_bytes": 0,
        }  #: guarded by self._lock
        #: Obs plane (PR 14): cluster-level registry + flight recorder.  The
        #: registry absorbs the collective plane's surfaces (exchange timings,
        #: elastic recovery counters, the trace ring's health); per-executor
        #: wire surfaces live in each PeerTransport's own registry.  The
        #: recorder does NOT install the global TransportError hook — clusters
        #: have no close() to unhook from, and PeerTransports already cover
        #: the wire error path — it captures on the cluster's own fault paths
        #: (elastic recovery, chaos kills) explicitly.
        self.metrics = MetricsRegistry()
        self.metrics.register("ops", stats_aggregator_provider(self.stats))
        self.metrics.register(
            "elastic", counter_dict_provider("elastic", self._elastic_snapshot)
        )
        self.metrics.register("obs", tracer_provider(TRACER))
        self.metrics.register(
            "store",
            labelled_counter_provider(
                "store", "executor", lambda: [t.store.write_stats() for t in self.transports]
            ),
        )
        self.metrics.register(
            "deviceread",
            labelled_counter_provider("deviceread", "executor", self.device_read_stats),
        )
        self.metrics.register(
            "orderedread",
            labelled_counter_provider(
                "orderedread", "executor", self.ordered_read_stats,
                gauges=("in_flight", "in_flight_device_bytes"),
            ),
        )
        self.recorder = FlightRecorder(
            TRACER,
            postmortem_dir=self.conf.obs_postmortem_dir or None,
            ring_capacity=self.conf.obs_ring_capacity,
        )
        self.recorder.attach_registry(self.metrics)
        self.recorder.attach_membership(self.membership.snapshot)

    # -- membership / lookup ----------------------------------------------

    def transport(self, executor_id: ExecutorId) -> "TpuShuffleTransport":
        return self.transports[executor_id]

    def meta(self, shuffle_id: int) -> _ShuffleMeta:
        with self._lock:
            m = self._meta.get(shuffle_id)
        if m is None:
            raise TransportError(f"unknown shuffle {shuffle_id}")
        return m

    # -- obs plane ---------------------------------------------------------

    def _elastic_snapshot(self) -> Dict[str, float]:
        """Numeric view of the elastic telemetry for the metrics registry
        (the degraded-mesh tuple is for tests, not exposition)."""
        with self._lock:
            s = {k: v for k, v in self.elastic_stats.items() if isinstance(v, (int, float))}
        s["epoch"] = self.membership.epoch
        s["alive"] = len(self.membership.alive())
        s["dead"] = len(self.membership.dead())
        return s

    def export_trace(self, path: str, extra_buffers: Optional[List[List[dict]]] = None) -> int:
        """Merge every executor's trace events into ONE Perfetto file with
        pid = executor id; returns the event count.  Single-controller
        executors share the process-wide TRACER (tracks split by the
        ``executor_scope`` eid tag); multi-process meshes gather peer buffers
        over TRACE_PULL (``PeerTransport.pull_trace``) and pass the ``events``
        lists in via ``extra_buffers``."""
        import json as _json

        buffers = [TRACER.events]
        buffers.extend(extra_buffers or [])
        merged = merge_events(buffers)
        with open(path, "w") as f:
            _json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
        return len(merged)

    def executed_lowerings(self) -> Dict[str, List[str]]:
        """The lowering of every executable this cluster has compiled, by
        kind — what ran (``fn.spec.impl`` for exchanges, ``fn.impl`` for block
        gathers and for the block scatters of the stores' device write path),
        not what the conf asked for.  ``benchmark/`` holds a run's ``correct``
        to it."""
        out: Dict[str, List[str]] = {"exchange": [], "gather": [], "scatter": []}
        with self._lock:
            for key, fn in self._exchange_cache.items():
                if key[0] == "gather":
                    out["gather"].append(fn.impl)
                else:
                    out["exchange"].append(fn.spec.impl)
        for t in self.transports:
            out["scatter"].extend(t.store.scatter_lowerings())
        return out

    def device_read_stats(self) -> List[Dict[str, int]]:
        """The device-read counters, one row an executor (its ``executor`` id
        in the row) — the ``deviceread`` metrics family."""
        with self._lock:
            return [{"executor": e, **row} for e, row in enumerate(self._device_read_stats)]

    def ordered_read_stats(self) -> List[Dict[str, int]]:
        """The ordered-read counters, one row an executor — the
        ``orderedread`` metrics family."""
        with self._lock:
            return [{"executor": e, **row} for e, row in enumerate(self._ordered_read_stats)]

    def metrics_text(self) -> str:
        """The cluster registry's Prometheus exposition (collective-plane
        surfaces; per-executor wire surfaces are each peer's METRICS_PULL)."""
        return self.metrics.prometheus_text()

    # -- shuffle lifecycle -------------------------------------------------

    def create_shuffle(
        self,
        shuffle_id: int,
        num_mappers: int,
        num_reducers: int,
        map_owner: Optional[Sequence[ExecutorId]] = None,
        capacity: Optional[int] = None,
    ) -> _ShuffleMeta:
        """Declare a shuffle cluster-wide: reducer ownership is contiguous ranges
        over executors; map tasks are assigned round-robin unless given.
        ``capacity`` overrides ``conf.staging_capacity_per_executor`` for this
        shuffle only — right-sizing small shuffles; capacity bucketing in
        ``_exchange_fn`` keeps nearby sizes on one compiled exchange."""
        n = self.num_executors
        owners = list(map_owner) if map_owner is not None else [m % n for m in range(num_mappers)]
        if len(owners) != num_mappers:
            raise ValueError("map_owner length != num_mappers")
        ranges = default_peer_ranges(num_reducers, n)
        meta = _ShuffleMeta(shuffle_id, num_mappers, num_reducers, owners, ranges)
        with self._lock:
            if shuffle_id in self._meta:
                raise TransportError(f"shuffle {shuffle_id} already exists")
            self._meta[shuffle_id] = meta
        for t in self.transports:
            t.store.create_shuffle(
                shuffle_id, num_mappers, num_reducers, peer_ranges=ranges, capacity=capacity
            )
        meta.region_bytes = self.transports[0].store.region_bytes(shuffle_id)
        return meta

    def remove_shuffle(self, shuffle_id: int) -> None:
        self.drop_meta(shuffle_id)
        for t in self.transports:
            t.store.remove_shuffle(shuffle_id)

    def drop_meta(self, shuffle_id: int) -> None:
        """Forget the cluster-level state of a shuffle and release what it
        holds — for callers whose resolvers remove the per-store state
        themselves (the unregisterShuffle split,
        CommonUcxShuffleManager.scala:103-106).  The received shards are
        released HERE, not at the interpreter's next collection: the HBM
        copies (``recv_device``, gigabytes a shuffle under
        ``keep_device_recv``) and the host or memmap copies are dropped from
        the meta object, which a reader's closure may keep alive long after;
        the HBM bytes are counted as ``released_device_bytes`` of the owning
        executor's ``store`` family."""
        with self._lock:
            meta = self._meta.pop(shuffle_id, None)
        if meta is None:
            return
        recv_device, meta.recv_device = meta.recv_device, None
        for rnd in recv_device or ():
            for t, shard in zip(self.transports, rnd):
                t.store.count_released_device(shard)
        del recv_device
        meta.recv_shards = None  # drop memmap views before unlinking
        for path, size in meta.recv_spill_paths:
            self._unlink_recv_spill(path, size)

    def _unlink_recv_spill(self, path: str, size: int) -> bool:
        """Remove one received-shard spill file and refund its disk budget;
        False — still on disk, still charged — where it could not be removed."""
        import os

        try:
            os.unlink(path)
        except FileNotFoundError:
            pass  # already gone: the bytes are not on disk
        except OSError:
            return False
        with self._lock:
            self._recv_spill_bytes -= size
        return True

    def commit_mapper(self, info: MapperInfo) -> None:
        """AM id 2 sink — the cluster is the 'daemon' holding the commit table."""
        meta = self.meta(info.shuffle_id)
        with self._lock:
            meta.mapper_infos[info.map_id] = info

    # -- the superstep -----------------------------------------------------

    @property
    def row_bytes(self) -> int:
        return self.conf.block_alignment

    def _exchange_fn(self, send_rows: int, lowering: Optional[str] = None):
        # Capacity bucketing: round the per-peer slot up to the next power of
        # two so shuffles of varying staging size share one compiled
        # executable per bucket (the caller relocates payloads into the
        # bucketed slot layout; padding rows carry zero sizes and never cross
        # the wire under the ragged lowering).  ``lowering`` is the plan's
        # collective tier (defaults to the conf knob); a key miss lowers
        # through the shared build_plan_exchange dispatch.
        send_rows = bucket_send_rows(send_rows, self.num_executors)
        from sparkucx_tpu.ops.ici_exchange import resolve_exchange_impl

        impl = resolve_exchange_impl(
            lowering or self.conf.exchange_impl,
            self.mesh.devices.reshape(-1)[0].platform,
            self.num_executors,
        )
        key = (
            self.num_executors, send_rows, self.row_bytes,
            self.conf.num_slices, impl,
        )
        with self._lock:
            fn = self._exchange_cache.get(key)
            if fn is None:
                fn = build_plan_exchange(
                    self.mesh,
                    num_executors=self.num_executors,
                    send_rows=send_rows,
                    lane=self.row_bytes // 4,
                    axis_name=self.conf.mesh_axis_name,
                    impl=impl,
                    num_slices=self.conf.num_slices,
                )
                self._exchange_cache[key] = fn
        return fn

    def _received_prefix(self, shard, used_rows: int):
        """What of one received shard crosses back to the host: ``None`` when
        nothing was received (no D2H at all), the shard itself when the
        bucket of its used rows reaches the shard (no extra device op), else
        the device-side slice ``shard[:bucket]``.  The exchange leaves a
        tight sender-major prefix of ``used_rows`` in the shard and no reader
        addresses a row past it; ``used_rows`` is the size matrix's column
        sum, host metadata known at submit.  The bucket is the next power of
        two of rows and at least a sixteenth of the shard: below that a
        transfer is already short, and a shorter one would only buy another
        executable — so a shard shape has four slices at most, and shuffles
        of wandering size share them."""
        if used_rows <= 0:
            return None
        shard_rows = int(shard.shape[0])
        bucket = max(1 << (used_rows - 1).bit_length(), shard_rows >> 4)
        if bucket >= shard_rows:
            return shard
        return self._prefix_fn(bucket)(shard)

    def _prefix_fn(self, bucket: int):
        """The jitted slice ``shard[:bucket]`` (``jit_recv_prefix``), one a
        bucket; its executables are cached by shape and device inside it."""
        with self._lock:
            fn = self._prefix_cache.get(bucket)
            if fn is None:

                def recv_prefix(shard):
                    return jax.lax.slice_in_dim(shard, 0, bucket)

                fn = self._prefix_cache[bucket] = jax.jit(recv_prefix)
        return fn

    def _landing(self) -> Optional[LandingPool]:
        """Where received shards land, decided once a cluster from what the
        runtime says: where a plain ``np.asarray`` of a device's array copies
        (``_d2h_copies``: a chip), every landing is a NumPy array the runtime
        allocates anew — at 64 MiB a fresh mapping, first touched by the
        runtime's copy and unmapped again when the shuffle goes — so the
        submit lane allocates them from a ``LandingPool``: a block comes back
        when the last view of its shard is dropped and the next shard of that
        size lands in it, pages this process already holds.  Kept under the
        store's own rule for host pages held between jobs
        (``ram_round_budget``), an executor's figure each; past it a shard
        lands in fresh pages as before.  ``None`` — the landing of before —
        on the CPU backend, where an array already lies in host memory, and
        where the native library or NumPy's allocator hook is missing."""
        with self._lock:
            if self._landing_pool is _UNDECIDED:
                self._landing_pool = None
                if all(_d2h_copies(d) for d in self.mesh.devices.reshape(-1)):
                    self._landing_pool = LandingPool.create(
                        ram_round_budget(self.conf) * self.num_executors, LANDING_MIN_BYTES
                    )
            return self._landing_pool

    def run_exchange(self, shuffle_id: int) -> None:
        """Seal every executor's staging for this shuffle and run ONE collective
        superstep.  After this, every block is resident on its consuming
        executor and fetches are local."""
        with span("exchange.superstep", shuffle_id=shuffle_id):
            self._run_exchange(shuffle_id)

    def _run_exchange(self, shuffle_id: int) -> None:
        meta = self.meta(shuffle_id)
        if meta.exchanged:
            raise TransportError(f"shuffle {shuffle_id} already exchanged")
        committed = len(meta.mapper_infos)
        if committed != meta.num_mappers:
            raise TransportError(
                f"exchange before all maps committed ({committed}/{meta.num_mappers})"
            )

        # ONE host_recv_mode gate (transport/executor.py) — an unknown mode
        # is rejected here, before any staging allocation, with the same
        # vocabulary and error text as the SPMD executor's gate.
        mode = validate_host_recv_mode(self.conf.host_recv_mode)
        if mode == "device" and not self.conf.keep_device_recv:
            raise TransportError(
                "host_recv_mode='device' serves fetches from the HBM shards — "
                "it requires conf.keep_device_recv=true"
            )

        with span("exchange.seal", shuffle_id=shuffle_id):
            sealed = [t.store.seal(shuffle_id) for t in self.transports]
        num_rounds = max(len(s) for s in sealed)
        first_payload = sealed[0][0][0]
        send_rows, lane = int(first_payload.shape[0]), int(first_payload.shape[1])
        # Every executor's every round must share one (rows, lane) shape — the
        # assembly below slices the global array at bucketed-row strides, so a
        # divergent store geometry would mis-slice silently, not fail.
        for eid, s in enumerate(sealed):
            for rnd, (payload, _) in enumerate(s):
                shape = (int(payload.shape[0]), int(payload.shape[1]))
                if shape != (send_rows, lane):
                    raise TransportError(
                        f"executor {eid} sealed round {rnd} with shape {shape}, "
                        f"expected {(send_rows, lane)} — mismatched staging "
                        "geometry (stagingCapacity/blockAlignment) across executors"
                    )
        n = self.num_executors
        staging_slot = send_rows // n
        # Plan context from the sealed size matrices (metadata-before-data:
        # the planner never sees payload bytes), plus the local telemetry
        # snapshot for the serve-plane decisions and the plan span.
        round_maxes = tuple(
            max(
                (int(np.max(s[rnd][1], initial=0)) for s in sealed if rnd < len(s)),
                default=0,
            )
            for rnd in range(num_rounds)
        )
        # the job's (sender, destination) lanes in rows, every round summed:
        # what the key law did to the exchange, read off the same matrices
        lanes = np.zeros((n, n), dtype=np.int64)
        for sender, s in enumerate(sealed):
            for _, size_rows in s:
                lanes[sender] += size_rows
        used_total, lane_max = int(lanes.sum()), int(lanes.max())
        recv_rows = [int(rows) for rows in lanes.sum(axis=0)]
        signals = PlanSignals.from_registry(self.metrics)
        ctx = PlanContext(
            num_executors=n,
            staging_slot_rows=staging_slot,
            round_max_rows=round_maxes,
            used_rows_total=used_total,
            row_bytes=self.row_bytes,
            platform=self.mesh.devices.reshape(-1)[0].platform,
            # raw block shuffles carry no aggregation geometry: agg_partial
            # stays False, so the planner's combine tier resolves to 'off'
            # (the fused fold only applies to partial-aggregate exchanges —
            # ops/relational.py fills these fields on that path)
            signals=signals,
        )
        plan = self.planner.plan(ctx)
        instant(
            "exchange.plan",
            shuffle_id=shuffle_id,
            planner=type(self.planner).__name__,
            **plan.describe(),
            **{f"signal_{k}": v for k, v in signals.describe().items()},
            recv_rows=recv_rows,
            lane_rows_max=lane_max,
            lane_rows_mean=used_total / lanes.size,
        )
        # Counters ``exchange.plan``, once an exchange: rows received by each
        # executor, and the job's hottest and mean lane (sums over the
        # ``exchanges`` counted: one shuffle's are the values themselves).
        self.stats.record_counters(
            "exchange.plan",
            exchanges=1,
            lane_rows_max=lane_max,
            lane_rows_mean=used_total // lanes.size,
            **{f"recv_rows_e{j}": rows for j, rows in enumerate(recv_rows)},
        )

        q = plan.slot_rows
        bucketed = q * n  # staged rows per executor (n slots x the plan slot)
        fn = self._exchange_fn(bucketed, plan.lowering)
        # a sealed round already on its executor's device is donated as it is
        # where the plan's slot is the staging slot
        donates = plan.single_shot and q == staging_slot

        def release_early_rounds():
            """The copies of completed rounds the stores put on their devices
            before the seal (``HbmBlockStore.take_early_round``) that no
            submit took are let go: the host rounds are what is read from
            here on."""
            for t in self.transports:
                t.store.release_early_rounds(shuffle_id)

        if not donates:
            # a window of such a copy would have to be cut on the device, an
            # executable a shape: the host rounds are put as ever
            release_early_rounds()

        # Elastic prep: snapshot the membership epoch the plan was built
        # against, and (when replication is on) copy each executor's sealed
        # rounds to its ring successors so a mid-superstep death is
        # recoverable.  Degraded recovery covers single-shot plans only (the
        # historical quota-off engine); chunked plans fail fast with a typed
        # error, exactly like the retired quota engine.
        epoch0 = self.membership.epoch
        pool = self._landing() if mode != "device" else None
        if plan.single_shot and self.conf.elastic and self.conf.replication_factor >= 1:
            with span("exchange.replicate", shuffle_id=shuffle_id) as replicate:
                copied = self._replicate_sealed(shuffle_id, pool)
                if replicate is not None:
                    replicate.args.update(copied)

        def _mesh_changed() -> Optional[_MeshChanged]:
            if self.membership.epoch != epoch0:
                return _MeshChanged(epoch0, self.membership.snapshot())
            return None

        ax = self.conf.mesh_axis_name
        data_sharding = NamedSharding(self.mesh, P(ax, None))
        devices = list(self.mesh.devices.reshape(-1))
        keep_device = self.conf.keep_device_recv

        def _submit(rnd, chunk, nchunks):
            """One sub-round's assemble + H2D + collective dispatch + async
            D2H kick-off.  Everything here is JAX async dispatch: this
            sub-round's collective is still in flight when the next one
            assembles.  The round's global send array is made from one
            device-resident piece per executor, never from a global host
            buffer.  Three child spans of ``exchange.pipeline.submit``, once
            a round and chunk: ``exchange.assemble`` (choosing and slicing
            each executor's piece: the round's copy on its device where the
            store put it there before the seal (``take_early_round``), else a
            view of its sealed round wherever the plan's slot is the staging
            slot, else the one copy ``slice_subround`` makes),
            ``exchange.h2d`` (the time the per-executor ``device_put`` calls
            — of the rounds that are still on the host: none where every one
            was put early — and the global array's construction hold this
            lane — NOT the DMA, which is asynchronous) and
            ``exchange.collective`` (the dispatch).  Counters
            ``exchange.assemble``: ``direct_bytes`` (host bytes handed to
            ``device_put`` as views of a sealed round), ``copied_bytes``
            (host bytes that went through a pad / chunk-window copy first)
            and, on a submit that found one, ``early_bytes`` (bytes of rounds
            found on their device: no host byte of them moves in the exchange);
            where a piece's source is a mapping of the store's disk tier
            (``np.memmap``) also ``disk_rounds`` / ``disk_bytes``, and its put
            is ``exchange.h2d``'s child ``exchange.h2d.disk``."""
            faults.check("exchange.submit", shuffle_id=shuffle_id, round=rnd)
            if self.membership.epoch != epoch0:
                if plan.single_shot:
                    raise _MeshChanged(epoch0, self.membership.snapshot())
                snap = self.membership.snapshot()
                dead = sorted(snap["dead"])
                raise ExecutorLostError(
                    dead[0] if dead else -1,
                    snap["epoch"],
                    "executor lost mid-exchange; degraded recovery does not "
                    "cover the quota-capped engine (slot_quota_rows > 0) — "
                    f"dead: {dead}",
                )
            payloads, size_rows = [], []
            direct_bytes = copied_bytes = early_bytes = 0
            for t, s in zip(self.transports, sealed):
                if rnd < len(s):
                    payload = s[rnd][0]
                    if donates and not isinstance(payload, jax.Array):
                        # the store's own copy of the round on its device,
                        # put when the round became final: nothing to put here
                        early = t.store.take_early_round(shuffle_id, rnd)
                        if early is not None:
                            payload = early
                            early_bytes += int(early.nbytes)
                    payloads.append(payload)
                    size_rows.append(s[rnd][1])
                else:  # executor had fewer spill rounds: empty contribution
                    payloads.append(None)
                    size_rows.append(np.zeros(n, dtype=np.int32))
            sub_sizes = np.stack([chunk_size_rows(sr, chunk, q) for sr in size_rows])
            on_disk = [isinstance(p, np.memmap) for p in payloads]  # the store's disk tier
            with span(
                "exchange.assemble",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk,
                bytes=n * bucketed * self.row_bytes,
            ):
                pieces = []
                for p in payloads:
                    if p is None:
                        piece = None  # a zero piece, made on its device below
                    elif isinstance(p, jax.Array):
                        # Sealed straight onto its executor's device: donate
                        # as-is when the bucket is the staging slot (the
                        # historical single-shot no-copy fast path), else
                        # relocate / slice the chunk window on that device.
                        if donates:
                            piece = p
                        else:
                            piece = slice_subround(p, n, chunk, q, xp=jnp)
                    else:
                        # np.asarray strips the spill tier's np.memmap
                        # subclass without copying: the runtime sees a plain
                        # ndarray.
                        host = np.asarray(p)
                        piece = slice_subround(host, n, chunk, q)
                        if np.may_share_memory(piece, host):
                            direct_bytes += piece.nbytes
                        else:  # a pad or a strided chunk window: the one copy
                            copied_bytes += piece.nbytes
                    pieces.append(piece)
            assembled = dict(direct_bytes=direct_bytes, copied_bytes=copied_bytes)
            if early_bytes:  # rounds found on their devices: no host byte of them moves here
                assembled.update(early_bytes=early_bytes)
            if any(on_disk):  # pieces whose source is a mapping of the store's disk tier
                disk_bytes = sum(piece.nbytes for piece, disk in zip(pieces, on_disk) if disk)
                assembled.update(disk_rounds=sum(on_disk), disk_bytes=disk_bytes)
            self.stats.record_counters("exchange.assemble", **assembled)
            # What this span measures is the time the device_put calls hold
            # the submit lane (runtime staging copy + enqueue), not the DMA:
            # the transfer itself is asynchronous and is no XLA op.
            disk_marks = []
            with span(
                "exchange.h2d",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk,
                bytes=direct_bytes + copied_bytes + sub_sizes.size * 4,
            ) as h2d:
                for i, piece in enumerate(pieces):
                    if piece is None:
                        # Made on the device, no host bytes; and fresh every
                        # submit, because the exchange donates its argument 0
                        # when send_rows == recv_rows: a cached zero piece
                        # would be invalidated by the first round to use it.
                        pieces[i] = jnp.zeros(
                            (bucketed, lane), dtype=jnp.int32, device=devices[i]
                        )
                    elif not isinstance(piece, jax.Array):
                        # The runtime may read a view of the sealed round
                        # after device_put returns.  That is safe: sealed
                        # rounds are immutable until remove_shuffle
                        # (HbmBlockStore.seal), and run_exchange returns only
                        # after every round has drained.
                        t_put = time.perf_counter_ns() if on_disk[i] else 0
                        pieces[i] = jax.device_put(piece, devices[i])
                        if on_disk[i]:
                            disk_marks.append(("exchange.h2d.disk", t_put, time.perf_counter_ns()))
                data = jax.make_array_from_single_device_arrays(
                    (n * bucketed, lane), data_sharding, pieces
                )
                size_mat = jax.device_put(sub_sizes.astype(np.int32), data_sharding)
            if disk_marks and h2d is not None:
                # ``exchange.h2d``'s child, once a piece whose source is a
                # mapping of the disk tier: the time the put holds the lane
                # reading the file's pages, not the DMA
                TRACER.record_spans(h2d, disk_marks, args={"round": rnd, "bytes": assembled["disk_bytes"]})
            with span(
                "exchange.collective",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk, rows=bucketed,
            ):
                recv, recv_sizes = fn(data, size_mat)
            # Pin the per-device shard objects HERE (addressable_shards builds
            # fresh wrappers per call — reusing these keeps the async-copy
            # cache) and start their D2H now, while later sub-rounds keep the
            # device busy; the drain's np.asarray then observes completion
            # instead of initiating the copy.  What crosses is each shard's
            # received prefix (_received_prefix), and nothing for a consumer
            # that received nothing: the column sums of the size matrix say
            # how long it is, with no wait for recv_sizes.  Where it lands is
            # the cluster's rule (_landing).  Counters ``exchange.d2h``:
            # ``shard_bytes`` (the whole shards), ``moved_bytes`` (what was
            # pinned for the host), ``used_bytes`` (the rows received:
            # ``moved ÷ used`` is the buckets' overshoot), ``skipped_shards``
            # / ``sliced_shards``, ``kept_shards`` / ``fresh_shards`` (shards
            # that land in a block the pool had kept / in newly allocated
            # pages: with the skipped ones, the shards of a sub-round).
            shard_by_device = {s.device: s.data for s in recv.addressable_shards}
            host_src, landing = None, None
            if mode != "device":
                shards = [shard_by_device[d] for d in devices]
                used = sub_sizes.sum(axis=0)
                host_src = [self._received_prefix(a, int(u)) for a, u in zip(shards, used)]
                moving = [a for a in host_src if a is not None]
                kept = 0
                if pool is None:
                    for a in moving:
                        _start_landing(a)
                else:
                    hits = pool.stats()["hits"]
                    with pool.allocating():
                        for a in moving:
                            _start_landing(a)
                    kept = pool.stats()["hits"] - hits
                landing = "kept" if moving and kept == len(moving) else "fresh"
                self.stats.record_counters(
                    "exchange.d2h",
                    shard_bytes=sum(a.nbytes for a in shards),
                    moved_bytes=sum(a.nbytes for a in moving),
                    used_bytes=int(used.sum()) * self.row_bytes,
                    skipped_shards=len(host_src) - len(moving),
                    sliced_shards=sum(
                        a is not None and a is not whole for a, whole in zip(host_src, shards)
                    ),
                    kept_shards=kept,
                    fresh_shards=len(moving) - kept,
                )
            recv_sizes.copy_to_host_async()
            return recv, recv_sizes, shard_by_device, host_src, landing

        def _drain_chunk(rnd, chunk, nchunks, ticket):
            """Complete one sub-round host-side (drain-worker thread at
            depth > 1).  Single-shot rounds materialize their whole receive
            state here — including the streamed memmap spill — so host RSS
            keeps the historical one-in-flight-window bound."""
            recv, recv_sizes, shard_by_device, host_src, landing = ticket
            sizes_host = np.asarray(recv_sizes)

            def host_part(j):
                # the bytes _submit pinned: a received prefix, or nothing
                a = host_src[j]
                if a is None:
                    return np.empty(0, dtype=np.uint8)
                return np.asarray(a).reshape(-1).view(np.uint8)

            if mode == "device":
                # No host copy at all: fetches slice the retained HBM shard
                # and D2H only the requested block (locate_received_block).
                jax.block_until_ready(recv)
                host_parts = None
            elif plan.single_shot and mode == "memmap":
                # One D2H per shard, streamed straight into a disk-backed
                # mapping; the round's RAM is released once pages flush, so
                # host RSS stays bounded by ~one in-flight window however many
                # rounds the shuffle spills.
                with span("exchange.d2h_memmap", shuffle_id=shuffle_id, round=rnd):
                    host_parts = self._memmap_round(
                        meta, rnd, (host_part(j) for j in range(n))
                    )
            else:
                # One D2H per executor shard that received rows; fetches (or
                # the round splice) then slice host memory.
                with span(
                    "exchange.d2h",
                    shuffle_id=shuffle_id, round=rnd, chunk=chunk,
                    bytes=sum(a.nbytes for a in host_src if a is not None),
                    landing=landing,
                ):
                    host_parts = [host_part(j) for j in range(n)]
            dev_parts = (
                [shard_by_device[devices[j]] for j in range(n)] if keep_device else None
            )
            return sizes_host, host_parts, dev_parts

        def _finish_round(rnd, nchunks, parts):
            """Emit one staging round's receive state: a single-shot round
            passes its only chunk through (each shard's received prefix,
            bucketed: the historical layout up to where a reader looks); a
            chunked round splices its sub-round shards back into the exact
            single-shot layout (bit-equality pinned in tests/test_skew.py and
            tests/test_planner.py)."""
            if plan.single_shot:
                sizes_host, shards, dev_shards = parts[0]
                used = int(sizes_host.sum())
                return shards, sizes_host, dev_shards, (used, n * bucketed - used)
            sub_size_mats = [p[0] for p in parts]
            logical = np.sum(sub_size_mats, axis=0).astype(np.int32)
            shards = dev_shards = None
            if mode != "device":
                assembled = [
                    reassemble_round(
                        [p[1][j] for p in parts],
                        [m[j] for m in sub_size_mats],
                        self.row_bytes,
                    )
                    for j in range(n)
                ]
                if mode == "memmap":
                    with span("exchange.d2h_memmap", shuffle_id=shuffle_id, round=rnd):
                        shards = self._memmap_round(meta, rnd, assembled)
                else:
                    shards = assembled
            if keep_device:
                dev_shards = []
                for j in range(n):
                    splice = piece_slices([m[j] for m in sub_size_mats])
                    pieces = [
                        parts[c][2][j][start : start + rows] for c, start, rows in splice
                    ]
                    if pieces:
                        # pow2-pad so the block gather's jit cache stays
                        # bounded despite data-dependent reassembled rows
                        dshard = pad_rows_pow2(jnp.concatenate(pieces), xp=jnp)
                    else:
                        dshard = jnp.zeros(
                            (1, lane), dtype=parts[0][2][j].dtype, device=devices[j]
                        )
                    dev_shards.append(dshard)
            used = int(logical.sum())
            return shards, logical, dev_shards, (used, nchunks * n * bucketed - used)

        try:
            results = execute_plan(
                plan,
                submit=_submit,
                drain_chunk=_drain_chunk,
                finish_round=_finish_round,
                result_bytes=lambda r: int(r[1].sum()) * self.row_bytes,
                # staging occupancy per round: used rows vs. the slot padding
                # the planner's quota/chunking decisions exist to shrink
                occupancy=lambda r: r[3],
                stats=self.stats,
                interrupt=_mesh_changed if plan.single_shot else None,
            )
        except _MeshChanged:
            # An executor died under this exchange: abort the stale full-mesh
            # plan and re-run degraded on the surviving pow2 bucket (or raise
            # a typed ExecutorLostError when recovery is impossible).  The
            # re-run reads host rounds only.
            release_early_rounds()
            # The recovery's large host arrays — the restaged rounds, the
            # landings of each sub-exchange's received prefixes, the
            # recovered shards — come from the pool received shards land in,
            # where there is one (_landing): the blocks the last job's
            # recovery gave back, not fresh mappings a time.  (The allocator
            # is this thread's: the re-run's drain worker takes it itself.)
            allocating = pool.allocating() if pool is not None else contextlib.nullcontext()
            with span("exchange.recover", shuffle_id=shuffle_id), allocating:
                self._recover_and_rerun(meta, sealed, mode, pool, plan.pipeline_depth)
            return
        except BaseException:
            release_early_rounds()  # an aborted exchange holds no HBM of rounds it never sent
            raise

        meta.recv_shards, meta.recv_sizes = [], []
        for shards, sizes_host, dev_shards, _occ in results:
            if shards is not None:
                meta.recv_shards.append(shards)
            meta.recv_sizes.append(sizes_host)
            active = int(np.count_nonzero(sizes_host))
            self.stats.record_rows("exchange.lanes", active, sizes_host.size - active)
            if dev_shards is not None:
                if meta.recv_device is None:
                    meta.recv_device = []
                meta.recv_device.append(dev_shards)
        if mode == "device":
            meta.recv_shards = None  # explicit no-host-copy marker
        meta.exchanged = True

    # -- elastic membership / degraded-mode recovery -----------------------

    def _replicate_sealed(self, shuffle_id: int, pool: Optional[LandingPool]) -> Dict[str, int]:
        """Copy every executor's sealed rounds to its ring successors
        (single-controller twin of PeerTransport._replicate_push): a direct
        store-to-store ``put_replica`` with the same entry table and landing
        zone as the wire path, so ``_recover_and_rerun`` restages from the
        same placement either way.

        A replicated byte is written once: for each round and successor the
        source store gathers the round's blocks, outside its lock, into ONE
        array (``replica_source``), which the successor's store installs as
        it is — the replica's own bytes, never a view of the source's
        staging.  That array is allocated from ``pool``, the blocks received
        shards land in (``_landing``), where there is one: the body lengths
        of a job's rounds come again with the next job, so from the second
        job on a body is a block the one before gave back at
        ``remove_shuffle``, pages this process already holds.  All of it is
        done when this returns: nothing is still copying at the first
        submit.  Returns the ``exchange.replicate`` span's arguments, which
        are also what the ``elastic`` counters rise by."""
        n = self.num_executors
        factor = self.conf.replication_factor
        t0 = time.perf_counter_ns()
        copied_rounds = copied_bytes = 0
        before = pool.stats() if pool is not None else None

        def alloc(nbytes: int) -> np.ndarray:
            if pool is None:
                return np.empty(nbytes, dtype=np.uint8)
            with pool.allocating():
                return np.empty(nbytes, dtype=np.uint8)

        for t in self.transports:
            if not self.membership.is_alive(t.executor_id):
                continue
            for succ in ring_neighbors(t.executor_id, range(n), factor):
                if not self.membership.is_alive(succ):
                    continue
                # a gather a successor: each holds bytes of its own
                for rnd, entries, body in t.store.replica_source(shuffle_id, alloc):
                    self.transports[succ].store.put_replica(
                        shuffle_id, t.executor_id, rnd, entries, body
                    )
                    copied_rounds += 1
                    copied_bytes += len(body)
        hits = misses = 0
        if before is not None:
            after = pool.stats()
            hits, misses = after["hits"] - before["hits"], after["misses"] - before["misses"]
        with self._lock:
            self.elastic_stats["replicated_rounds"] += copied_rounds
            self.elastic_stats["replicated_bytes"] += copied_bytes
            self.elastic_stats["replica_copied_bytes"] += copied_bytes
            self.elastic_stats["replica_landing_hits"] += hits
            self.elastic_stats["replica_landing_misses"] += misses
            self.elastic_stats["replicate_ns"] += time.perf_counter_ns() - t0
        return {"copied_bytes": copied_bytes, "landing_hits": hits, "landing_misses": misses}

    def _recover_and_rerun(self, meta, sealed, mode: str, pool: Optional[LandingPool], depth: int) -> None:
        """Degraded-mode recovery: quarantine the aborted exchange's partial
        state, restage every dead executor's rounds from ring-successor
        replicas, shrink to the surviving pow2 bucket, and re-run the whole
        shuffle as ``waves x waves`` sub-exchanges on the shrunk mesh.

        The re-run is a plan the plan executor interprets (``execute_plan``
        under the name ``exchange.recover.pipeline``, ``depth`` deep as the
        aborted plan was): a staging round is a round, a sub-exchange that
        carries a row a chunk of it, and the three closures below are the
        recovery's own — so sub-exchange k + 1 is put while k's received rows
        cross back.  ``pool``: the blocks received shards land in, or None.

        Determinism: each sub-exchange (i, j) moves wave i's senders' regions
        for wave j's consumers, and a consumer's final shard concatenates its
        sub-shards in ascending wave order — exactly the sender-major packed
        layout the full-mesh exchange produces, so the recovered bytes are
        bit-identical to an undisturbed run (pinned in tests/test_elastic.py).
        """
        shuffle_id = meta.shuffle_id
        op = OperationStats()
        t0 = time.monotonic_ns()
        snap = self.membership.snapshot()
        dead, alive, epoch = snap["dead"], snap["alive"], snap["epoch"]
        first_dead = sorted(dead)[0] if dead else -1

        # Quarantine: drop any partially-drained receive state and refund its
        # disk budget — the aborted plan's outputs must never leak into the
        # recovered shuffle.
        meta.recv_shards = None
        meta.recv_sizes = None
        meta.recv_device = None
        with self._lock:
            doomed, meta.recv_spill_paths = meta.recv_spill_paths, []
        if doomed:
            import os

            for path, size in doomed:
                try:
                    os.unlink(path)
                except OSError:
                    pass
                with self._lock:
                    self._recv_spill_bytes -= size

        def unsupported(why: str) -> ExecutorLostError:
            return ExecutorLostError(
                first_dead, epoch, f"{why}; dead executors: {dict(dead)}"
            )

        if not self.conf.elastic:
            raise unsupported(
                "elastic recovery disabled (spark.shuffle.tpu.elastic.enabled=false)"
            )
        if dead and self.conf.replication_factor < 1:
            raise unsupported("no replicas to restage from (replication.factor=0)")
        if self.conf.num_slices > 1:
            raise unsupported(
                "degraded recovery does not cover multi-slice meshes (num_slices > 1)"
            )
        if mode == "device" or self.conf.keep_device_recv:
            raise unsupported(
                "degraded recovery does not cover device-resident receive "
                "(host_recv_mode='device' / keep_device_recv)"
            )

        n = self.num_executors
        num_rounds = max(len(s) for s in sealed)
        m, phys, waves = degraded_plan(n, alive)
        alive_set = set(alive)
        slot_rows = meta.region_bytes // self.row_bytes
        lane = self.row_bytes // 4

        # Span ``exchange.recover.restage``, once a recovery: every dead
        # executor's rounds rebuilt from its ring successors' replicas.
        with span("exchange.recover.restage", shuffle_id=shuffle_id) as restage:
            restaged, restaged_blocks, restaged_bytes = self._restage_dead(
                meta, sealed, sorted(dead), alive_set
            )
            if restage is not None:
                restage.args.update(blocks=restaged_blocks, bytes=restaged_bytes)

        fn, submesh = self._degraded_exchange_fn(m, phys, m * slot_rows)
        bucketed = bucket_send_rows(m * slot_rows, m)
        ax = self.conf.mesh_axis_name
        sub_sharding = NamedSharding(submesh, P(ax, None))
        sub_devices = list(submesh.devices.reshape(-1))

        # The re-run as a plan (transport/executor.py): a round of the plan
        # is a staging round, a chunk of it one (senders' wave i, consumers'
        # wave j) sub-exchange — and only a pair that carries a row is a
        # chunk, read off the sealed and restaged size matrices before a byte
        # moves: a pair with no row dispatches nothing.
        payloads: List[List[Optional[np.ndarray]]] = []
        #: [round][sender, destination] rows, zero-padded to whole waves
        sizes = np.zeros((num_rounds, waves * m, waves * m), dtype=np.int64)
        for rnd in range(num_rounds):
            payloads.append([])
            for l in range(n):
                src = sealed[l] if sealed[l] is not None else restaged.get(l, [])
                payload, size_rows = src[rnd] if rnd < len(src) else (None, 0)
                payloads[rnd].append(payload)
                sizes[rnd, l, :n] = size_rows

        def pair_sizes(rnd, i, j):
            return sizes[rnd, i * m : (i + 1) * m, j * m : (j + 1) * m]

        pairs = [
            [(i, j) for i in range(waves) for j in range(waves) if pair_sizes(rnd, i, j).any()]
            for rnd in range(num_rounds)
        ]
        plan = ExchangePlan(
            slot_rows=bucketed // m,
            chunks_per_round=tuple(len(p) for p in pairs),
            pipeline_depth=depth,
        )
        allocating = pool.allocating if pool is not None else contextlib.nullcontext
        #: what each round's submits handed the devices (the ``elastic``
        #: counters ``recover_*`` and the round span's arguments) and the
        #: clock marks its ``exchange.recover.round`` span is made from: the
        #: submit lane writes ``began``, the drain worker ``ended``, both are
        #: read when the pipeline has shut down
        tallies = [dict(direct_bytes=0, copied_bytes=0, zero_pieces=0) for _ in range(num_rounds)]
        began, ended = [0] * num_rounds, [0] * num_rounds

        def submit(rnd, chunk, nchunks):
            """One sub-exchange's pieces, their puts, the dispatch and the
            start of its landings — all asynchronous: it is in flight when
            the next is assembled.  A sender's piece is a view of its sealed
            or restaged round wherever the shrunk mesh's slot is the region
            (``_wave_piece``), put on its own device; a sender with no row
            for this wave's consumers, or no round at all, contributes a zero
            piece made on its device, fresh every submit (the exchange
            donates its first argument).  Child spans of
            ``exchange.recover.pipeline.submit``: ``exchange.recover.h2d``
            (the time the puts hold this lane, not the DMA) and
            ``exchange.collective.degraded`` (the dispatch)."""
            if chunk == 0:
                began[rnd] = time.perf_counter_ns()
            faults.check("exchange.recover.submit", shuffle_id=shuffle_id, round=rnd, chunk=chunk)
            if self.membership.epoch != epoch:
                # a second loss (or a rejoin) under the re-run: its plan is
                # stale too, and there is no recovery of a recovery
                now = self.membership.snapshot()
                newly = sorted(set(now["dead"]) - set(dead))
                raise ExecutorLostError(
                    newly[0] if newly else first_dead,
                    now["epoch"],
                    "membership changed again under the degraded re-run "
                    f"(epoch {epoch} -> {now['epoch']}); dead executors: {dict(now['dead'])}",
                )
            i, j = pairs[rnd][chunk]
            sub_sizes = pair_sizes(rnd, i, j).astype(np.int32)
            lo, hi = j * m * slot_rows, min((j + 1) * m, n) * slot_rows
            tally = tallies[rnd]
            pieces = []
            for p in range(m):
                l = i * m + p
                payload = payloads[rnd][l] if l < n else None
                if payload is None or not sub_sizes[p].any():
                    pieces.append(None)
                    tally["zero_pieces"] += 1
                    continue
                host = np.asarray(payload)
                piece = _wave_piece(host, lo, hi, m, slot_rows, bucketed)
                is_view = np.may_share_memory(piece, host)
                tally["direct_bytes" if is_view else "copied_bytes"] += piece.nbytes
                pieces.append(piece)
            with span(
                "exchange.recover.h2d",
                shuffle_id=shuffle_id, round=rnd, wave=(i, j),
                bytes=sum(piece.nbytes for piece in pieces if piece is not None),
            ):
                for p, piece in enumerate(pieces):
                    if piece is None:
                        pieces[p] = jnp.zeros((bucketed, lane), dtype=jnp.int32, device=sub_devices[p])
                    else:
                        # a view is read after device_put returns: sealed and
                        # restaged rounds are not written until this returns
                        pieces[p] = jax.device_put(piece, sub_devices[p])
                data = jax.make_array_from_single_device_arrays(
                    (m * bucketed, lane), sub_sharding, pieces
                )
                size_mat = jax.device_put(sub_sizes, sub_sharding)
            with span(
                "exchange.collective.degraded",
                shuffle_id=shuffle_id, round=rnd, wave=(i, j), rows=bucketed,
            ):
                recv, recv_sizes = fn(data, size_mat)
            # Each consumer's received prefix starts for the host now, into a
            # block of the landing pool where there is one; how long it is
            # the size matrix's column sum says, with no wait for recv_sizes.
            shard_by_device = {s.device: s.data for s in recv.addressable_shards}
            landings = []
            with allocating():
                for q in range(m):
                    used = int(sub_sizes[:, q].sum())
                    if used:
                        prefix = self._received_prefix(shard_by_device[sub_devices[q]], used)
                        _start_landing(prefix)
                        landings.append((j * m + q, q, prefix))
            recv_sizes.copy_to_host_async()
            return landings, recv_sizes

        def drain_chunk(rnd, chunk, nchunks, ticket):
            """A sub-exchange's received rows as host views, one a consumer
            that received any (the drain worker at depth > 1).  Span
            ``exchange.recover.d2h``: the wait until they are host-readable."""
            landings, recv_sizes = ticket
            with span(
                "exchange.recover.d2h",
                shuffle_id=shuffle_id, round=rnd, wave=pairs[rnd][chunk],
                bytes=sum(prefix.nbytes for _, _, prefix in landings),
            ):
                sizes_host = np.asarray(recv_sizes)  # [consumer, sender]
                return [
                    (c, np.asarray(prefix)[: int(sizes_host[q].sum())].reshape(-1).view(np.uint8))
                    for c, q, prefix in landings
                ]

        def finish_round(rnd, nchunks, parts):
            """A re-run round's recovered shards: each consumer's parts in
            chunk order — ascending senders' wave, the sender-major layout of
            the full mesh.  The landing pool's allocator is the calling
            thread's, and this is the drain worker: it is taken here, or
            every recovered shard is a fresh mapping."""
            consumer_parts: List[List[np.ndarray]] = [[] for _ in range(n)]
            for chunk_parts in parts:
                for c, part in chunk_parts:
                    consumer_parts[c].append(part)
            with allocating():
                shards = [
                    np.concatenate(ps) if ps else np.empty(0, dtype=np.uint8)
                    for ps in consumer_parts
                ]
            if mode == "memmap":
                with span("exchange.d2h_memmap", shuffle_id=shuffle_id, round=rnd):
                    shards = self._memmap_round(meta, rnd, iter(shards))
            ended[rnd] = time.perf_counter_ns()
            return rnd, shards

        def used_rows(result):
            return int(sizes[result[0]].sum())

        planned = time.perf_counter_ns()
        results = dict(
            execute_plan(
                plan,
                submit=submit,
                drain_chunk=drain_chunk,
                finish_round=finish_round,
                result_bytes=lambda r: used_rows(r) * self.row_bytes,
                occupancy=lambda r: (
                    used_rows(r), len(pairs[r[0]]) * m * bucketed - used_rows(r)
                ),
                stats=self.stats,
                # its own aggregator and spans: ``exchange.pipeline`` is the
                # full mesh's rounds, and its readers see only those
                name="exchange.recover.pipeline",
            )
        )
        meta.recv_shards, meta.recv_sizes = [], []
        for rnd in range(num_rounds):
            # a round in which no executor had a row dispatched nothing
            empty = [np.empty(0, dtype=np.uint8) for _ in range(n)]
            meta.recv_shards.append(results.get(rnd, empty))
            recv_mat = np.ascontiguousarray(sizes[rnd, :n, :n].T, dtype=np.int32)
            meta.recv_sizes.append(recv_mat)
            active = int(np.count_nonzero(recv_mat))
            self.stats.record_rows("exchange.lanes", active, recv_mat.size - active)
        recovered = {f"recover_{k}": sum(t[k] for t in tallies) for k in tallies[0]}
        if TRACER.active:
            # Span ``exchange.recover.round``, once a re-run staging round,
            # from its clock marks — rounds overlap in the pipeline: the
            # first submit of the round to the end of its ``finish_round``.
            # ``subexchanges``: the collectives it dispatched on the shrunk
            # mesh (a wave pair that carries no row dispatches none).
            recover_span = TRACER.current_context()
            for rnd in range(num_rounds):
                t_began = began[rnd] or planned
                TRACER.record_spans(
                    recover_span,
                    [("exchange.recover.round", t_began, ended[rnd] or t_began)],
                    args={"shuffle_id": shuffle_id, "round": rnd,
                          "subexchanges": len(pairs[rnd]), **tallies[rnd]},
                )
        meta.recv_adopted = frozenset(dead)
        meta.exchanged = True
        recover_ns = time.monotonic_ns() - t0
        recovery_ms = recover_ns / 1e6
        with self._lock:
            self.elastic_stats["recoveries"] += 1
            self.elastic_stats["last_recovery_ms"] = recovery_ms
            self.elastic_stats["last_epoch"] = epoch
            self.elastic_stats["degraded_mesh"] = (m, tuple(phys))
            self.elastic_stats["restaged_blocks"] += restaged_blocks
            self.elastic_stats["restaged_bytes"] += restaged_bytes
            self.elastic_stats["degraded_subexchanges"] += plan.num_subrounds
            self.elastic_stats["recover_ns"] += recover_ns
            for key, value in recovered.items():
                self.elastic_stats[key] += value
        op.mark_done()
        self.stats.record("exchange.recovery", op)
        instant(
            "exchange.recovered",
            shuffle_id=shuffle_id, epoch=epoch, mesh=m, waves=waves,
            recovery_ms=round(recovery_ms, 3),
        )
        # full postmortem bundle (metrics + membership): safe here — the
        # recovery is done and no subsystem lock is held on this thread
        self.recorder.capture(
            "elastic_recovery",
            shuffle_id=shuffle_id,
            epoch=epoch,
            mesh=m,
            recovery_ms=round(recovery_ms, 3),
        )

    def _restage_dead(self, meta, sealed, dead, alive_set):
        """Rebuild each dead executor's sealed rounds bit-identically from
        replicas: zeros staging (padding rows are zero by construction),
        replica block bodies at their MapperInfo absolute offsets (a block
        staged in pieces: each piece in its own round, cut from the replica's
        whole body), per-region
        used-row counts rebuilt from the padded lengths (allocation was
        contiguous, so the padded sum IS the region's used prefix).  The dead
        executors' entries of ``sealed`` are dropped: their memory died with
        them.  Returns ({executor: [(payload, size_rows) a round]}, blocks
        restaged, their bytes)."""
        shuffle_id = meta.shuffle_id
        n = self.num_executors
        send_rows = n * (meta.region_bytes // self.row_bytes)
        lane = self.row_bytes // 4
        restaged: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
        blocks = nbytes = 0
        for d in dead:
            dead_rounds = len(sealed[d])
            sealed[d] = None  # its memory died with it — recover honestly
            cands = ring_neighbors(d, range(n), self.conf.replication_factor)
            live_cands = [c for c in cands if c in alive_set]
            rounds_out: List[Tuple[np.ndarray, np.ndarray]] = []
            for rnd in range(dead_rounds):
                payload = np.zeros((send_rows, lane), dtype=np.int32)
                flat = payload.reshape(-1).view(np.uint8)
                sizes = np.zeros(n, dtype=np.int64)
                for map_id, info in meta.mapper_infos.items():
                    if meta.map_owner[map_id] != d:
                        continue
                    for r, (off, ln) in enumerate(info.partitions):
                        # (staging offset, offset in the block, bytes) of what
                        # the block has in this round: all of it, or one of
                        # the pieces of a block longer than a region
                        if info.splits is not None and r in info.splits:
                            extents, at = [], 0
                            for piece_round, piece_off, piece_bytes in info.splits[r]:
                                if piece_round == rnd:
                                    extents.append((piece_off, at, piece_bytes))
                                at += piece_bytes
                        else:
                            extents = [(off, 0, ln)] if ln and info.round_of(r) == rnd else []
                        if not extents:
                            continue
                        body = None
                        for c in live_cands:
                            body = self.transports[c].store.replica_block(
                                shuffle_id, d, map_id, r
                            )
                            if body is not None:
                                break
                        if body is None:
                            raise BlockNotFoundError(
                                shuffle_id, map_id, r,
                                f"primary executor {d} is dead and no replica "
                                f"found on candidates {cands} (alive: "
                                f"{live_cands}) — shuffle {shuffle_id} is "
                                "unrecoverable",
                            )
                        for piece_off, at, piece_bytes in extents:
                            # a view of the replica: one copy
                            flat[piece_off : piece_off + piece_bytes] = body[at : at + piece_bytes]
                            sizes[piece_off // meta.region_bytes] += -(-piece_bytes // self.row_bytes)
                            blocks += not at  # a block once, with its first byte
                            nbytes += piece_bytes
                rounds_out.append((payload, sizes.astype(np.int32)))
            restaged[d] = rounds_out
        return restaged, blocks, nbytes

    def _degraded_exchange_fn(self, m: int, phys, sub_rows: int):
        """Compile (or reuse) the shrunk-mesh exchange for a degraded mesh.
        The cache key carries the surviving device set on top of the usual
        pow2 bucket: those identify the executable.  The membership epoch
        does not — an executor that is lost, rejoins and is lost again
        leaves the same survivors two epochs later, and finds the executable
        of the first loss again."""
        send_rows = bucket_send_rows(sub_rows, m)
        from sparkucx_tpu.ops.ici_exchange import resolve_exchange_impl

        submesh = surviving_submesh(self.mesh, phys, self.conf.mesh_axis_name)
        impl = resolve_exchange_impl(
            self.conf.exchange_impl, submesh.devices.reshape(-1)[0].platform, m
        )
        key = ("degraded", m, tuple(phys), send_rows, self.row_bytes, impl)
        with self._lock:
            fn = self._exchange_cache.get(key)
            if fn is None:
                fn = build_plan_exchange(
                    submesh,
                    num_executors=m,
                    send_rows=send_rows,
                    lane=self.row_bytes // 4,
                    axis_name=self.conf.mesh_axis_name,
                    impl=impl,
                )
                self._exchange_cache[key] = fn
        return fn, submesh

    def note_executor_lost(self, executor_id: ExecutorId, reason: str) -> bool:
        """Report a death observed outside the chaos harness (wire errors,
        timeouts); returns True when this observation newly killed the
        executor (epoch bumped)."""
        return self.membership.mark_dead(executor_id, reason)

    def drop_received_of(self, executor_id: ExecutorId) -> int:
        """What dies with an executor's process besides its store: the
        shards it received of every exchanged shuffle — host arrays, the
        ``memmap`` mode's files (unlinked, their disk budget refunded) and
        the HBM copies.  The cluster lets go of them here, so nothing the
        dead executor held is ever served: a read addressed to them is
        ``ExecutorLostError`` from now on (``_locate_rows``).  Shards a
        recovery produced AFTER the executor's death are the survivors'
        (``recv_adopted``) and stay.  Counted in the ``elastic`` family as
        ``lost_recv_shards`` / ``lost_recv_bytes``; returns the bytes."""
        with self._lock:
            metas = list(self._meta.values())
        shards = nbytes = 0
        for meta in metas:
            if not meta.exchanged or executor_id in meta.recv_adopted or executor_id in meta.recv_lost:
                continue
            meta.recv_lost.add(executor_id)  # before the entries go: a reader sees the typed error
            files = set()
            for rounds in (meta.recv_shards, meta.recv_device):
                for rnd in rounds or ():
                    shard, rnd[executor_id] = rnd[executor_id], None
                    if shard is None:
                        continue
                    shards += 1
                    nbytes += int(shard.nbytes)
                    if isinstance(shard, np.memmap):
                        files.add(shard.filename)
                    del shard  # the mapping goes before its file
            if files:
                with self._lock:
                    mine = [entry for entry in meta.recv_spill_paths if entry[0] in files]
                    meta.recv_spill_paths = [entry for entry in meta.recv_spill_paths if entry[0] not in files]
                still = [entry for entry in mine if not self._unlink_recv_spill(*entry)]
                if still:  # on disk and charged: listed for the shuffle's removal
                    with self._lock:
                        meta.recv_spill_paths.extend(still)
        with self._lock:
            self.elastic_stats["lost_recv_shards"] += shards
            self.elastic_stats["lost_recv_bytes"] += nbytes
        if shards:
            instant("exchange.recv_lost", executor=executor_id, shards=shards, bytes=nbytes)
        return nbytes

    def rejoin_executor(self, executor_id: ExecutorId) -> bool:
        """Regrow: a previously-dead executor comes back, as a restarted
        process does — with an empty store of its own and nothing of the one
        that died (``TpuShuffleTransport.restart``), and able to die again —
        and is marked alive.  The full mesh is restored for the NEXT shuffle
        epoch — in-flight degraded state is untouched, and because no
        compile-cache key carries an epoch, regrowing recompiles nothing.
        False, and nothing done, for an executor that is alive or unknown."""
        if executor_id not in range(self.num_executors) or self.membership.is_alive(executor_id):
            return False
        self.transports[executor_id].restart()
        return self.membership.mark_alive(executor_id)

    def _memmap_round(self, meta, rnd: int, host_views):
        """Spill one round's received shards to a disk-backed mapping and
        return uint8 ``np.memmap`` views (host_recv_mode='memmap').

        ``host_views`` yields one flat uint8 array per executor; passing a
        generator keeps host RSS at ~one transient shard — each view is
        materialized, written, and dropped before the next is produced."""
        import os
        import tempfile

        spill_dir = self.conf.spill_dir
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
        views = []
        for j, host in enumerate(host_views):
            cap = self.conf.spill_disk_cap_bytes
            nbytes = int(host.nbytes)
            if nbytes == 0:
                # nothing received (a quota-path tight shard can be empty);
                # np.memmap cannot map a zero-byte file, and there is nothing
                # to spill — keep the empty array itself
                views.append(host)
                continue
            # reserve-then-write keeps check+charge atomic under the lock;
            # any write failure refunds the reservation and removes the
            # half-written file so the budget cannot leak
            with self._lock:
                if cap and self._recv_spill_bytes + nbytes > cap:
                    raise TransportError(
                        f"received-shard spill would exceed spill_disk_cap_bytes "
                        f"({self._recv_spill_bytes + nbytes} > {cap}); raise the "
                        f"cap or use host_recv_mode='device'"
                    )
                self._recv_spill_bytes += nbytes
            fd, path = tempfile.mkstemp(
                prefix=f"sparkucx_tpu_recv_s{meta.shuffle_id}_r{rnd}_e{j}_",
                dir=spill_dir,
            )
            os.close(fd)
            shape = host.shape
            try:
                mm = np.memmap(path, dtype=np.uint8, mode="w+", shape=shape)
                mm[:] = host
                mm.flush()
            except BaseException:
                with self._lock:
                    self._recv_spill_bytes -= nbytes
                try:
                    os.unlink(path)
                except OSError:
                    pass
                raise
            # Drop the write mapping and reopen read-only: the dirty pages are
            # unmapped (host RSS actually falls back to ~one transient shard),
            # and fetches fault in only the pages they touch.
            del mm, host
            # the drain worker appends while remove_shuffle may iterate on the
            # main thread — same lock as the budget it charges against
            with self._lock:
                meta.recv_spill_paths.append((path, nbytes))
            views.append(np.memmap(path, dtype=np.uint8, mode="r", shape=shape))
        return views

    # -- post-exchange block lookup ---------------------------------------

    def locate_received_block(
        self, consumer: ExecutorId, shuffle_id: int, map_id: int, reduce_id: int
    ) -> Tuple[np.ndarray, int]:
        """Locate block (map_id, reduce_id) inside ``consumer``'s received shard.

        Returns (uint8 view of the block payload, length).  Offset math:
        sender's chunk starts at sum of earlier senders' recv sizes; within the
        chunk the block sits at its region-relative offset (MapperInfo offsets
        are absolute in the sender's staging buffer; regions are slot-aligned).
        """
        return self._received_block(
            self._exchanged_meta(shuffle_id), consumer, map_id, reduce_id
        )

    def resident_blocks(
        self, consumer: ExecutorId, block_ids: Sequence[ShuffleBlockId],
        assembled: Optional[List[int]] = None,
    ) -> List[np.ndarray]:
        """``locate_received_block`` for a batch of one shuffle's blocks that
        ``consumer`` received: one look-up of the meta, each (round, sender)
        chunk start summed once a call, and a READ-ONLY uint8 view a block,
        in order, straight out of the received shard (of the mapping in
        ``host_recv_mode='memmap'``; the block's own D2H array in
        ``'device'``).  Nothing is copied and nothing allocated: a view keeps
        its shard alive, by reference count, for as long as it is held — past
        ``remove_shuffle`` too, which drops references and deletes nothing.
        The one exception is a block staged in pieces (longer than a peer
        region; ``MapperInfo.splits``): it lies in several rounds' shards, so
        it is handed out as ONE read-only array put together from its pieces'
        views in order — one copy of that block, under the span
        ``read.block_assemble`` — and its length is appended to ``assembled``
        where the caller passes a list (the reader's counters).
        Raises the typed errors of ``locate_received_block`` at the first
        block that has none."""
        if not block_ids:
            return []
        meta = self._exchanged_meta(block_ids[0].shuffle_id)
        starts: Dict[Tuple[int, int], int] = {}
        views = []
        for bid in block_ids:
            if bid.shuffle_id != meta.shuffle_id:
                raise TransportError(f"block {bid} not from shuffle {meta.shuffle_id}")
            view, _ = self._received_block(meta, consumer, bid.map_id, bid.reduce_id, starts, assembled)
            view.flags.writeable = False
            views.append(view)
        return views

    def _exchanged_meta(self, shuffle_id: int) -> _ShuffleMeta:
        meta = self.meta(shuffle_id)
        if not meta.exchanged:
            raise TransportError(f"shuffle {shuffle_id} not exchanged yet")
        return meta

    def _received_block(
        self,
        meta: _ShuffleMeta,
        consumer: ExecutorId,
        map_id: int,
        reduce_id: int,
        starts: Optional[Dict[Tuple[int, int], int]] = None,
        assembled: Optional[List[int]] = None,
    ) -> Tuple[np.ndarray, int]:
        """(view, length) of one block of an exchanged shuffle; ``starts`` as
        in ``_locate_rows``, ``assembled`` as in ``resident_blocks``."""
        rnd, src_row, rows, pieces = self._locate_rows(meta, consumer, map_id, reduce_id, starts)
        if rows == 0:
            return np.empty(0, dtype=np.uint8), 0
        length = meta.mapper_infos[map_id].partitions[reduce_id][1]
        if pieces is not None:
            return self._assembled_block(meta, consumer, map_id, reduce_id, pieces, length, assembled), length
        if meta.recv_shards is None:
            # host_recv_mode='device': no host copy exists — slice the block's
            # rows out of the HBM-resident shard and D2H just those bytes.
            shard = meta.recv_device[rnd][consumer]
            if shard is None:  # went with its executor since the look-up above
                raise self._received_shards_lost(meta, consumer, reduce_id)
            block_rows = np.asarray(shard[src_row : src_row + rows])
            return block_rows.reshape(-1).view(np.uint8)[:length], length
        shard = meta.recv_shards[rnd][consumer]
        if shard is None:
            raise self._received_shards_lost(meta, consumer, reduce_id)
        start = src_row * self.row_bytes
        if start + length > shard.size:
            # the host part is the shard's received prefix: a block past its
            # end was never received, and a short slice would pass for it
            raise TransportError(
                f"block ({meta.shuffle_id},{map_id},{reduce_id}) at bytes "
                f"[{start}, {start + length}) lies past the {shard.size} bytes "
                f"executor {consumer} received in round {rnd}"
            )
        return shard[start : start + length], length

    def _assembled_block(
        self, meta: _ShuffleMeta, consumer: ExecutorId, map_id: int, reduce_id: int,
        pieces: Sequence[Tuple[int, int, int]], length: int, assembled: Optional[List[int]],
    ) -> np.ndarray:
        """A block staged in pieces, whole: ONE array filled from its pieces'
        views of ``consumer``'s received shards in order (``pieces`` as
        ``_locate_pieces`` gives them) — the one copy of that block.  The
        same two arms and the same guards a piece as ``_received_block`` has
        a block (which keeps them inline: it runs once a block of every job).
        Span ``read.block_assemble``."""
        row = self.row_bytes
        with span(
            "read.block_assemble", shuffle_id=meta.shuffle_id, map_id=map_id,
            reduce_id=reduce_id, executor=consumer, pieces=len(pieces), bytes=length,
        ):
            whole = np.empty(length, dtype=np.uint8)
            at = 0
            for rnd, src_row, nbytes in pieces:
                shards = meta.recv_device if meta.recv_shards is None else meta.recv_shards
                shard = shards[rnd][consumer]
                if shard is None:  # went with its executor
                    raise self._received_shards_lost(meta, consumer, reduce_id)
                if meta.recv_shards is None:  # host_recv_mode='device': D2H just the piece's rows
                    piece = np.asarray(shard[src_row : src_row + -(-nbytes // row)]).reshape(-1).view(np.uint8)
                else:
                    piece = shard[src_row * row :]
                if piece.size < nbytes:  # past the received prefix: never a short block
                    raise TransportError(
                        f"block ({meta.shuffle_id},{map_id},{reduce_id}): its piece at row {src_row} of "
                        f"round {rnd} ({nbytes} B) lies past what executor {consumer} received"
                    )
                whole[at : at + nbytes] = piece[:nbytes]
                at += nbytes
        if assembled is not None:
            assembled.append(length)
        return whole

    def _received_shards_lost(self, meta: _ShuffleMeta, consumer: ExecutorId, reduce_id: int) -> ExecutorLostError:
        return ExecutorLostError(
            consumer, self.membership.epoch,
            f"the shards it received of shuffle {meta.shuffle_id} died with it; reducer "
            f"{reduce_id}'s blocks are with the executors that staged them and their replicas",
        )

    def _locate_rows(
        self,
        meta: _ShuffleMeta,
        consumer: ExecutorId,
        map_id: int,
        reduce_id: int,
        starts: Optional[Dict[Tuple[int, int], int]] = None,
    ) -> Tuple[int, int, int, Optional[List[Tuple[int, int, int]]]]:
        """Row-granular location of a block inside ``consumer``'s received shard:
        (round, src_row, row_count, pieces).  Same offset math as
        ``locate_received_block`` in rows of ``row_bytes``.  A batch passes
        one ``starts`` dict for all its blocks: the (round, sender) chunk
        starts it has summed so far.  ``pieces`` is None for the block of one
        extent that every block but one longer than a peer region is; for
        such a block (``MapperInfo.splits``) it lists every piece in order,
        ``(round, src_row, bytes)`` — the first three figures are then its
        first piece's round and row and the rows of the WHOLE block — and a
        caller that cannot put pieces together refuses the block by name."""
        if meta.owner_of_reduce(reduce_id) != consumer:
            raise TransportError(
                f"reducer {reduce_id} is owned by executor "
                f"{meta.owner_of_reduce(reduce_id)}, not {consumer}"
            )
        if consumer in meta.recv_lost:
            raise self._received_shards_lost(meta, consumer, reduce_id)
        info = meta.mapper_infos.get(map_id)
        if info is None:
            raise TransportError(f"map {map_id} never committed")
        abs_offset, length = info.partitions[reduce_id]
        if length == 0:
            return 0, 0, 0, None
        rnd = info.round_of(reduce_id)
        sender = meta.map_owner[map_id]
        region_bytes = meta.region_bytes
        region_rel = abs_offset - consumer * region_bytes
        if not (0 <= region_rel < region_bytes):
            raise TransportError(
                f"block ({meta.shuffle_id},{map_id},{reduce_id}) offset {abs_offset} "
                f"not in consumer {consumer}'s region"
            )
        row = self.row_bytes
        chunk_start = None if starts is None else starts.get((rnd, sender))
        if chunk_start is None:
            chunk_start = int(meta.recv_sizes[rnd][consumer, :sender].sum())
            if starts is not None:
                starts[rnd, sender] = chunk_start
        if info.splits is not None and reduce_id in info.splits:
            return rnd, chunk_start + region_rel // row, -(-length // row), self._locate_pieces(
                meta, consumer, sender, info.splits[reduce_id], length, (map_id, reduce_id)
            )
        return rnd, chunk_start + region_rel // row, -(-length // row), None

    def _locate_pieces(
        self, meta: _ShuffleMeta, consumer: ExecutorId, sender: ExecutorId,
        pieces: Sequence[Tuple[int, int, int]], length: int, key: Tuple[int, int],
    ) -> List[Tuple[int, int, int]]:
        """``(round, src_row, bytes)`` of every piece of a split block, in
        order, inside ``consumer``'s received shards.  A commit record whose
        pieces do not add up to the block, or lie outside the consumer's
        region, is refused typed: a block is never handed out short."""
        region_bytes, row = meta.region_bytes, self.row_bytes
        base = consumer * region_bytes
        located = []
        for rnd, offset, nbytes in pieces:
            if not (0 < nbytes and base <= offset and offset + nbytes <= base + region_bytes
                    and 0 <= rnd < len(meta.recv_sizes)):
                raise TransportError(
                    f"block ({meta.shuffle_id},{key[0]},{key[1]}): piece (round {rnd}, offset "
                    f"{offset}, {nbytes} B) not in consumer {consumer}'s region"
                )
            chunk_start = int(meta.recv_sizes[rnd][consumer, :sender].sum())
            located.append((rnd, chunk_start + (offset - base) // row, nbytes))
        if sum(nbytes for _, _, nbytes in located) != length:
            raise TransportError(
                f"block ({meta.shuffle_id},{key[0]},{key[1]}): its {len(located)} pieces "
                f"hold {sum(nbytes for _, _, nbytes in located)} B of {length}"
            )
        return located

    def _gather_fn(self, impl: Optional[str], num_blocks: int, out_rows: int, exact: bool = False):
        """Cache compiled gathers; shapes are bucketed to powers of two (blocks
        padded with zero-count entries, which the kernels skip) so repeated
        fetches of varying batch sizes reuse a handful of compilations.
        ``exact``: ``out_rows`` is already one static figure a shuffle (the
        ordered read's) and is taken as it is."""
        from sparkucx_tpu.ops.pallas_kernels import build_block_gather

        b = 1 << max(num_blocks - 1, 0).bit_length()
        r = out_rows if exact else 1 << max(out_rows - 1, 0).bit_length()
        key = ("gather", impl, b, r)
        with self._lock:
            fn = self._exchange_cache.get(key)
            if fn is None:
                gather = build_block_gather(b, r, impl=impl)

                # One dispatch a gather: the (3, B) plan goes in whole, as the
                # host array it is, and is split inside the executable —
                # uploading it and slicing it on the device first was four
                # dispatches more, most of a small gather's host time.
                def block_gather(plan, src):
                    return gather(plan[0], plan[1], plan[2], src)

                fn = jax.jit(block_gather)  # the executable stays jit_block_gather
                fn.impl = gather.impl
                self._exchange_cache[key] = fn
        return fn, b, r

    def fetch_blocks_to_device(
        self,
        consumer: ExecutorId,
        shuffle_id: int,
        block_ids: Sequence[ShuffleBlockId],
        impl: Optional[str] = None,
    ) -> Tuple[object, np.ndarray]:
        """Device-side batch fetch: pack the requested blocks into ONE
        HBM-resident buffer on ``consumer``'s device — the bytes never visit the
        host.  The TPU analogue of the reference's reply packing (parallel
        reads into one pooled bounce buffer, single AM reply —
        UcxWorkerWrapper.scala:397-448), with the DMA engine playing the IO
        thread pool (ops/pallas_kernels.py).

        Returns ``(packed, entries)``: ``packed`` is a (rows, lane) int32
        ``jax.Array`` whose row count is the gather's power-of-two bucket, not
        the blocks' total (a slice to the total would build one executable
        per distinct total; rows no entry covers are unspecified);
        ``entries`` is (B, 2) int64 — per requested block, its starting ROW
        in ``packed`` and its true byte length.  Blocks of one staging round
        are packed back to back in request order; a later round's blocks
        start at the next bucket boundary.  Requires ``conf.keep_device_recv``.

        Two spans, once a call: ``read.device.locate`` (block table -> the
        (3, B) gather plan of every round, host only) and
        ``fetch.device_gather`` (one dispatch a round, the plan its argument;
        the gather itself is asynchronous).  Counter family ``deviceread{executor}``, once a call.
        """
        meta = self._retained_meta(shuffle_id)
        t0 = time.perf_counter_ns()
        with span("read.device.locate", shuffle_id=shuffle_id, blocks=len(block_ids)):
            entries, plans, rows = self._plan_device_fetch(meta, consumer, shuffle_id, block_ids, impl)
        locate_ns = time.perf_counter_ns() - t0
        with span("fetch.device_gather", shuffle_id=shuffle_id, blocks=len(block_ids)):
            packed = self._gather_plans(meta, consumer, plans)
        with self._lock:
            counters = self._device_read_stats[consumer]
            counters["tasks"] += 1
            counters["blocks"] += len(block_ids)
            counters["rows"] += rows
            counters["bytes"] += int(entries[:, 1].sum())
            counters["gathers"] += len(plans)
            counters["locate_ns"] += locate_ns
        return packed, entries

    def _retained_meta(self, shuffle_id: int) -> _ShuffleMeta:
        """The meta of an exchanged shuffle whose received shards are in HBM."""
        meta = self._exchanged_meta(shuffle_id)
        if meta.recv_device is None:
            raise TransportError("device shards not retained (conf.keep_device_recv=false)")
        return meta

    def _plan_device_fetch(self, meta, consumer, shuffle_id, block_ids, impl, slot_rows=1, out_rows=None):
        """Host half of a device fetch: every block located in ``consumer``'s
        received shards, and one gather plan a staging round — ``(round, fn,
        (3, B) starts/counts/outs)``.  Returns (entries, plans, payload rows).
        The ordered read's form: every block starts on a multiple of
        ``slot_rows`` rows and every round's segment is ``out_rows`` rows,
        exactly (``_ordered_geometry``)."""
        located = []  # (round, src_row, rows) per request
        for bid in block_ids:
            if bid.shuffle_id != shuffle_id:
                raise TransportError(f"block {bid} not from shuffle {shuffle_id}")
            rnd, src_row, rows, pieces = self._locate_rows(meta, consumer, bid.map_id, bid.reduce_id)
            if pieces is not None:
                raise SplitBlockError(
                    shuffle_id, bid.map_id, bid.reduce_id, len(pieces),
                    "the device fetch gathers a block out of one round's shard — read it on the host",
                )
            located.append((rnd, src_row, rows))

        entries = np.zeros((len(located), 2), dtype=np.int64)
        plans = []
        base = rows = 0
        for rnd in sorted({r for r, _, c in located if c}):
            idxs = [i for i, (r, _, c) in enumerate(located) if r == rnd and c]
            starts = np.asarray([located[i][1] for i in idxs], dtype=np.int32)
            counts = np.asarray([located[i][2] for i in idxs], dtype=np.int32)
            slots = -(-counts // slot_rows) * slot_rows
            outs = (np.cumsum(slots) - slots).astype(np.int32)
            total = int(counts.sum())
            for i, o in zip(idxs, outs):
                bid = block_ids[i]
                entries[i] = (base + int(o), meta.mapper_infos[bid.map_id].partitions[bid.reduce_id][1])
            fn, b_pad, r_pad = self._gather_fn(
                impl, len(idxs), total if out_rows is None else out_rows, exact=out_rows is not None
            )
            pad = b_pad - len(idxs)
            if pad:
                starts = np.pad(starts, (0, pad))
                counts = np.pad(counts, (0, pad))
                # Padding entries land at the packed end (count=0): the xla
                # lowering's searchsorted needs outs+counts non-decreasing;
                # the Pallas lowerings skip zero-count blocks.
                outs = np.pad(outs, (0, pad), constant_values=int(slots.sum()))
            plans.append((rnd, fn, np.stack([starts, counts, outs])))
            # the next round's blocks start where this round's bucket ends:
            # the segments are concatenated whole, never sliced to ``total``
            base += r_pad
            rows += total
        return entries, plans, rows

    def _gather_plans(self, meta, consumer, plans):
        """Device half: one gather dispatch a round, its (3, B) plan an
        argument of the call.  Every shape here is a power-of-two bucket, so
        tasks of different totals share their executables."""
        segments = []
        for rnd, fn, plan in plans:
            segments.append(fn(plan, meta.recv_device[rnd][consumer]))
        if not segments:
            return jnp.zeros(
                (0, self.row_bytes // 4), dtype=jnp.int32,
                device=self.transports[consumer].device,
            )
        return segments[0] if len(segments) == 1 else jnp.concatenate(segments, axis=0)

    # -- the ordered read: a task's records sorted on the device -----------

    def _ordered_geometry(self, meta: _ShuffleMeta, record_bytes: int) -> Tuple[int, int, int]:
        """(slot rows, slot records, capacity records) of an ordered read of
        this shuffle's ``record_bytes``-byte records.

        A block starts on a row and its records lie back to back from there,
        so rows and records meet again every ``lcm(record_bytes, row_bytes)``
        bytes: a *slot* (25 rows = 128 records of 100 B at 512 B rows).  A
        gather that starts every block on a slot boundary therefore leaves a
        buffer that IS a ``(records, lanes)`` array by reshape — no record
        straddles anything — with fewer than a slot of unused record places
        after each block, which the sort takes for padding.  ``capacity`` is
        the most record places any ONE reduce partition of the shuffle needs
        that way (its records, each block rounded up to a slot): one static
        figure a shuffle from the sealed size matrix, the same on every
        executor, so every task shares one executable."""
        slot_bytes = int(np.lcm(record_bytes, self.row_bytes))
        slot_rows, slot_records = slot_bytes // self.row_bytes, slot_bytes // record_bytes
        with self._lock:
            capacity = meta.ordered_capacity.get(record_bytes)
        if capacity is None:
            lengths = np.asarray(
                [[ln for _, ln in info.partitions] for info in meta.mapper_infos.values()],
                dtype=np.int64,
            ).reshape(-1, meta.num_reducers)
            slots = -(-lengths // slot_bytes)  # a ragged block is refused by name later
            capacity = int(slots.sum(axis=0).max(initial=0)) * slot_records
            with self._lock:
                meta.ordered_capacity[record_bytes] = capacity
        return slot_rows, slot_records, capacity

    def fetch_blocks_ordered(
        self,
        consumer: ExecutorId,
        shuffle_id: int,
        block_ids: Sequence[ShuffleBlockId],
        record_bytes: int,
        key_bytes: int,
        flat: bool = False,
    ) -> Tuple[object, int]:
        """The ordered form of ``fetch_blocks_to_device``: the blocks' records
        — ``record_bytes`` wide, back to back in every block — gathered and
        **sorted on ``consumer``'s device** by their first ``key_bytes`` bytes
        compared as unsigned bytes, most significant first (stable).

        Returns ``(records, n)``: ``records`` a ``(capacity, record_bytes / 4)``
        int32 ``jax.Array`` on the device whose first ``n`` rows are the
        records in key order and whose other rows are zero; ``n`` comes from
        the block table, no sync.  ``capacity`` is one figure a shuffle
        (``_ordered_geometry``) times the reduce partitions the blocks span,
        so a shuffle's tasks share the gather's and the sort's executables.
        ``flat``: the same rows as one ``(capacity * record_bytes / 4,)``
        array, row-major — what ``ordered_to_host`` takes.
        Blocks of several staging rounds are gathered a round a segment and
        sorted together.  A block that is no whole number of records raises
        ``RaggedBlockError``; shards not retained, the same typed error as
        the unordered fetch.

        Spans, once a call: ``read.device.locate``, ``fetch.device_gather``
        (as in the unordered fetch) and ``read.ordered.sort`` — the dispatch
        of ``ordered_records``: the time the call holds the thread, not the
        sort, which is asynchronous.  Counter family ``orderedread{executor}``.

        Safe from as many task threads as the caller runs: the read is in
        the family's ``in_flight`` gauges from its gather's dispatch until
        ``ordered_to_host`` has brought the array across or
        ``ordered_handed_out`` has given it away — one of the two ends every
        call, as ``TpuShuffleReader._read_ordered`` does."""
        from sparkucx_tpu.shuffle.reader import RaggedBlockError

        if record_bytes <= 0 or record_bytes % 4 or not 0 < key_bytes <= record_bytes:
            raise ValueError(
                f"an ordered read needs records of whole 32-bit lanes and a key inside them, "
                f"not {record_bytes} B records with {key_bytes} B keys"
            )
        meta = self._retained_meta(shuffle_id)
        slot_rows, slot_records, capacity = self._ordered_geometry(meta, record_bytes)
        capacity *= max(1, len({bid.reduce_id for bid in block_ids}))
        with span("read.device.locate", shuffle_id=shuffle_id, blocks=len(block_ids)):
            entries, plans, _ = self._plan_device_fetch(
                meta, consumer, shuffle_id, block_ids, None,
                slot_rows=slot_rows, out_rows=capacity // slot_records * slot_rows,
            )
            for i in np.flatnonzero(entries[:, 1] % record_bytes)[:1]:
                raise RaggedBlockError(int(entries[i, 1]), record_bytes, block_ids[i])
            n = int(entries[:, 1].sum()) // record_bytes
            if plans:
                # a power-of-two bucket of blocks, as the gather's plan: tasks
                # of nearby block counts share the sort's executable
                table = np.zeros((2, 1 << (len(block_ids) - 1).bit_length()), dtype=np.int32)
                table[0, : len(block_ids)] = entries[:, 0] // slot_rows * slot_records
                table[1, : len(block_ids)] = entries[:, 1] // record_bytes
        lanes = record_bytes // 4
        # what the task holds on the device from here on: a gathered segment
        # a round (each of the sort's capacity) and the sorted array
        held = (len(plans) + 1) * capacity * record_bytes
        with self._lock:
            counters = self._ordered_read_stats[consumer]
            counters["in_flight"] += 1
            counters["in_flight_device_bytes"] += held
            counters["in_flight_peak"] = max(counters["in_flight_peak"], counters["in_flight"])
            counters["in_flight_device_bytes_peak"] = max(
                counters["in_flight_device_bytes_peak"], counters["in_flight_device_bytes"]
            )
        try:
            if not plans:
                shape = (capacity * lanes,) if flat else (capacity, lanes)
                records = jnp.zeros(shape, dtype=jnp.int32, device=self.transports[consumer].device)
            else:
                with span("fetch.device_gather", shuffle_id=shuffle_id, blocks=len(block_ids)):
                    segments = [fn(plan, meta.recv_device[rnd][consumer]) for rnd, fn, plan in plans]
                with span("read.ordered.sort", shuffle_id=shuffle_id, records=n, capacity=capacity):
                    records = ordered_records(
                        table, *segments, record_lanes=lanes, key_bytes=key_bytes, flat=flat
                    )
        except BaseException:
            with self._lock:
                counters["in_flight"] -= 1
                counters["in_flight_device_bytes"] -= held
            raise
        with self._lock:
            counters["tasks"] += 1
            counters["records"] += n
            counters["bytes"] += n * record_bytes
            counters["capacity_records"] += capacity
            counters["sort_dispatches"] += bool(plans)
            self._ordered_held[id(records)] = (consumer, held)
        return records, n

    def _ordered_left(self, records) -> None:
        """The ordered read whose device array ``records`` is leaves the
        ``in_flight`` gauges; one that has left already is left alone.
        Caller holds the cluster lock."""
        consumer, held = self._ordered_held.pop(id(records), (None, 0))
        if consumer is not None:
            counters = self._ordered_read_stats[consumer]
            counters["in_flight"] -= 1
            counters["in_flight_device_bytes"] -= held

    def ordered_in_flight(self, consumer: ExecutorId) -> int:
        """Ordered reads in flight on ``consumer``'s device now: the gauge as
        one unlocked read of an int, for a span's argument."""
        return self._ordered_read_stats[consumer]["in_flight"]

    def ordered_handed_out(self, records) -> None:
        """An ordered read's device array has left the reader (``read_device()``
        hands it to its consumer; ``ordered_to_host`` has brought it across):
        the read is out of the ``in_flight`` gauges."""
        with self._lock:
            self._ordered_left(records)

    def ordered_to_host(self, consumer: ExecutorId, records, n: int, record_bytes: int) -> np.ndarray:
        """The first ``n`` records of a ``flat`` ordered read on the host: ONE
        D2H of the whole array (its shape is the shuffle's, so nothing is
        sliced on the device and nothing compiles), landed where received
        shards land (``_landing``), handed out as a read-only ``(n,
        record_bytes)`` ``uint8`` view of it.  Span ``read.ordered.d2h``: the
        wait until the bytes are host-readable — the sort and the transfer."""
        capacity = records.size * 4 // record_bytes
        pool = self._landing()
        t0 = time.perf_counter_ns()
        try:
            with span("read.ordered.d2h", records=n, bytes=n * record_bytes, capacity=capacity):
                with pool.allocating() if pool is not None else contextlib.nullcontext():
                    _start_landing(records)
                host = np.asarray(records)
        except BaseException:
            self.ordered_handed_out(records)
            raise
        d2h_ns = time.perf_counter_ns() - t0
        with self._lock:
            counters = self._ordered_read_stats[consumer]
            counters["d2h_bytes"] += host.nbytes
            counters["d2h_ns"] += d2h_ns
            self._ordered_left(records)
        batch = host.view(np.uint8).reshape(capacity, record_bytes)[:n]
        batch.flags.writeable = False
        return batch


class TpuShuffleTransport(ShuffleTransport):
    """Per-executor facet of the cluster — implements the transport trait."""

    def __init__(self, cluster: TpuShuffleCluster, executor_id: ExecutorId, device=None) -> None:
        self.cluster = cluster
        self.executor_id = executor_id
        self.device = device
        self.store = HbmBlockStore(cluster.conf, device=device, executor_id=executor_id)
        self._registry: Dict[BlockId, Block] = {}
        self._registry_lock = threading.Lock()
        self._outstanding: List[Request] = []
        self._outstanding_lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------

    def init(self) -> bytes:
        return f"tpu:{self.executor_id}".encode()

    def close(self) -> None:
        with self._outstanding_lock:
            for req in self._outstanding:
                if not req.completed():
                    req.cancel()
            self._outstanding.clear()
        self.store.close()

    @property
    def stats_agg(self) -> StatsAggregator:
        """The cluster's aggregator: where a reader on this facet flushes its
        ``read`` counters, once a task (``sparkucx_tpu_ops_*{kind="read"}``)."""
        return self.cluster.stats

    @property
    def recorder(self) -> FlightRecorder:
        """The cluster's flight recorder — exposed per-facet so the chaos
        harness (testing.faults.kill_executor) finds it on any transport."""
        return self.cluster.recorder

    def chaos_kill(self) -> None:
        """Chaos-harness death hook (testing.faults.kill_executor): what the
        executor's process held at its death goes with it, like a dead
        process's memory.  The store is closed — its staging, spills and the
        replicas it held for others become unreachable — and the cluster lets
        go of the shards this executor received of every exchanged shuffle
        (``drop_received_of``: a read addressed to them is
        ``ExecutorLostError``).  What others hold of ITS output stays: the
        replicas of its sealed rounds on its ring successors, the shards
        other executors received from it.  The loss is reported to cluster
        membership, the collective-plane analogue of a peer observing
        ECONNRESET."""
        self.store.close()
        self.cluster.membership.mark_dead(self.executor_id, "chaos kill_executor")
        self.cluster.drop_received_of(self.executor_id)

    def peer_alive(self, executor_id: ExecutorId) -> bool:
        """Whether the cluster's membership holds ``executor_id`` alive: what
        a reader's pull path asks before it tries — or sleeps on — a
        candidate (``TpuShuffleReader._retry_fetch``)."""
        return self.cluster.membership.is_alive(executor_id)

    @property
    def membership_epoch(self) -> int:
        return self.cluster.membership.epoch

    def restart(self) -> None:
        """Come back as a restarted executor process does
        (``TpuShuffleCluster.rejoin_executor``): outstanding requests
        cancelled, no block registered, and a NEW store — no shuffle, no
        replica, no free list, no spill directory, counters from zero; what
        the old one held went with the process.  What ``kill_executor``
        latched on this transport is cleared: the executor can be lost again.
        Holders of the executor's store look it up here (the manager's
        resolver does), never keep the old one."""
        self.close()
        with self._registry_lock:
            blocks, self._registry = list(self._registry.values()), {}
        for block in blocks:
            block.close()
        self.store = HbmBlockStore(
            self.cluster.conf, device=self.device, executor_id=self.executor_id
        )
        self._chaos_killed = False

    def add_executor(self, executor_id: ExecutorId, address: bytes) -> None:
        # Single-controller mode: membership is the cluster's mesh; nothing to do.
        pass

    def remove_executor(self, executor_id: ExecutorId) -> None:
        pass

    # -- server side (upstream peer-serving registry, §3.5 parity) ---------

    def register(self, block_id: BlockId, block: Block) -> None:
        with self._registry_lock:
            self._registry[block_id] = block

    def mutate(self, block_id: BlockId, block: Block, callback: Optional[OperationCallback]) -> None:
        with self._registry_lock:
            old = self._registry.get(block_id)
            if old is not None:
                with old.lock:
                    self._registry[block_id] = block
            else:
                self._registry[block_id] = block
        if callback is not None:
            callback(OperationResult(OperationStatus.SUCCESS))

    def unregister(self, block_id: BlockId) -> None:
        with self._registry_lock:
            block = self._registry.pop(block_id, None)
        if block is not None:
            block.close()  # release serving resources (cached mmaps) eagerly

    def unregister_shuffle(self, shuffle_id: int) -> None:
        with self._registry_lock:
            doomed = [
                b for b in self._registry
                if isinstance(b, ShuffleBlockId) and b.shuffle_id == shuffle_id
            ]
            blocks = [self._registry.pop(b) for b in doomed]
        for block in blocks:
            block.close()

    def registered_block(self, block_id: BlockId) -> Optional[Block]:
        with self._registry_lock:
            return self._registry.get(block_id)

    # -- client side -------------------------------------------------------

    def fetch_blocks_by_block_ids(
        self,
        executor_id: ExecutorId,
        block_ids: Sequence[BlockId],
        result_buffers: Sequence[MemoryBlock],
        callbacks: Sequence[Optional[OperationCallback]],
    ) -> List[Request]:
        """Post-exchange batch fetch: each block is a local slice of this
        executor's received shard (``executor_id`` names the *sender*, kept for
        trait parity; the data already arrived via the collective)."""
        if not (len(block_ids) == len(result_buffers) == len(callbacks)):
            raise ValueError("length mismatch")
        requests = []
        for bid, buf, cb in zip(block_ids, result_buffers, callbacks):
            req = Request(OperationStats())
            try:
                if not isinstance(bid, ShuffleBlockId):
                    raise TransportError(f"TpuShuffleTransport fetches ShuffleBlockIds, got {bid!r}")
                view, length = self.cluster.locate_received_block(
                    self.executor_id, bid.shuffle_id, bid.map_id, bid.reduce_id
                )
                dest = buf.host_view()
                if length > dest.size:
                    raise TransportError(
                        f"block {bid} ({length} B) exceeds result buffer ({dest.size} B)"
                    )
                dest[:length] = view
                buf.size = length
                req.stats.mark_done(recv_size=length)
                result = OperationResult(OperationStatus.SUCCESS, stats=req.stats, data=buf)
            except Exception as e:
                req.stats.mark_done()
                err = e if isinstance(e, TransportError) else TransportError(str(e))
                result = OperationResult(OperationStatus.FAILURE, error=err, stats=req.stats)
            req.complete(result)
            if cb is not None:
                cb(result)
            requests.append(req)
        return requests

    def resident_blocks(
        self, block_ids: Sequence[ShuffleBlockId], assembled: Optional[List[int]] = None
    ) -> List[np.ndarray]:
        """The blocks this executor received, where they lie: a read-only
        uint8 view a block, in order, of its received shard
        (``TpuShuffleCluster.resident_blocks``) — no buffer, no copy, no
        ``Request`` (a block staged in pieces: one array put together from
        them, its length appended to ``assembled``).  What ``TpuShuffleReader`` takes in place of
        ``fetch_blocks_by_block_ids`` for a window addressed to its own
        executor; raises ``TransportError`` where that fetch would have
        completed a block with ``FAILURE``."""
        return self.cluster.resident_blocks(self.executor_id, block_ids, assembled)

    def fetch_blocks_device(
        self,
        block_ids: Sequence[ShuffleBlockId],
        impl: Optional[str] = None,
        shuffle_id: Optional[int] = None,
    ) -> Tuple[object, np.ndarray]:
        """Device-resident batch fetch: pack these blocks into one HBM buffer on
        this executor's device (see ``TpuShuffleCluster.fetch_blocks_to_device``).
        All blocks must be from one shuffle; ``shuffle_id`` names it for a
        request that may be empty (``TpuShuffleReader.read_device`` on a
        reducer no mapper wrote to)."""
        if shuffle_id is None:
            if not block_ids:
                raise ValueError("no block ids")
            shuffle_id = block_ids[0].shuffle_id
        return self.cluster.fetch_blocks_to_device(self.executor_id, shuffle_id, block_ids, impl=impl)

    def fetch_blocks_ordered(
        self, block_ids: Sequence[ShuffleBlockId], shuffle_id: int, record_bytes: int, key_bytes: int,
        flat: bool = False,
    ) -> Tuple[object, int]:
        """These blocks' fixed-width records gathered and sorted by key on
        this executor's device (``TpuShuffleCluster.fetch_blocks_ordered``)."""
        return self.cluster.fetch_blocks_ordered(
            self.executor_id, shuffle_id, block_ids, record_bytes, key_bytes, flat=flat
        )

    def ordered_to_host(self, records, n: int, record_bytes: int) -> np.ndarray:
        """A ``flat`` ordered read's first ``n`` records on the host, in one
        D2H (``TpuShuffleCluster.ordered_to_host``)."""
        return self.cluster.ordered_to_host(self.executor_id, records, n, record_bytes)

    def ordered_handed_out(self, records) -> None:
        """An ordered read's device array goes to its consumer as it is
        (``TpuShuffleCluster.ordered_handed_out``)."""
        self.cluster.ordered_handed_out(records)

    def ordered_in_flight(self) -> int:
        """Ordered reads in flight on this executor's device now (the
        ``orderedread`` gauge ``in_flight``)."""
        return self.cluster.ordered_in_flight(self.executor_id)

    def progress(self) -> None:
        """Poll outstanding async work (non-blocking).  Post-exchange fetches
        complete synchronously (local memory), so this mostly drives the
        pull-fallback path and keeps the trait's polling contract alive."""
        with self._outstanding_lock:
            self._outstanding = [r for r in self._outstanding if not r.completed()]

    # -- staged-store extensions ------------------------------------------

    def init_executor(self, num_mappers: int, num_reducers: int) -> None:
        # Store sizing happens in cluster.create_shuffle; the reference's NVKV
        # handshake (UcxWorkerWrapper.scala:286-322) has no wire step here.
        pass

    def commit_block(self, mapper_info_blob: bytes, callback: Optional[OperationCallback] = None) -> None:
        info = MapperInfo.unpack(mapper_info_blob)
        self.cluster.commit_mapper(info)
        if callback is not None:
            callback(OperationResult(OperationStatus.SUCCESS))

    def fetch_block(
        self,
        executor_id: ExecutorId,
        shuffle_id: int,
        map_id: int,
        reduce_id: int,
        result_buffer: MemoryBlock,
        callback: Optional[OperationCallback] = None,
    ) -> Request:
        """Pull fallback: direct read of a peer's staged store (per-block AM path
        ids 3/4 — the straggler/retry escape hatch next to the collective)."""
        req = Request(OperationStats())

        def poll() -> bool:
            try:
                payload = self.cluster.transports[executor_id].store.read_block(
                    shuffle_id, map_id, reduce_id
                )
                dest = result_buffer.host_view()
                if len(payload) > dest.size:
                    raise TransportError(
                        f"staged block ({len(payload)} B) exceeds result buffer ({dest.size} B)"
                    )
                dest[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
                result_buffer.size = len(payload)
                req.stats.mark_done(recv_size=len(payload))
                result = OperationResult(OperationStatus.SUCCESS, stats=req.stats, data=result_buffer)
            except Exception as e:
                req.stats.mark_done()
                err = e if isinstance(e, TransportError) else TransportError(str(e))
                result = OperationResult(OperationStatus.FAILURE, error=err, stats=req.stats)
            req.complete(result)
            if callback is not None:
                callback(result)
            return True

        req.attach_poll(poll)
        with self._outstanding_lock:
            self._outstanding.append(req)
        return req

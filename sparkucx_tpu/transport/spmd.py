"""Multi-controller (SPMD) shuffle executor — the multi-host data plane.

``TpuShuffleCluster`` (transport/tpu.py) drives all executors from one
controller — right for one TPU VM.  A TPU *pod* is multi-controller: one process
per host, each owning its local chips, every process executing the same program.
This module is that deployment: the counterpart of the reference's one
``UcxShuffleTransport`` per Spark executor wired together by driver RPC
(CommonUcxShuffleManager.scala:67-99), with

* the JAX coordination service as the driver (``jax.distributed.initialize`` —
  parallel/mesh.py), after which ``jax.devices()`` shows the global mesh the way
  ``IntroduceAllExecutors`` shows the executor set,
* the collective exchange compiled over the **global** mesh and executed by all
  processes in lockstep (XLA ICI/DCN collectives — the NCCL/MPI analogue),
* the peer socket plane (transport/peer.py) for what stays point-to-point:
  MapperInfo commit broadcast (AM id 2) and the per-block pull fallback
  (AM ids 3/4).

SPMD discipline: every process must call ``run_exchange`` for each shuffle in
the same order — the same contract as every collective backend (SURVEY.md
section 7 "multi-controller discipline").
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.definitions import MapperInfo
from sparkucx_tpu.core.operation import ExecutorLostError, SplitBlockError, TransportError
from sparkucx_tpu.core.transport import ExecutorId
from sparkucx_tpu.ops.exchange import bucket_send_rows
from sparkucx_tpu.ops.planner import PlanContext, PlanSignals, make_planner
from sparkucx_tpu.ops.skew import (
    chunk_size_rows,
    reassemble_round,
    slice_subround,
)
from sparkucx_tpu.store.hbm_store import HbmBlockStore, default_peer_ranges
from sparkucx_tpu.transport.executor import (
    build_plan_exchange,
    execute_plan,
    validate_host_recv_mode,
)
from sparkucx_tpu.transport.peer import PeerTransport
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.stats import StatsAggregator
from sparkucx_tpu.utils.trace import TRACER, instant, merge_events, span

logger = get_logger("transport.spmd")


class SpmdShuffleExecutor:
    """One process of the multi-controller deployment."""

    def __init__(
        self,
        conf: Optional[TpuShuffleConf] = None,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ) -> None:
        import jax
        from jax.sharding import Mesh

        if coordinator_address is not None:
            # Must run before anything touches the XLA backend (including
            # jax.process_count()); tolerate an already-initialized service.
            from jax._src import distributed as _dist

            if _dist.global_state.client is None:
                jax.distributed.initialize(
                    coordinator_address, num_processes=num_processes, process_id=process_id
                )
        self.conf = conf or TpuShuffleConf()
        self.num_executors = jax.process_count()
        self.executor_id: ExecutorId = jax.process_index()

        # One mesh slot per process: its first local device (executor<->chip
        # mapping; multi-device hosts designate a lead chip for the exchange).
        per_proc: Dict[int, object] = {}
        for d in jax.devices():
            per_proc.setdefault(d.process_index, d)
        self.mesh = Mesh(
            np.array([per_proc[p] for p in range(self.num_executors)]),
            (self.conf.mesh_axis_name,),
        )
        self.device = per_proc[self.executor_id]

        # The store seals onto this process's lead device, so device-staged
        # rounds (conf.device_staging) hand the exchange an HBM-resident
        # payload with no host round trip.
        self.store = HbmBlockStore(
            self.conf, device=self.device, executor_id=self.executor_id
        )
        self.peer = PeerTransport(self.conf, executor_id=self.executor_id, store=self.store)
        # Liveness view fed by the wire plane (peer send failures + gossiped
        # MEMBER_SUSPECT/MEMBER_REJOIN frames).  The SPMD exchange cannot
        # shrink unilaterally — every process executes the same compiled
        # collective — so a degraded view fails the superstep FAST with a
        # typed error instead of hanging in a collective the dead process
        # will never join.  Elastic shrink/regrow is the single-controller
        # cluster's recovery path (transport/tpu.py).
        from sparkucx_tpu.parallel.membership import ClusterMembership

        self.membership = ClusterMembership(
            range(self.num_executors), self.conf.membership_suspect_after_ms
        )
        self.peer.membership = self.membership
        self._mapper_infos: Dict[int, Dict[int, MapperInfo]] = {}
        self._recv: Dict[int, Tuple[List[np.ndarray], List[np.ndarray]]] = {}
        self._meta: Dict[int, Tuple[int, int, List[Tuple[int, int]]]] = {}
        self._exchange_fns: Dict[int, object] = {}
        #: memmap spill files per shuffle as (path, charged nbytes) —
        #: host_recv_mode='memmap'; the refund uses the tracked charge.
        #: _host_shard runs on the pipeline DRAIN worker while remove_shuffle
        #: runs on the caller thread — both sides take _spill_lock.
        self._recv_spill: Dict[int, List[Tuple[str, int]]] = {}  #: guarded by self._spill_lock
        self._recv_spill_bytes = 0  #: guarded by self._spill_lock (vs conf.spill_disk_cap_bytes)
        self._spill_lock = threading.Lock()
        #: per-stage pipeline timings (same occupancy view as the cluster's)
        self.stats = StatsAggregator()
        #: the exchange planner (ops/planner.py) — the collective-schedule
        #: fields of its plans derive only from all-gathered quantities, so
        #: every process stays in lockstep whatever the local telemetry says
        self.planner = make_planner(self.conf)
        # ONE host_recv_mode gate (transport/executor.py): fail at
        # construction, not after round 0's collective has run on every host
        # — 'device' needs retained HBM shards this executor releases after
        # the collective; anything else is a typo.
        validate_host_recv_mode(
            self.conf.host_recv_mode,
            allowed=("array", "memmap"),
            where="the SPMD executor",
        )

    # -- control plane -----------------------------------------------------

    def init(self) -> bytes:
        return self.peer.init()

    def add_executor(self, executor_id: ExecutorId, address: bytes) -> None:
        self.peer.add_executor(executor_id, address)

    def close(self) -> None:
        self.peer.close()

    # -- obs plane ---------------------------------------------------------

    def export_trace(self, path: str) -> int:
        """Merge the whole mesh's trace buffers into ONE Perfetto file with
        pid = executor id: every peer's ring is pulled over the TRACE_PULL
        Active Message, the local ring read directly.  Unreachable peers are
        skipped — a postmortem export must work on a degraded mesh."""
        buffers = [
            [dict(e, eid=e.get("eid", self.executor_id)) for e in TRACER.events]
        ]
        for eid in range(self.num_executors):
            if eid == self.executor_id:
                continue
            try:
                buf = self.peer.pull_trace(eid)
                buffers.append(
                    [dict(e, eid=e.get("eid", eid)) for e in buf.get("events", [])]
                )
            except (TransportError, OSError):
                continue
        merged = merge_events(buffers)
        import json as _json

        with open(path, "w") as f:
            _json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
        return len(merged)

    def metrics_text(self) -> str:
        """Prometheus exposition for the whole mesh: the local registry's
        text plus every reachable peer's METRICS_PULL reply, concatenated
        (rows stay distinct — each executor labels its own samples)."""
        parts = [self.peer.metrics.prometheus_text()]
        for eid in range(self.num_executors):
            if eid == self.executor_id:
                continue
            try:
                parts.append(self.peer.pull_metrics(eid))
            except (TransportError, OSError):
                continue
        return "".join(parts)

    # -- shuffle lifecycle -------------------------------------------------

    def create_shuffle(self, shuffle_id: int, num_mappers: int, num_reducers: int) -> None:
        ranges = default_peer_ranges(num_reducers, self.num_executors)
        self.store.create_shuffle(shuffle_id, num_mappers, num_reducers, peer_ranges=ranges)
        self._meta[shuffle_id] = (num_mappers, num_reducers, ranges)
        self._mapper_infos[shuffle_id] = {}

    def map_owner(self, map_id: int) -> ExecutorId:
        """Round-robin map-task placement convention (all processes agree)."""
        return map_id % self.num_executors

    def commit_map(self, writer) -> MapperInfo:
        """Commit a local map task: record locally + broadcast AM id 2."""
        info = writer.commit()
        self._mapper_infos[info.shuffle_id][info.map_id] = info
        self.peer.commit_block(info.pack())
        return info

    def _await_commits(self, shuffle_id: int, timeout: float = 60.0) -> None:
        """Wait until every map's MapperInfo arrived (local or via AM id 2)."""
        num_mappers, _, _ = self._meta[shuffle_id]
        infos = self._mapper_infos[shuffle_id]
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for m in self.store.committed_map_ids(shuffle_id):
                if m not in infos:
                    # peer commit landed in the store table; reconstruct info
                    infos[m] = self.store.mapper_info(shuffle_id, m)
            if len(infos) >= num_mappers:
                return
            time.sleep(0.005)
        raise TransportError(
            f"timed out waiting for map commits ({len(infos)}/{num_mappers})"
        )

    # -- the superstep -----------------------------------------------------

    def run_exchange(self, shuffle_id: int) -> None:
        """Collective superstep — ALL processes must call this in lockstep."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        snap = self.membership.snapshot()
        if snap["dead"]:
            # fail before entering the collective: a lockstep exchange with a
            # dead process hangs every live process until the backend timeout
            first_dead = min(snap["dead"])
            raise ExecutorLostError(
                first_dead,
                snap["epoch"],
                "SPMD exchange requires every process; degraded recovery is "
                f"the single-controller cluster's path — dead: {snap['dead']}",
            )
        self._await_commits(shuffle_id)
        rounds = self.store.seal(shuffle_id)
        n = self.num_executors
        ax = self.conf.mesh_axis_name
        send_rows, lane = int(rounds[0][0].shape[0]), int(rounds[0][0].shape[1])
        staging_slot = send_rows // n

        data_sharding = NamedSharding(self.mesh, P(ax, None))
        sizes_sharding = NamedSharding(self.mesh, P(ax, None))

        # Agree on the plan inputs cluster-wide (spill rounds and skew may
        # differ per host, but every process must derive the IDENTICAL
        # collective schedule): a one-int round-count all_gather, then one
        # (n, rounds + 1) gather carrying each process's per-round hottest
        # lane and its used-row total — the geometry the planner's
        # collective-schedule decisions are a pure function of.  Local
        # telemetry (PlanSignals) only steers serve-plane fields that never
        # enter a collective.
        my_rounds = np.array([[len(rounds)]], dtype=np.int32)
        rc = jax.make_array_from_single_device_arrays(
            (n, 1), sizes_sharding, [jax.device_put(my_rounds, self.device)]
        )
        num_rounds = int(np.max(jax.jit(lambda x: jnp.max(x), out_shardings=None)(rc)))
        local = np.zeros((1, num_rounds + 1), dtype=np.int32)
        for rnd in range(min(len(rounds), num_rounds)):
            local[0, rnd] = int(np.max(rounds[rnd][1], initial=0))
        local[0, num_rounds] = sum(int(np.sum(r[1])) for r in rounds)
        mx = jax.make_array_from_single_device_arrays(
            (n, num_rounds + 1), sizes_sharding, [jax.device_put(local, self.device)]
        )
        maxes, total = jax.jit(
            lambda x: (jnp.max(x[:, :-1], axis=0), jnp.sum(x[:, -1])),
            out_shardings=None,
        )(mx)
        ctx = PlanContext(
            num_executors=n,
            staging_slot_rows=staging_slot,
            round_max_rows=tuple(int(v) for v in np.asarray(maxes)),
            used_rows_total=int(total),
            row_bytes=self.conf.block_alignment,
            platform=self.mesh.devices.reshape(-1)[0].platform,
            # raw block shuffles: no aggregation geometry (agg_partial False)
            # -> plan.combine is always 'off' here; the fields are filled by
            # the aggregation plane.  All-gathered geometry only (maxes/total
            # above), so every process derives the SAME tier — SPMD lockstep
            signals=PlanSignals.from_registry(self.peer.metrics),
        )
        plan = self.planner.plan(ctx)
        instant(
            "exchange.plan",
            shuffle_id=shuffle_id,
            planner=type(self.planner).__name__,
            **plan.describe(),
            **{f"signal_{k}": v for k, v in ctx.signals.describe().items()},
        )
        q = plan.slot_rows
        # Capacity bucketing (same discipline as the cluster's _exchange_fn):
        # varying-size shuffles share one compiled exchange per power-of-two
        # slot bucket; payloads relocate into the bucketed slot layout below.
        bucketed = q * n
        fn = self._exchange_fn_for(bucketed, lane, plan.lowering)
        # a sealed round already on the device is donated as it is where the
        # plan's slot is the staging slot: so is the copy of a completed round
        # the store put there before the seal (HbmBlockStore.take_early_round);
        # a window of one would have to be cut on the device, so such a plan
        # lets the copies go and puts the host rounds as ever
        donates = plan.single_shot and q == staging_slot
        if not donates:
            self.store.release_early_rounds(shuffle_id)

        def _submit(rnd, chunk, nchunks):
            """One sub-round's assemble + H2D + collective dispatch (all JAX
            async dispatch — SPMD order is preserved because every process
            submits the same plan's sub-rounds in the same order, whatever
            the depth)."""
            round_bytes = bucketed * lane * 4
            with span(
                "exchange.assemble",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk, bytes=round_bytes,
            ):
                if rnd < len(rounds):
                    payload, sizes = rounds[rnd]
                    sub_sizes = chunk_size_rows(sizes, chunk, q)
                    if donates and not isinstance(payload, jax.Array):
                        early = self.store.take_early_round(shuffle_id, rnd)
                        if early is not None:
                            payload = early
                    if isinstance(payload, jax.Array):
                        # Sealed straight onto the device (device staging or
                        # the single-round host seal): relocate/slice
                        # on-device, no host round trip; device_put is then a
                        # no-op pin.  A single-shot plan whose bucket equals
                        # the staging slot donates the sealed payload as-is
                        # (historical fast path).
                        piece = (
                            payload if donates else slice_subround(payload, n, chunk, q, xp=jnp)
                        )
                    else:
                        piece = slice_subround(np.asarray(payload), n, chunk, q)
                else:
                    piece = np.zeros((bucketed, lane), dtype=np.int32)
                    sub_sizes = np.zeros(n, dtype=np.int32)
            # the time the device_put calls hold this lane, not the DMA
            with span(
                "exchange.h2d",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk, bytes=round_bytes + n * 4,
            ):
                local_payload = jax.device_put(piece, self.device)
                local_sizes = jax.device_put(
                    np.reshape(np.asarray(sub_sizes), (1, n)).astype(np.int32), self.device
                )
                data = jax.make_array_from_single_device_arrays(
                    (n * bucketed, lane), data_sharding, [local_payload]
                )
                size_mat = jax.make_array_from_single_device_arrays(
                    (n, n), sizes_sharding, [local_sizes]
                )
            with span(
                "exchange.collective",
                shuffle_id=shuffle_id, round=rnd, chunk=chunk, rows=bucketed,
            ):
                recv, rs = fn(data, size_mat)
            my_recv = next(
                s.data for s in recv.addressable_shards if s.device == self.device
            )
            my_rs = next(
                s.data for s in rs.addressable_shards if s.device == self.device
            )
            # start D2H of this process's shard while later sub-rounds run
            my_recv.copy_to_host_async()
            my_rs.copy_to_host_async()
            return my_recv, my_rs

        def _drain_chunk(rnd, chunk, nchunks, ticket):
            """Materialize one sub-round's shard host-side (drain worker)."""
            my_recv, my_rs = ticket
            return (
                np.asarray(my_recv).reshape(-1).view(np.uint8),
                np.asarray(my_rs).reshape(-1),
            )

        def _finish_round(rnd, nchunks, parts):
            """Emit one staging round's receive state: single-shot rounds
            keep their whole padded shard (historical layout); chunked rounds
            splice back into the exact single-shot layout (bit-equality
            pinned in tests/test_skew.py).  host_recv_mode applies here, on
            the drain worker — memmap spill stays off the submit thread."""
            if plan.single_shot:
                raw, sizes = parts[0]
                shard = self._host_shard(shuffle_id, rnd, raw)
                used = int(sizes.sum())
                return shard, sizes, (used, bucketed - used)
            sub_sizes = [s for _, s in parts]
            logical = np.sum(sub_sizes, axis=0).astype(np.int32)
            assembled = reassemble_round(
                [b for b, _ in parts], sub_sizes, self.conf.block_alignment
            )
            shard = self._host_shard(shuffle_id, rnd, assembled)
            used = int(logical.sum())
            return shard, logical, (used, nchunks * bucketed - used)

        try:
            results = execute_plan(
                plan,
                submit=_submit,
                drain_chunk=_drain_chunk,
                finish_round=_finish_round,
                result_bytes=lambda r: int(r[1].sum()) * self.conf.block_alignment,
                # per-round staging occupancy of this process's shard (the slot
                # padding the planner's quota/chunking exists to shrink)
                occupancy=lambda r: r[2],
                stats=self.stats,
            )
        except BaseException:
            self.store.release_early_rounds(shuffle_id)  # no HBM for rounds never sent
            raise
        recv_shards = [shard for shard, _, _ in results]
        recv_sizes_rows = [sizes for _, sizes, _ in results]
        for sizes in recv_sizes_rows:
            active = int(np.count_nonzero(sizes))
            self.stats.record_rows("exchange.lanes", active, sizes.size - active)
        self._recv[shuffle_id] = (recv_shards, recv_sizes_rows)
        logger.info(
            "exchange done: shuffle=%d rounds=%d subrounds=%d slot=%d depth=%d "
            "single_shot=%s",
            shuffle_id, num_rounds, plan.num_subrounds, q,
            plan.pipeline_depth, plan.single_shot,
        )

    def _exchange_fn_for(self, bucketed_rows: int, lane: int, lowering=None):
        """Compiled-exchange cache lookup, keyed on the bucketed slot layout.

        ``bucketed_rows`` is re-bucketed here (``bucket_send_rows`` is a fixed
        point on pow2-slot multiples, so plans — whose ``slot_rows`` are
        already pow2-bucketed — pass through unchanged) so a raw staging size
        can never become a compile-cache key.  The lowering itself lives in
        ``transport/executor.build_plan_exchange`` — this method owns only
        the cache."""
        n = self.num_executors
        bucketed_rows = bucket_send_rows(bucketed_rows, n)
        from sparkucx_tpu.ops.ici_exchange import resolve_exchange_impl

        impl = resolve_exchange_impl(
            lowering or self.conf.exchange_impl,
            self.mesh.devices.reshape(-1)[0].platform,
            n,
        )
        key = (bucketed_rows, lane, self.conf.num_slices, impl)
        fn = self._exchange_fns.get(key)
        if fn is None:
            fn = build_plan_exchange(
                self.mesh,
                num_executors=n,
                send_rows=bucketed_rows,
                lane=lane,
                axis_name=self.conf.mesh_axis_name,
                impl=impl,
                num_slices=self.conf.num_slices,
            )
            self._exchange_fns[key] = fn
        return fn

    # -- post-exchange reads ----------------------------------------------

    def owner_of_reduce(self, shuffle_id: int, reduce_id: int) -> ExecutorId:
        _, _, ranges = self._meta[shuffle_id]
        for p, (s, e) in enumerate(ranges):
            if s <= reduce_id < e:
                return p
        raise ValueError(f"reduce {reduce_id} unowned")

    def read_received_block(self, shuffle_id: int, map_id: int, reduce_id: int) -> bytes:
        """Read a block this executor received in the exchange."""
        if self.owner_of_reduce(shuffle_id, reduce_id) != self.executor_id:
            raise TransportError(
                f"reducer {reduce_id} not owned by executor {self.executor_id}"
            )
        if shuffle_id not in self._recv:
            raise TransportError(f"shuffle {shuffle_id} not exchanged")
        info = self._mapper_infos[shuffle_id].get(map_id)
        if info is None:
            raise TransportError(f"map {map_id} never committed")
        abs_offset, length = info.partitions[reduce_id]
        if length == 0:
            return b""
        if info.splits is not None and reduce_id in info.splits:
            raise SplitBlockError(
                shuffle_id, map_id, reduce_id, len(info.splits[reduce_id]),
                "the SPMD executor reads a block out of one round's received shard",
            )
        rnd = info.round_of(reduce_id)
        sender = self.map_owner(map_id)
        region_bytes = self.store.region_bytes(shuffle_id)
        region_rel = abs_offset - self.executor_id * region_bytes
        shards, sizes_rows = self._recv[shuffle_id]
        chunk_start = int(sizes_rows[rnd][:sender].sum()) * self.conf.block_alignment
        start = chunk_start + region_rel
        return bytes(shards[rnd][start : start + length])

    def _host_shard(self, shuffle_id: int, rnd: int, host: np.ndarray) -> np.ndarray:
        """Apply ``conf.host_recv_mode`` to one received round: 'array' keeps
        the RAM copy (historical behavior), 'memmap' spills it to a read-only
        disk mapping so per-host RSS stays bounded by one round — the same
        budget discipline as the single-controller cluster (transport/tpu.py
        ``_memmap_round``): every spilled byte reserves against
        ``spill_disk_cap_bytes`` up front and a failed write refunds and
        unlinks (mode validity is checked at construction)."""
        if self.conf.host_recv_mode == "array":
            return host
        import os
        import tempfile

        cap = self.conf.spill_disk_cap_bytes
        nbytes = int(host.nbytes)
        if nbytes == 0:
            # nothing received this round (quota-path tight shards can be
            # empty); np.memmap cannot map a zero-byte file — keep the array
            return host
        # reserve-then-write: check+charge atomic under the spill lock (the
        # drain worker charges here while remove_shuffle refunds concurrently)
        with self._spill_lock:
            if cap and self._recv_spill_bytes + nbytes > cap:
                raise TransportError(
                    f"received-shard spill would exceed spill_disk_cap_bytes "
                    f"({self._recv_spill_bytes + nbytes} > {cap}) on executor "
                    f"{self.executor_id}"
                )
            self._recv_spill_bytes += nbytes
        spill_dir = self.conf.spill_dir
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
        fd, path = tempfile.mkstemp(
            prefix=f"sparkucx_tpu_spmd_recv_s{shuffle_id}_r{rnd}_e{self.executor_id}_",
            dir=spill_dir,
        )
        os.close(fd)
        shape = host.shape
        try:
            mm = np.memmap(path, dtype=np.uint8, mode="w+", shape=shape)
            mm[:] = host
            mm.flush()
        except BaseException:
            with self._spill_lock:
                self._recv_spill_bytes -= nbytes
            try:
                os.unlink(path)
            except OSError:
                pass
            raise
        del mm, host  # drop the dirty mapping; reopen read-only (RSS falls)
        # track the CHARGED bytes with the path: the refund must mirror the
        # charge, not os.path.getsize (block-size rounding / sparse files /
        # truncation by an operator would drift _recv_spill_bytes permanently)
        with self._spill_lock:
            self._recv_spill.setdefault(shuffle_id, []).append((path, nbytes))
        return np.memmap(path, dtype=np.uint8, mode="r", shape=shape)

    def remove_shuffle(self, shuffle_id: int) -> None:
        self.store.remove_shuffle(shuffle_id)
        self._recv.pop(shuffle_id, None)
        self._meta.pop(shuffle_id, None)
        self._mapper_infos.pop(shuffle_id, None)
        import os

        with self._spill_lock:
            doomed = self._recv_spill.pop(shuffle_id, [])
        for path, nbytes in doomed:
            try:
                os.unlink(path)
                freed = True
            except FileNotFoundError:
                freed = True  # already gone: still refund
            except OSError:
                freed = False  # still on disk: keep it charged
            if freed:
                with self._spill_lock:
                    self._recv_spill_bytes -= nbytes

"""Elastic mesh: shrink/regrow with degraded-mode exchange recovery.

Pins the PR's elasticity contracts end to end:

* ``ClusterMembership`` — observation-driven liveness, local epochs,
  debounced suspicion, idempotent transitions,
* ``degraded_plan`` — pow2 shrink + wave decomposition invariants,
* the headline chaos scenario: kill an executor MID-SUPERSTEP at
  ``replication.factor=1`` and the shuffle completes on the surviving pow2
  bucket with every block BIT-IDENTICAL to the no-fault run (stock and
  pallas exchange impls, array and memmap receive modes),
* the no-hang guarantee: factor=0 / elastic-off / double failure all raise
  typed, addressed errors instead of stalling,
* regrow: a rejoined executor restores the full mesh for the next shuffle,
* membership gossip over the peer wire (MEMBER_SUSPECT / MEMBER_REJOIN),
* the SPMD executor's fail-fast guard (degraded view -> typed error before
  the lockstep collective).
"""

import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import (
    BlockNotFoundError,
    ExecutorLostError,
)
from sparkucx_tpu.parallel.membership import ClusterMembership
from sparkucx_tpu.shuffle.resolver import degraded_plan
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.tpu import TpuShuffleCluster


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def tracer():
    """The process-wide tracer, enabled and cleared; back to what it was afterwards."""
    from sparkucx_tpu.utils.trace import TRACER

    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.enable()
    TRACER.clear()
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


# ---------------------------------------------------------------------------
# membership units
# ---------------------------------------------------------------------------


class TestClusterMembership:
    def test_initial_state(self):
        m = ClusterMembership(range(4))
        assert m.epoch == 0 and not m.degraded
        assert m.alive() == [0, 1, 2, 3] and m.dead() == {}

    def test_mark_dead_bumps_epoch_once(self):
        m = ClusterMembership(range(4))
        assert m.mark_dead(2, "chaos")
        assert m.epoch == 1 and m.degraded
        assert not m.mark_dead(2, "again")  # idempotent: no re-bump
        assert m.epoch == 1
        assert m.dead() == {2: "chaos"}
        assert m.alive() == [0, 1, 3]

    def test_unknown_ids_absorbed(self):
        m = ClusterMembership(range(2))
        assert not m.mark_dead(9, "who?")
        assert not m.mark_alive(9)
        assert not m.suspect(9, "noise")
        assert m.epoch == 0

    def test_rejoin_bumps_epoch(self):
        m = ClusterMembership(range(3))
        m.mark_dead(1, "down")
        assert m.mark_alive(1)
        assert m.epoch == 2 and not m.degraded
        assert not m.mark_alive(1)  # already alive
        assert m.epoch == 2

    def test_suspect_without_debounce_kills_first_error(self):
        m = ClusterMembership(range(3), suspect_after_ms=0)
        assert m.suspect(2, "RST")
        assert m.dead() == {2: "RST"}

    def test_suspect_debounce_window(self):
        m = ClusterMembership(range(3), suspect_after_ms=10_000)
        assert not m.suspect(2, "first error")  # inside the window
        assert m.is_alive(2) and m.epoch == 0
        assert not m.suspect(2, "second error, still inside")
        assert m.is_alive(2)

    def test_suspect_debounce_expiry_kills(self):
        m = ClusterMembership(range(3), suspect_after_ms=20)
        assert not m.suspect(2, "first")
        time.sleep(0.05)
        assert m.suspect(2, "persisted")
        assert not m.is_alive(2)

    def test_snapshot_is_consistent(self):
        m = ClusterMembership(range(4))
        m.mark_dead(3, "gone")
        snap = m.snapshot()
        assert snap == {"epoch": 1, "alive": [0, 1, 2], "dead": {3: "gone"}}

    def test_liveness_observation_clears_pending_suspicion(self):
        """A gray peer that recovers inside the debounce window must restart
        suspicion from scratch: mark_alive on an already-alive executor pops
        the pending suspect entry (no epoch bump), so the NEXT error opens a
        fresh window instead of inheriting the stale first-error timestamp."""
        m = ClusterMembership(range(3), suspect_after_ms=30)
        assert not m.suspect(2, "first error")  # window opens
        time.sleep(0.04)  # window would have expired...
        assert not m.mark_alive(2)  # ...but the peer was seen alive
        assert m.epoch == 0
        assert not m.suspect(2, "fresh error")  # fresh window, absorbed again
        assert m.is_alive(2)
        time.sleep(0.04)
        assert m.suspect(2, "persisted past the fresh window")
        assert not m.is_alive(2)

    def test_flapping_storm_bumps_epoch_once_per_real_transition(self):
        """The flapping scenario: a storm of suspicions and liveness flaps
        against one executor.  Debounce absorbs every error inside the
        window; the epoch moves exactly once per REAL transition (one death,
        one rejoin) no matter how many observations piled up, so gossiping
        peers re-applying known facts can never start a re-broadcast storm."""
        m = ClusterMembership(range(4), suspect_after_ms=25)
        for _ in range(20):  # error storm inside one window: all absorbed
            assert not m.suspect(2, "flap")
        assert m.epoch == 0 and m.is_alive(2)
        time.sleep(0.04)
        assert m.suspect(2, "persisted")  # the one real death...
        assert m.epoch == 1
        for _ in range(10):  # ...re-applying it is a no-op (no re-broadcast)
            assert not m.suspect(2, "echo")
            assert not m.mark_dead(2, "echo")
        assert m.epoch == 1
        assert m.mark_alive(2)  # the one real rejoin
        assert m.epoch == 2
        for _ in range(10):
            assert not m.mark_alive(2)
        assert m.epoch == 2 and m.dead() == {}


# ---------------------------------------------------------------------------
# degraded_plan units
# ---------------------------------------------------------------------------


class TestDegradedPlan:
    def test_pow2_shrink(self):
        m, phys, waves = degraded_plan(4, [0, 1, 3])
        assert m == 2 and phys == [0, 1] and waves == 2

    def test_exact_pow2_survivors(self):
        m, phys, waves = degraded_plan(8, [0, 2, 4, 6])
        assert m == 4 and phys == [0, 2, 4, 6] and waves == 2

    def test_single_survivor(self):
        m, phys, waves = degraded_plan(4, [2])
        assert m == 1 and phys == [2] and waves == 4

    def test_wave_count_covers_all_slots(self):
        for n in (2, 4, 8):
            for k in range(1, n + 1):
                m, phys, waves = degraded_plan(n, list(range(k)))
                assert m * waves >= n  # every wave slot is covered
                assert len(phys) == m
                assert m & (m - 1) == 0  # pow2

    def test_no_survivors_raises(self):
        from sparkucx_tpu.core.operation import TransportError

        with pytest.raises(TransportError):
            degraded_plan(4, [])


# ---------------------------------------------------------------------------
# chaos: kill mid-superstep, recover on the shrunk mesh
# ---------------------------------------------------------------------------


def _run_shuffle(cluster, meta, shuffle_id, M, R, seed=7, kill=None, kill_round=1):
    """Stage deterministic blocks, optionally arm a mid-superstep kill, run
    the exchange, and return {(map, reduce): bytes} read from the reducers."""
    rng = np.random.default_rng(seed)
    oracle = {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(R):
            payload = rng.integers(0, 256, size=2000, dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    try:
        if kill is not None:
            kills = kill if isinstance(kill, (list, tuple)) else [kill]

            def die(**ctx):
                for k in kills:
                    faults.kill_executor(cluster.transport(k))

            faults.arm(
                "exchange.submit", die, times=1, match={"round": kill_round}
            )
        cluster.run_exchange(shuffle_id)
    finally:
        faults.reset()
    blocks = {}
    for (m, r) in oracle:
        consumer = meta.owner_of_reduce(r)
        view, length = cluster.locate_received_block(consumer, shuffle_id, m, r)
        blocks[(m, r)] = bytes(view[:length])
    assert blocks == oracle, "received blocks diverge from staged payloads"
    return blocks


def _mk_cluster(n=4, **conf_kw):
    conf_kw.setdefault("staging_capacity_per_executor", n * 4096)
    conf_kw.setdefault("block_alignment", 128)
    conf_kw.setdefault("elastic", True)
    conf_kw.setdefault("replication_factor", 1)
    conf = TpuShuffleConf(num_executors=n, **conf_kw)
    return TpuShuffleCluster(conf, num_executors=n)


class TestElasticRecovery:
    @pytest.mark.parametrize(
        # killing the highest id leaves the contiguous prefix [0, 1] as the
        # shrunk mesh; killing 2 leaves a gap the survivors are picked around
        "impl, kill", [("stock", 2), ("pallas", 2), ("stock", 3)]
    )
    def test_kill_mid_superstep_bit_identical(self, impl, kill):
        """The acceptance scenario: baseline run vs killed-and-recovered run
        must produce byte-identical blocks, for both exchange impls."""
        n, M, R = 4, 12, 8
        base_cluster = _mk_cluster(n, exchange_impl=impl)
        meta = base_cluster.create_shuffle(0, M, R)
        baseline = _run_shuffle(base_cluster, meta, 0, M, R)
        assert base_cluster.elastic_stats["recoveries"] == 0

        cluster = _mk_cluster(n, exchange_impl=impl)
        meta = cluster.create_shuffle(0, M, R)
        recovered = _run_shuffle(cluster, meta, 0, M, R, kill=kill)
        assert recovered == baseline
        stats = cluster.elastic_stats
        assert stats["recoveries"] == 1
        assert stats["last_epoch"] == 1
        m, phys = stats["degraded_mesh"]
        assert m == 2 and kill not in phys
        assert stats["last_recovery_ms"] > 0

    def test_kill_with_memmap_recv_mode(self):
        n, M, R = 4, 12, 8
        base = _run_shuffle(
            (c := _mk_cluster(n, host_recv_mode="memmap")),
            c.create_shuffle(0, M, R), 0, M, R,
        )
        cluster = _mk_cluster(n, host_recv_mode="memmap")
        meta = cluster.create_shuffle(0, M, R)
        assert _run_shuffle(cluster, meta, 0, M, R, kill=3) == base
        assert cluster.elastic_stats["recoveries"] == 1

    def test_factor_zero_raises_typed_no_hang(self):
        cluster = _mk_cluster(4, replication_factor=0)
        meta = cluster.create_shuffle(0, 12, 8)
        with pytest.raises(ExecutorLostError) as ei:
            _run_shuffle(cluster, meta, 0, 12, 8, kill=2)
        assert ei.value.executor_id == 2
        assert "replication.factor=0" in str(ei.value)
        assert "2" in str(ei.value)  # names the lost executor

    def test_elastic_off_raises_typed(self):
        cluster = _mk_cluster(4, elastic=False)
        meta = cluster.create_shuffle(0, 12, 8)
        with pytest.raises(ExecutorLostError) as ei:
            _run_shuffle(cluster, meta, 0, 12, 8, kill=2)
        assert "elastic" in str(ei.value)

    def test_double_failure_primary_and_replica(self):
        """Killing an executor AND its ring successor (the only replica
        holder at factor=1) is unrecoverable: a typed BlockNotFoundError
        names the shuffle and every candidate tried — never a hang."""
        cluster = _mk_cluster(4)
        meta = cluster.create_shuffle(0, 12, 8)
        with pytest.raises(BlockNotFoundError) as ei:
            _run_shuffle(cluster, meta, 0, 12, 8, kill=[1, 2])
        msg = str(ei.value)
        assert "candidates [2]" in msg
        assert "unrecoverable" in msg
        assert ei.value.shuffle_id == 0

    def test_regrow_restores_full_mesh(self):
        """Kill -> shrunk completion -> rejoin -> the NEXT shuffle runs on
        the full mesh again (no recovery, full-epoch exchange)."""
        n, M, R = 4, 12, 8
        cluster = _mk_cluster(n)
        meta = cluster.create_shuffle(0, M, R)
        _run_shuffle(cluster, meta, 0, M, R, kill=2)
        assert cluster.elastic_stats["recoveries"] == 1
        assert cluster.membership.alive() == [0, 1, 3]

        # the executor comes back: fresh store on the same id
        assert cluster.rejoin_executor(2)
        assert cluster.membership.alive() == [0, 1, 2, 3]
        epoch_after_rejoin = cluster.membership.epoch

        meta2 = cluster.create_shuffle(1, M, R)
        blocks = _run_shuffle(cluster, meta2, 1, M, R, seed=11)
        assert len(blocks) == M * R
        # full-mesh run: no new recovery, epoch unchanged
        assert cluster.elastic_stats["recoveries"] == 1
        assert cluster.membership.epoch == epoch_after_rejoin

        # the rejoined executor is a new process: it can be lost AGAIN (a
        # latch of the first kill made the second a silent no-op), the
        # recovery runs again, and finds the shrunk mesh's executable of the
        # first loss although the membership is two epochs on
        executables = len(cluster._exchange_cache)
        meta3 = cluster.create_shuffle(2, M, R)
        again = _run_shuffle(cluster, meta3, 2, M, R, seed=13, kill=2)
        assert len(again) == M * R
        assert cluster.elastic_stats["recoveries"] == 2
        assert cluster.elastic_stats["last_epoch"] == epoch_after_rejoin + 1
        assert cluster.membership.alive() == [0, 1, 3]
        assert len(cluster._exchange_cache) == executables

    def test_rejoin_is_a_restarted_executor(self):
        """What comes back under a lost executor's id is what a restarted
        process has: a store of its own with nothing in it — no shuffle of
        before the loss, no replica it held for its ring predecessor, an empty
        free list, counters from zero — and no block registered.  An alive or
        unknown id is left as it is."""
        cluster = _mk_cluster(4)
        meta = cluster.create_shuffle(0, 12, 8)
        assert not cluster.rejoin_executor(2) and not cluster.rejoin_executor(9)
        before = cluster.transport(2).store
        _run_shuffle(cluster, meta, 0, 12, 8, kill=2)
        assert cluster.transport(3).store.replica_stats()["replica_sources"] == 1
        assert cluster.rejoin_executor(2)
        store = cluster.transport(2).store
        assert store is not before and store.executor_id == 2
        assert store.replica_stats() == {"replica_bytes": 0, "replica_rounds": 0, "replica_sources": 0}
        assert not any(v for k, v in store.write_stats().items() if k != "executor")
        with pytest.raises(Exception, match="unknown shuffle"):
            store.num_rounds(0)
        assert not cluster.rejoin_executor(2)  # alive again: nothing to do
        assert cluster.transport(2).store is store
        # the recovered shuffle is the cluster's and outlives the rejoin
        view, length = cluster.locate_received_block(meta.owner_of_reduce(5), 0, 2, 5)
        assert length == 2000 and len(view) == 2000
        cluster.remove_shuffle(0)
        assert all(t.store.replica_stats()["replica_bytes"] == 0 for t in cluster.transports)

    def test_the_elastic_counters_count_what_a_recovery_did(self):
        """The ``elastic`` family of ``metrics_text()``: one replica of every
        sealed round before the first submit, and a recovery's restaged
        blocks, sub-exchanges and nanoseconds; all zero on a conf with
        replication off, which copies nothing."""
        n, M, R = 4, 12, 8
        cluster = _mk_cluster(n)
        meta = cluster.create_shuffle(0, M, R)
        _run_shuffle(cluster, meta, 0, M, R, kill=2)
        stats = cluster.elastic_stats
        lost_maps = [m for m in range(M) if meta.map_owner[m] == 2]
        assert stats["restaged_blocks"] == len(lost_maps) * R
        assert stats["restaged_bytes"] == len(lost_maps) * R * 2000
        assert stats["replicated_bytes"] == M * R * 2000
        assert stats["replicated_rounds"] >= n  # a round or more an executor, one copy each
        assert 1 <= stats["degraded_subexchanges"] <= 4 * len(meta.recv_sizes)
        assert stats["replicate_ns"] > 0 and stats["recover_ns"] > 0
        text = cluster.metrics_text()
        for name in ("recoveries", "restaged_blocks", "restaged_bytes", "degraded_subexchanges",
                     "replicated_rounds", "replicated_bytes", "replicate_ns", "recover_ns"):
            assert f"sparkucx_tpu_elastic_{name} " in text, name
        assert "sparkucx_tpu_elastic_recoveries 1" in text

        plain = _mk_cluster(n, replication_factor=0, elastic=False)
        _run_shuffle(plain, plain.create_shuffle(0, M, R), 0, M, R)
        assert not any(v for k, v in plain.elastic_stats.items() if k != "degraded_mesh")
        assert all(t.store.replica_stats()["replica_bytes"] == 0 for t in plain.transports)

    def test_quota_engine_fails_fast_on_loss(self):
        """The quota-capped engine has no degraded path: losing an executor
        mid-run must raise the typed error, not hang in a stale plan."""
        cluster = _mk_cluster(4, slot_quota_rows=4)
        meta = cluster.create_shuffle(0, 12, 8)
        with pytest.raises(ExecutorLostError) as ei:
            _run_shuffle(cluster, meta, 0, 12, 8, kill=2)
        assert "quota" in str(ei.value)


# ---------------------------------------------------------------------------
# the re-run through the plan executor: views, the one copy, zero pieces
# ---------------------------------------------------------------------------

#: (executors, the one lost, staging bytes an executor, pipeline depth): a
#: region of 32 rows is the shrunk mesh's slot and every wave is whole — a
#: sender's piece is a view; a region of 24 rows is not the slot (32); three
#: executors on two survivors leave a last wave of one — both take the one copy
GEOMETRIES = {
    "pow2-slot": (4, 2, 4 * 4096, 2),
    "pow2-slot-serial": (4, 2, 4 * 4096, 1),
    "odd-slot": (4, 2, 4 * 3072, 2),
    "short-last-wave": (3, 1, 3 * 4096, 2),
}


def _shards_and_sizes(cluster, shuffle_id=0):
    """Every round's received shards cut to the rows received (the full mesh
    keeps a shard's bucketed prefix, the recovery its exact rows: a reader
    looks no further), and the size matrices."""
    meta = cluster.meta(shuffle_id)
    row = cluster.row_bytes
    shards = [
        [bytes(np.asarray(shard)[: int(sizes[c].sum()) * row]) for c, shard in enumerate(rnd_shards)]
        for rnd_shards, sizes in zip(meta.recv_shards, meta.recv_sizes)
    ]
    return shards, [sizes.tolist() for sizes in meta.recv_sizes]


def _pieces_by_geometry(cluster, shuffle_id=0):
    """(sub-exchanges, pieces that carry a row) of a recovered shuffle, from
    its size matrices and the shrunk mesh alone."""
    n = cluster.num_executors
    m, _phys = cluster.elastic_stats["degraded_mesh"]
    waves = -(-n // m)
    subexchanges = carrying = 0
    for recv_mat in cluster.meta(shuffle_id).recv_sizes:  # [consumer, sender]
        for i in range(waves):
            for j in range(waves):
                rows = recv_mat[j * m : (j + 1) * m, i * m : (i + 1) * m].sum(axis=0)
                subexchanges += bool(rows.sum())
                carrying += int(np.count_nonzero(rows))
    return subexchanges, carrying


class TestTheRerunThroughThePlanExecutor:
    @pytest.mark.parametrize("mode", ["array", "memmap"])
    @pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
    def test_the_recovered_shards_are_the_undisturbed_runs_byte_for_byte(self, geometry, mode):
        n, lost, staging, depth = GEOMETRIES[geometry]
        M, R = 3 * n, 2 * n
        conf = dict(staging_capacity_per_executor=staging, host_recv_mode=mode, pipeline_depth=depth)
        whole = _mk_cluster(n, **conf)
        _run_shuffle(whole, whole.create_shuffle(0, M, R), 0, M, R)
        cluster = _mk_cluster(n, **conf)
        _run_shuffle(cluster, cluster.create_shuffle(0, M, R), 0, M, R, kill=lost)
        assert _shards_and_sizes(cluster) == _shards_and_sizes(whole)
        stats = cluster.elastic_stats
        assert stats["recoveries"] == 1 and stats["degraded_mesh"][0] == 2
        subexchanges, carrying = _pieces_by_geometry(cluster)
        assert stats["degraded_subexchanges"] == subexchanges > len(cluster.meta(0).recv_sizes)
        assert stats["recover_zero_pieces"] == 2 * subexchanges - carrying
        piece_bytes = 2 * 32 * cluster.row_bytes  # two slots of the 32-row bucket
        if geometry.startswith("pow2-slot"):
            assert stats["recover_copied_bytes"] == 0
            assert stats["recover_direct_bytes"] == carrying * piece_bytes
        else:
            assert stats["recover_direct_bytes"] < carrying * piece_bytes
            assert stats["recover_direct_bytes"] + stats["recover_copied_bytes"] == carrying * piece_bytes
            assert stats["recover_copied_bytes"] > 0
        text = cluster.metrics_text()
        for name in ("recover_direct_bytes", "recover_copied_bytes", "recover_zero_pieces"):
            assert f"sparkucx_tpu_elastic_{name} {stats[name]}" in text, name

    def test_the_rerun_is_pipelined_under_its_own_name(self, groupbytest, tracer):
        """Sub-exchange k + 1 is submitted before k has drained; the re-run's
        spans and aggregator are ``exchange.recover.pipeline`` and its
        children, and nothing is recorded under the full mesh's names
        (``exchange.pipeline.*``, ``exchange.h2d``, ``exchange.d2h``) once the
        exchange has been aborted."""
        records = groupbytest.records(MAPPERS, seed=47)
        with _manager() as mgr:
            _run_job(mgr, groupbytest, records, 0)
            events = [ev for ev in tracer.events if ev.get("ph") == "X"]
            stats = dict(mgr.cluster.elastic_stats)
            ops = mgr.cluster.stats
            full = ops.summary("exchange.pipeline.submit"), ops.summary("exchange.pipeline.drain")
            rerun = ops.summary("exchange.recover.pipeline.submit"), ops.summary("exchange.recover.pipeline.drain")
        named = lambda name: sorted((ev for ev in events if ev["name"] == name), key=lambda ev: ev["ts"])
        end = lambda ev: ev["ts"] + ev["dur"]
        inside = lambda ev, outer: outer["ts"] <= ev["ts"] and end(ev) <= end(outer) + 1
        [recover] = named("exchange.recover")
        submits, drains = named("exchange.recover.pipeline.submit"), named("exchange.recover.pipeline.drain")
        k = stats["degraded_subexchanges"]
        assert len(submits) == len(drains) == k == rerun[0].ops == rerun[1].ops > 2
        assert all(submits[i + 1]["ts"] < end(drains[i]) for i in range(k - 1))
        assert all(inside(ev, recover) for ev in submits)
        for child in ("exchange.recover.h2d", "exchange.collective.degraded"):
            assert len(named(child)) == k
            assert all(any(inside(ev, submit) for submit in submits) for ev in named(child)), child
        assert len(named("exchange.recover.d2h")) == k
        assert all(any(inside(ev, drain) for drain in drains) for ev in named("exchange.recover.d2h"))
        # the full mesh's names saw the rounds submitted before the loss, and nothing since
        assert full[0].ops == LOST_AT_ROUND and full[1].ops <= LOST_AT_ROUND
        for name in ("exchange.pipeline.submit", "exchange.pipeline.drain", "exchange.h2d",
                     "exchange.d2h", "exchange.assemble", "exchange.collective"):
            assert all(end(ev) <= recover["ts"] + 1 for ev in named(name)), name
        # the rounds' spans carry what their submits handed the devices
        reruns = named("exchange.recover.round")
        for key in ("direct_bytes", "copied_bytes", "zero_pieces"):
            assert sum(ev["args"][key] for ev in reruns) == stats[f"recover_{key}"], key
        assert stats["recover_copied_bytes"] == 0 < stats["recover_direct_bytes"]

    @pytest.mark.parametrize("at", [{"round": 0, "chunk": 0}, {"round": 2, "chunk": 1}])
    def test_a_second_loss_inside_the_rerun_is_refused_typed_and_leaves_no_thread(self, at):
        import threading

        n, M, R = 4, 12, 8
        cluster = _mk_cluster(n)
        meta = cluster.create_shuffle(0, M, R)
        fired = []

        def second(**ctx):
            fired.append(ctx)
            faults.kill_executor(cluster.transport(0))

        def first(**ctx):
            faults.kill_executor(cluster.transport(2))
            faults.arm("exchange.recover.submit", second, times=1, match=at)

        faults.arm("exchange.submit", first, times=1, match={"round": 1})
        rng = np.random.default_rng(3)
        for m in range(M):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(0, m)
            for r in range(R):
                w.write_partition(r, rng.integers(0, 256, size=2000, dtype=np.uint8).tobytes())
            t.commit_block(w.commit().pack())
        with pytest.raises(ExecutorLostError, match="under the degraded re-run") as ei:
            cluster.run_exchange(0)
        assert len(fired) == 1 and ei.value.executor_id == 0
        assert not cluster.meta(0).exchanged and cluster.elastic_stats["recoveries"] == 0
        assert not [t.name for t in threading.enumerate() if "pipeline" in t.name]

    def test_the_drain_workers_arrays_come_from_the_landing_pool(self, monkeypatch):
        """With a runtime whose D2H copies (a chip's, patched in as
        tests/test_recv_landing.py does) the recovered shards — concatenated
        on the pipeline's drain worker — are the landing pool's, and a second
        job of the same sizes finds every large array of its recovery among
        the blocks the first gave back."""
        import gc

        import sparkucx_tpu.transport.tpu as tpu_mod
        from sparkucx_tpu.native import LandingPool
        from test_recv_landing import copying_runtime, get_handler_name  # a chip's copy_to_host_async

        if LandingPool.create(1 << 20) is None:
            pytest.skip("no landing pool here (no compiler, or no NumPy allocator hook)")
        monkeypatch.setattr(tpu_mod, "_d2h_copies", lambda device: True)
        monkeypatch.setattr(tpu_mod, "_start_landing", copying_runtime)
        monkeypatch.setattr(tpu_mod, "LANDING_MIN_BYTES", 1024)
        n, M, R = 4, 12, 8
        cluster = _mk_cluster(n, staging_capacity_per_executor=4 * 8192)
        pool = cluster._landing()
        assert pool is not None
        for sid in range(2):
            before = pool.stats()
            meta = cluster.create_shuffle(sid, M, R)
            _run_shuffle(cluster, meta, sid, M, R, kill=2)
            assert cluster.elastic_stats["recoveries"] == sid + 1
            shards = [s for rnd in meta.recv_shards for s in rnd if s.nbytes]
            assert shards and all(get_handler_name(s) == LandingPool.NAME for s in shards)
            after = pool.stats()
            del shards, meta
            cluster.remove_shuffle(sid)
            gc.collect()
            assert cluster.rejoin_executor(2)
        assert after["hits"] - before["hits"] > 0 and after["misses"] == before["misses"]


# ---------------------------------------------------------------------------
# membership gossip over the peer wire
# ---------------------------------------------------------------------------


class TestMembershipGossip:
    def _wire_cluster(self, n=3, **conf_kw):
        from sparkucx_tpu.transport.peer import PeerTransport

        conf_kw.setdefault("staging_capacity_per_executor", 1 << 20)
        conf = TpuShuffleConf(**conf_kw)
        ts = [PeerTransport(conf, executor_id=i) for i in range(n)]
        addrs = [t.init() for t in ts]
        for t in ts:
            t.membership = ClusterMembership(range(n))
            for j, a in enumerate(addrs):
                if j != t.executor_id:
                    t.add_executor(j, a)
        return ts, addrs

    def test_wire_failure_gossips_suspicion(self):
        from sparkucx_tpu.core.block import MemoryBlock

        ts, _ = self._wire_cluster(3)
        try:
            faults.kill_executor(ts[2])
            buf = MemoryBlock(np.zeros(64, dtype=np.uint8), size=64)
            req = ts[0].fetch_block(2, 1, 0, 0, buf)
            deadline = time.monotonic() + 5
            while not req.completed() and time.monotonic() < deadline:
                ts[0].progress()
                time.sleep(0.002)
            assert req.completed()
            # the observer marked it dead...
            assert not ts[0].membership.is_alive(2)
            # ...and gossiped MEMBER_SUSPECT to the third executor
            deadline = time.monotonic() + 3
            while ts[1].membership.is_alive(2) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not ts[1].membership.is_alive(2)
            assert "wire failure" in ts[1].membership.dead()[2]
        finally:
            for t in ts:
                t.close()

    def test_rejoin_announcement_restores(self):
        ts, _ = self._wire_cluster(3)
        try:
            for t in ts:
                t.membership.mark_dead(2, "was down")
            ts[2].announce_rejoin()
            assert ts[2].membership.is_alive(2)
            deadline = time.monotonic() + 3
            while (
                not (ts[0].membership.is_alive(2) and ts[1].membership.is_alive(2))
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert ts[0].membership.is_alive(2)
            assert ts[1].membership.is_alive(2)
        finally:
            for t in ts:
                t.close()

    def test_rumors_about_self_ignored(self):
        """A live executor is the authority on its own liveness: a gossiped
        suspicion naming the receiver must not kill it locally."""
        from sparkucx_tpu.core.definitions import AmId

        ts, _ = self._wire_cluster(2)
        try:
            ts[1]._on_member_event(int(AmId.MEMBER_SUSPECT), 1, 1, 0)
            assert ts[1].membership.is_alive(1)
        finally:
            for t in ts:
                t.close()


# ---------------------------------------------------------------------------
# SPMD fail-fast guard
# ---------------------------------------------------------------------------


class TestSpmdDegradedGuard:
    def test_degraded_view_fails_before_collective(self):
        from sparkucx_tpu.transport.spmd import SpmdShuffleExecutor

        ex = SpmdShuffleExecutor(TpuShuffleConf())
        try:
            ex.create_shuffle(0, 1, 1)
            ex.membership.mark_dead(0, "chaos")
            with pytest.raises(ExecutorLostError) as ei:
                ex.run_exchange(0)
            assert "SPMD" in str(ei.value)
            assert ei.value.executor_id == 0
        finally:
            ex.close()


# ---------------------------------------------------------------------------
# the loss on the served path: TpuShuffleManager -> writer -> store ->
# run_exchange -> reader
# ---------------------------------------------------------------------------


#: the upstream gate job at test size on four executors: 8 map tasks of 60
#: records of 25,000 bytes over 200 reducers, 12 MB a job, through 1 MiB of
#: staging an executor — a dozen staging rounds, so that an executor can be
#: lost after some of them have gone through
LOST, LOST_AT_ROUND, MAPPERS = 2, 4, 8


def _manager(**conf_kw):
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    conf_kw.setdefault("staging_capacity_per_executor", 1 << 20)
    conf_kw.setdefault("elastic", True)
    conf_kw.setdefault("replication_factor", 1)
    return TpuShuffleManager(TpuShuffleConf(**conf_kw), num_executors=4)


def _run_job(mgr, groupbytest, records, shuffle_id, lose=(LOST,)):
    """One whole job through the manager: every map task written and
    committed, the exchange (during which ``lose`` die at the submit of
    staging round ``LOST_AT_ROUND``), every reduce task read in full.
    Returns ({reducer: [(key, value)]}, the readers' metrics)."""

    def die(**_ctx):
        for executor in lose:
            faults.kill_executor(mgr.cluster.transport(executor))

    if lose:
        faults.arm("exchange.submit", die, times=1,
                   match={"shuffle_id": shuffle_id, "round": LOST_AT_ROUND})
    try:
        groupbytest.write_and_exchange(mgr, shuffle_id, records)
    finally:
        faults.reset()
    read, metrics = {}, []
    for r in range(records.reducers):
        reader = mgr.get_reader(shuffle_id, r, r + 1)
        read[r] = [(key, bytes(value)) for key, value in reader.read()]
        metrics.append(reader.metrics)
    return read, metrics


def _equals_the_plain_groupby(records, read) -> bool:
    """Group count, crc32 of every value under its key, key in its partition."""
    checks = []
    for r, pairs in read.items():
        check = records.check(r, full=True)
        for key, value in pairs:
            check.add(key, value)
        checks.append(check)
    return all(c.ok() for c in checks) and records.complete(checks)


class TestLossThroughTheManager:
    def test_a_job_that_loses_an_executor_equals_the_plain_groupby_and_the_undisturbed_job(self, groupbytest):
        records = groupbytest.records(MAPPERS, seed=41)
        with _manager() as undisturbed:
            whole, _ = _run_job(undisturbed, groupbytest, records, 0, lose=())
            assert undisturbed.cluster.elastic_stats["recoveries"] == 0
        with _manager() as mgr:
            read, metrics = _run_job(mgr, groupbytest, records, 0)
            cluster = mgr.cluster
            assert cluster.elastic_stats["recoveries"] == 1
            assert cluster.membership.alive() == [0, 1, 3]
            assert len(cluster.meta(0).recv_sizes) > LOST_AT_ROUND  # rounds had gone through
        assert _equals_the_plain_groupby(records, read)
        assert read == whole  # byte for byte, record order too
        assert sum(len(pairs) for pairs in read.values()) == MAPPERS * 60
        assert not any(m.blocks_retried or m.failovers or m.fetch_timeouts for m in metrics)

    def test_the_dead_executors_partitions_are_read_through_the_managers_reader(self, groupbytest):
        """A quarter of the reduce partitions are the dead executor's; their
        reader resolves on its transport, borrows every block where the
        recovery left it and never sees the loss."""
        records = groupbytest.records(MAPPERS, seed=42)
        with _manager() as mgr:
            _run_job(mgr, groupbytest, records, 0)
            meta = mgr.cluster.meta(0)
            mine = [r for r in range(records.reducers) if meta.owner_of_reduce(r) == LOST]
            assert len(mine) == records.reducers // 4
            for r in mine:
                reader = mgr.get_reader(0, r, r + 1)
                assert reader.executor_id == LOST and reader.replica_of is not None
                check = records.check(r, full=True)
                for key, value in reader.read():
                    check.add(key, value)
                assert check.ok(), r
                m = reader.metrics
                assert m.resident_blocks == len(records.mappers_of(r)) and m.copied_blocks == 0
                assert (m.blocks_retried, m.failovers, m.fetch_timeouts) == (0, 0, 0)

    def test_three_jobs_in_a_row_each_lose_and_regain_executor_2(self, groupbytest):
        """One manager, the benchmark's loop: lose executor 2 mid-exchange,
        read, unregister, rejoin.  The third job compiles nothing (the shrunk
        mesh's executable is found again whatever the epoch, a rejoin
        recompiles nothing on the full mesh), and nothing of a job is left
        after its removal: no replica body, no received shard, and the
        survivors' round buffers back on their free lists, level from job to
        job."""
        from benchmark.counters import CompileCounter

        records = groupbytest.records(MAPPERS, seed=43)
        compiles = CompileCounter()
        held = []
        with _manager() as mgr:
            cluster = mgr.cluster
            for sid in range(3):
                mark = compiles.snapshot()
                read, metrics = _run_job(mgr, groupbytest, records, sid)
                assert _equals_the_plain_groupby(records, read), sid
                assert not any(m.blocks_retried or m.failovers or m.fetch_timeouts for m in metrics)
                assert cluster.elastic_stats["recoveries"] == sid + 1
                assert cluster.elastic_stats["last_epoch"] == 2 * sid + 1
                assert sum(t.store.replica_stats()["replica_bytes"] for t in cluster.transports) > 0
                mgr.unregister_shuffle(sid)
                stores = [t.store for t in cluster.transports]
                assert all(s.replica_stats()["replica_bytes"] == 0 for s in stores)
                with pytest.raises(Exception, match="unknown shuffle"):
                    cluster.meta(sid)
                assert cluster.rejoin_executor(LOST)
                assert cluster.membership.alive() == [0, 1, 2, 3]
                survivors = [t.store.write_stats() for t in cluster.transports if t.executor_id != LOST]
                assert all(s["pool_dropped_busy"] == 0 for s in survivors), survivors
                held.append([s["pool_held_bytes"] for s in survivors])
                assert cluster.transport(LOST).store.write_stats()["pool_held_bytes"] == 0
                if sid == 2:
                    assert compiles.since(mark)["compiles"] == 0
            assert held[0] == held[1] == held[2] and all(h > 0 for h in held[0])
            assert set(cluster.executed_lowerings()["exchange"]) == {"dense"}
            assert cluster.elastic_stats["restaged_blocks"] == 3 * sum(
                len(records.blocks[m]) for m in range(MAPPERS) if m % 4 == LOST)

    def test_the_loss_of_an_executor_and_its_successor_is_refused_typed(self, groupbytest):
        records = groupbytest.records(MAPPERS, seed=44)
        with _manager() as mgr:
            with pytest.raises(BlockNotFoundError, match="unrecoverable"):
                _run_job(mgr, groupbytest, records, 0, lose=(2, 3))
            mgr.unregister_shuffle(0)
            # both come back, and the next job runs whole
            assert mgr.cluster.rejoin_executor(2) and mgr.cluster.rejoin_executor(3)
            read, _ = _run_job(mgr, groupbytest, records, 1, lose=())
            assert _equals_the_plain_groupby(records, read)

    def test_with_replication_off_nothing_of_it_runs(self, groupbytest, tracer):
        """The default conf: no copy, no replica, no span of the elastic
        path, the counters at zero — and a loss is the typed error."""
        records = groupbytest.records(MAPPERS, seed=45)
        with _manager(replication_factor=0, elastic=False) as mgr:
            read, _ = _run_job(mgr, groupbytest, records, 0, lose=())
            names = {ev["name"] for ev in tracer.events}
            stats = dict(mgr.cluster.elastic_stats)
            replica_bytes = [t.store.replica_stats()["replica_bytes"] for t in mgr.cluster.transports]
            mgr.unregister_shuffle(0)
            with pytest.raises(ExecutorLostError):
                _run_job(mgr, groupbytest, records, 1)
        assert _equals_the_plain_groupby(records, read)
        assert "exchange.collective" in names
        assert not {n for n in names if n.startswith(("exchange.replicate", "exchange.recover"))}
        assert replica_bytes == [0, 0, 0, 0]
        assert not any(v for k, v in stats.items() if k != "degraded_mesh")

    def test_a_traced_recovery_records_its_phases(self, groupbytest, tracer):
        """``exchange.recover`` and its children: one ``restage`` with the
        blocks and bytes that came back from replicas, one ``round`` a re-run
        staging round with the sub-exchanges it dispatched, which are the
        ``exchange.collective.degraded`` spans inside it."""
        records = groupbytest.records(MAPPERS, seed=46)
        with _manager() as mgr:
            _run_job(mgr, groupbytest, records, 0)
            events = [ev for ev in tracer.events if ev.get("ph") == "X"]
            stats = dict(mgr.cluster.elastic_stats)
            rounds = len(mgr.cluster.meta(0).recv_sizes)
        named = lambda name: [ev for ev in events if ev["name"] == name]
        [replicate], [recover], [restage] = (
            named("exchange.replicate"), named("exchange.recover"), named("exchange.recover.restage"))
        assert restage["args"]["blocks"] == stats["restaged_blocks"] > 0
        assert restage["args"]["bytes"] == stats["restaged_bytes"] == sum(
            len(p) for m in range(MAPPERS) if m % 4 == LOST for _, p in records.blocks[m])
        reruns = named("exchange.recover.round")
        assert [ev["args"]["round"] for ev in reruns] == list(range(rounds))
        degraded = named("exchange.collective.degraded")
        assert sum(ev["args"]["subexchanges"] for ev in reruns) == len(degraded) == stats["degraded_subexchanges"]
        inside = lambda ev, outer: outer["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + 1
        assert inside(restage, recover) and all(inside(ev, recover) for ev in reruns)
        assert all(any(inside(ev, rerun) for rerun in reruns) for ev in degraded)
        assert replicate["ts"] + replicate["dur"] <= recover["ts"] + 1
        # the replicas are whole before the first round is submitted
        first_submit = min(ev["ts"] for ev in named("exchange.pipeline.submit"))
        assert replicate["ts"] + replicate["dur"] <= first_submit + 1

"""Chaos tests: fault-injection harness, neighbor replication, reducer failover.

Pins the PR's robustness contracts:

* the harness itself (arm/match/times/reset, factories, telemetry),
* seal -> background REPLICA_PUT push to ring neighbors -> replica tier
  accounting on both ends (``replication.factor``; factor=0 pushes nothing),
* replica serving: ``read_block`` and the peer wire serve a replicated block
  when the primary copy is gone,
* the headline chaos scenario: kill one loopback executor mid-superstep and
  the reducer's output is BIT-IDENTICAL to the no-fault run, with bounded
  stall telemetry and failovers accounted.
"""

import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import (
    BlockNotFoundError,
    OperationStatus,
    TransportError,
)
from sparkucx_tpu.shuffle.reader import TpuShuffleReader
from sparkucx_tpu.shuffle.resolver import ring_neighbors
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.peer import PeerTransport


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


def _cluster(n, **conf_kw):
    conf_kw.setdefault("staging_capacity_per_executor", 1 << 20)
    conf = TpuShuffleConf(**conf_kw)
    ts = [PeerTransport(conf, executor_id=i) for i in range(n)]
    addrs = [t.init() for t in ts]
    for t in ts:
        for j, a in enumerate(addrs):
            if j != t.executor_id:
                t.add_executor(j, a)
    return ts


def _close_all(ts):
    for t in ts:
        t.close()


# ---------------------------------------------------------------------------
# the harness itself
# ---------------------------------------------------------------------------


class TestHarness:
    def test_disarmed_is_noop(self):
        faults.check("nowhere", peer="x")
        assert faults.transform("nowhere", b"abc") == b"abc"
        assert not faults.active

    def test_times_and_match(self):
        hits = []
        faults.arm("p", lambda **ctx: hits.append(ctx), times=2, match={"lane": 1})
        faults.check("p", lane=0)  # match miss
        faults.check("q", lane=1)  # point miss
        for _ in range(5):
            faults.check("p", lane=1)
        assert len(hits) == 2  # times bound respected
        assert faults.fired["p"] == 2

    def test_sever_and_fail_raise(self):
        faults.arm("p", faults.sever("boom"))
        with pytest.raises(ConnectionResetError, match="boom"):
            faults.check("p")
        faults.reset()
        faults.arm("p", faults.fail(ValueError("typed")))
        with pytest.raises(ValueError, match="typed"):
            faults.check("p")

    def test_stall_sleeps(self):
        faults.arm("p", faults.stall(0.05))
        t0 = time.monotonic()
        faults.check("p")
        assert time.monotonic() - t0 >= 0.04

    def test_garble_transform_roundtrip(self):
        faults.arm("p", faults.garble(0xFF))
        out = faults.transform("p", b"\x00\x0f\xf0")
        assert bytes(out) == b"\xff\xf0\x0f"

    def test_context_manager_resets_on_error(self):
        with pytest.raises(RuntimeError):
            with faults.injected_faults(("p", faults.sever())):
                assert faults.active
                raise RuntimeError("test body explodes")
        assert not faults.active and not faults.fired

    def test_disarm_single_entry(self):
        e1 = faults.arm("p", faults.stall(0))
        faults.arm("q", faults.stall(0))
        faults.disarm(e1)
        assert faults.active  # q still armed
        faults.check("p")
        assert "p" not in faults.fired


# ---------------------------------------------------------------------------
# neighbor replication (seal -> REPLICA_PUT -> replica tier)
# ---------------------------------------------------------------------------


def _stage(t, shuffle_id, num_mappers, num_reducers, seed=0, block_bytes=200):
    """Stage deterministic random blocks on executor ``t``; returns
    {(map, reduce): payload}."""
    rng = np.random.default_rng(seed)
    t.store.create_shuffle(shuffle_id, num_mappers, num_reducers)
    payloads = {}
    for m in range(num_mappers):
        w = t.store.map_writer(shuffle_id, m)
        for r in range(num_reducers):
            data = rng.integers(0, 256, size=block_bytes + 37 * (m + r), dtype=np.uint8).tobytes()
            payloads[(m, r)] = data
            w.write_partition(r, data)
        w.commit()
    return payloads


class TestReplication:
    def test_seal_replicates_to_ring_neighbor(self):
        ts = _cluster(2, replication_factor=1)
        try:
            payloads = _stage(ts[0], 7, 2, 3)
            ts[0].store.seal(7)
            assert ts[0].replication_wait(7, timeout=10.0)
            stats = ts[1].store.replica_stats()
            assert stats["replica_sources"] == 1
            assert stats["replica_bytes"] == sum(len(p) for p in payloads.values())
            for (m, r), data in payloads.items():
                view = ts[1].store.replica_view(7, m, r)
                assert view is not None
                arr, off, ln = view
                assert arr[off : off + ln].tobytes() == data
            assert ts[0].replica_stats["acks"] == ts[0].replica_stats["pushed_rounds"] > 0
        finally:
            _close_all(ts)

    def test_factor_zero_pushes_nothing(self):
        ts = _cluster(2, replication_factor=0)
        try:
            _stage(ts[0], 7, 1, 2)
            ts[0].store.seal(7)
            assert ts[0].replication_wait(7, timeout=0.5)  # nothing pending
            assert ts[0].replica_stats["pushed_rounds"] == 0
            assert ts[1].store.replica_stats()["replica_sources"] == 0
        finally:
            _close_all(ts)

    def test_replica_serves_read_block_and_wire(self):
        """A block the holder never staged is served from its replica tier —
        both through read_block (BlockNotFoundError otherwise) and over the
        peer wire (_resolve_one's replica arm)."""
        ts = _cluster(2, replication_factor=1)
        try:
            payloads = _stage(ts[0], 3, 1, 2)
            ts[0].store.seal(3)
            assert ts[0].replication_wait(3, timeout=10.0)
            # executor 1 never created shuffle 3 locally; replica serves anyway
            got = ts[1].store.read_block(3, 0, 1)
            assert got == payloads[(0, 1)]
            # and over the wire: executor 0 fetches its own block BACK from 1
            buf = _buf(len(payloads[(0, 0)]))
            req = ts[0].fetch_block(1, 3, 0, 0, buf)
            deadline = time.monotonic() + 5
            while not req.completed() and time.monotonic() < deadline:
                ts[0].progress()
            res = req.wait(1)
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert buf.host_view()[: buf.size].tobytes() == payloads[(0, 0)]
        finally:
            _close_all(ts)

    def test_delayed_replication_wait_blocks_until_settled(self):
        ts = _cluster(2, replication_factor=1)
        try:
            faults.arm("replica.push", faults.delay(0.3), times=1)
            _stage(ts[0], 4, 1, 1)
            ts[0].store.seal(4)
            assert not ts[0].replication_wait(4, timeout=0.05)  # still delayed
            assert ts[0].replication_wait(4, timeout=10.0)
            assert ts[1].store.replica_view(4, 0, 0) is not None
        finally:
            _close_all(ts)

    def test_apply_sever_counts_as_unsettled(self):
        """Severing the receiving server mid-apply loses the ack; the pusher's
        replication_wait reports unsettled instead of hanging forever."""
        ts = _cluster(2, replication_factor=1)
        try:
            faults.arm("replica.apply", faults.sever(), times=1)
            _stage(ts[0], 5, 1, 1)
            ts[0].store.seal(5)
            assert not ts[0].replication_wait(5, timeout=0.7)
            assert ts[1].store.replica_view(5, 0, 0) is None
        finally:
            _close_all(ts)

    def test_ring_neighbors_placement(self):
        assert ring_neighbors(1, [0, 1, 2], 1) == [2]
        assert ring_neighbors(2, [0, 1, 2], 1) == [0]
        assert ring_neighbors(1, [0, 1, 2], 2) == [2, 0]
        assert ring_neighbors(1, [0, 1, 2], 99) == [2, 0]  # capped at ring-1
        assert ring_neighbors(5, [0, 1, 2], 1) == []  # not a member
        assert ring_neighbors(0, [0], 1) == []  # alone
        assert ring_neighbors(0, [0, 1], 0) == []  # disabled

    def test_block_not_found_is_typed_and_addressed(self):
        ts = _cluster(1, replication_factor=0)
        try:
            ts[0].store.create_shuffle(9, 1, 1)
            with pytest.raises(BlockNotFoundError) as ei:
                ts[0].store.read_block(9, 0, 0)
            assert (ei.value.shuffle_id, ei.value.map_id, ei.value.reduce_id) == (9, 0, 0)
            assert isinstance(ei.value, TransportError)  # old catch-sites work
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# the headline chaos scenario: executor killed mid-superstep
# ---------------------------------------------------------------------------


def _reader(transport, payloads, num_mappers, num_reducers, executors, **kw):
    kw.setdefault("fetch_retries", 2)
    kw.setdefault("fetch_deadline_ms", 2000)
    kw.setdefault("fetch_backoff_ms", 10)
    return TpuShuffleReader(
        transport,
        executor_id=transport.executor_id,
        shuffle_id=0,
        start_partition=0,
        end_partition=num_reducers,
        num_mappers=num_mappers,
        block_sizes=lambda m, r: len(payloads[(m, r)]),
        max_blocks_per_request=1,  # one window per block: kill lands mid-stream
        sender_of=lambda m: 1,
        replica_of=lambda primary: ring_neighbors(primary, executors, 1),
        **kw,
    )


class TestExecutorLossChaos:
    def _run(self, mappers, reducers, block_bytes, kill_after):
        """Stage on executor 1 (replica -> executor 2), read from executor 0;
        executor 1 dies once ``kill_after`` blocks are consumed (None: never)."""
        ts = _cluster(3, replication_factor=1, wire_timeout_ms=5000)
        try:
            payloads = _stage(ts[1], 0, mappers, reducers, seed=42, block_bytes=block_bytes)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            reader = _reader(ts[0], payloads, mappers, reducers, executors=[0, 1, 2])
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
                if len(got) == kill_after:
                    faults.kill_executor(ts[1])  # SIGKILL stand-in, mid-traffic
            assert kill_after is not None or got == payloads
            return got, reader.metrics
        finally:
            _close_all(ts)

    @pytest.mark.parametrize(
        "mappers, reducers, block_bytes, kill_after",
        [(2, 3, 200, 1), (1, 4, 128 << 10, 2)],
        ids=["six_small_blocks_killed_after_the_first", "four_128k_blocks_killed_at_half"],
    )
    def test_kill_mid_superstep_bit_identical(self, mappers, reducers, block_bytes, kill_after):
        baseline, base_metrics = self._run(mappers, reducers, block_bytes, None)
        chaotic, metrics = self._run(mappers, reducers, block_bytes, kill_after)
        assert chaotic == baseline  # bit-identical output despite the kill
        assert base_metrics.failovers == 0
        assert metrics.failovers >= 1  # replicas actually served
        assert metrics.blocks_retried >= 1
        # bounded stall: the dead peer fails fast (reset) or at the deadline,
        # never an unbounded spin — generous CI bound, far below hang territory
        assert metrics.fetch_wait_ns < 30 * 10**9

    def test_all_executors_dead_raises_typed(self):
        """When primary AND replica are gone the reader raises a TransportError
        naming every candidate — no silent truncation of the stream."""
        ts = _cluster(3, replication_factor=1, wire_timeout_ms=2000)
        try:
            payloads = _stage(ts[1], 0, 1, 1, seed=7)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            faults.kill_executor(ts[1])
            faults.kill_executor(ts[2])
            reader = _reader(
                ts[0], payloads, 1, 1, executors=[0, 1, 2],
                fetch_retries=1, fetch_deadline_ms=500,
            )
            with pytest.raises(TransportError, match=r"across executors \[1, 2\]"):
                list(reader.fetch_blocks())
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# tiered eviction x replication: demoted rounds through the chaos path
# ---------------------------------------------------------------------------


class TestDemotedRoundReplication:
    def test_demoted_round_bit_identical_through_kill(self):
        """Eviction composed with the existing resilience features: the
        primary's sealed round is demoted to disk (checksummed + compressed
        striped wire), the first fetch restages it transparently, the primary
        is then killed mid-stream and the ring replica — never demoted —
        serves the remainder.  Output must be bit-identical throughout."""
        from sparkucx_tpu.service.eviction import EvictionManager

        ts = _cluster(
            3,
            replication_factor=1,
            wire_timeout_ms=5000,
            wire_streams=2,
            wire_checksum=True,
            wire_compress_codec="dict",
        )
        try:
            payloads = _stage(ts[1], 0, 2, 3, seed=9)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            ev = EvictionManager(ts[1].store)
            ts[1].store.eviction = ev
            while ts[1].store.round_tier(0, 0) != "disk":
                assert ts[1].store.demote_round(0, 0) is not None
            reader = _reader(ts[0], payloads, 2, 3, executors=[0, 1, 2])
            got = {}
            it = reader.fetch_blocks()
            first = next(it)  # cold fetch: restages the demoted round
            got[(first.block_id.map_id, first.block_id.reduce_id)] = bytes(first.data)
            first.release()
            assert ts[1].store.round_tier(0, 0) == "host"
            assert ev.eviction_stats()["restages"] >= 1
            faults.kill_executor(ts[1])  # replica takes over mid-stream
            for blk in it:
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads  # bit-identical across tier + holder moves
            assert reader.metrics.failovers >= 1
        finally:
            _close_all(ts)

    def test_demotion_never_touches_replica_tier(self):
        """Demoting the primary's round is local: the neighbor's replica
        bytes stay resident and serve reads unchanged."""
        from sparkucx_tpu.service.eviction import EvictionManager

        ts = _cluster(2, replication_factor=1)
        try:
            payloads = _stage(ts[0], 6, 1, 2, seed=5)
            ts[0].store.seal(6)
            assert ts[0].replication_wait(6, timeout=10.0)
            ts[0].store.eviction = EvictionManager(ts[0].store)
            while ts[0].store.round_tier(6, 0) != "disk":
                assert ts[0].store.demote_round(6, 0) is not None
            for (m, r), data in payloads.items():
                assert ts[1].store.read_block(6, m, r) == data
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# gray-failure fault factories + chaos-kill postmortems
# ---------------------------------------------------------------------------


class TestGrayFactories:
    def test_garble_matches_per_byte_xor(self):
        """The vectorized garble must corrupt EXACTLY like the per-byte XOR it
        replaced — chaos tests pin corrupted-frame bytes, so the fast path
        cannot drift from the reference semantics."""
        rng = np.random.default_rng(123)
        data = rng.integers(0, 256, size=1 << 16, dtype=np.uint8).tobytes()
        faults.arm("p", faults.garble(0x5A))
        out = bytes(faults.transform("p", data))
        assert out == bytes(b ^ 0x5A for b in data)

    def test_throttle_paces_and_preserves_bytes(self):
        faults.arm("p", faults.throttle(10_000))  # 10 kB/s
        data = b"z" * 1000  # ~0.1 s at the armed rate
        t0 = time.monotonic()
        out = faults.transform("p", data)
        assert time.monotonic() - t0 >= 0.08  # paced...
        assert bytes(out) == data  # ...but every byte still bit-identical

    def test_flaky_is_seed_deterministic(self):
        def pattern(seed):
            act = faults.flaky(0.5, seed=seed)
            hits = []
            for _ in range(64):
                try:
                    act()
                    hits.append(False)
                except ConnectionResetError:
                    hits.append(True)
            return hits

        assert pattern(7) == pattern(7)  # same seed replays the same failures
        assert any(pattern(7)) and not all(pattern(7))
        assert pattern(7) != pattern(8)

    def test_kill_executor_idempotent_with_health_postmortem(self):
        """kill_executor captures the dying executor's peer-health/breaker
        view into its postmortem bundle BEFORE the kill, and a second kill of
        the same transport is a no-op (real processes die once)."""
        ts = _cluster(2)
        try:
            ts[1].record_peer_failure(0, "synthetic pre-kill failure")
            faults.kill_executor(ts[1])
            pm = ts[1].recorder.last_postmortem
            assert pm is not None and pm["reason"] == "chaos_kill"
            assert pm["context"]["executor"] == 1
            assert "failures" in pm["context"]["peer_health"]
            seq = pm["seq"]
            faults.kill_executor(ts[1])  # idempotent: no second bundle
            assert ts[1].recorder.last_postmortem["seq"] == seq
        finally:
            _close_all(ts)

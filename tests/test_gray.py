"""Gray-failure robustness: health scoring, breakers, hedges, load shedding.

Pins the PR's contracts:

* per-peer health scoring (latency/error EWMAs) and the closed -> open ->
  half-open circuit breaker (``breaker.failureThreshold`` /
  ``breaker.cooldownMs``),
* hedged fetches (``fetch.hedgeMs`` / ``fetch.hedgeMaxMs``): a straggling
  block gets a duplicate request to a replica holder, first completion wins
  bit-identically, the loser is quarantined,
* memory-pressure watermarks (``store.softWatermark`` /
  ``store.hardWatermark``): soft kicks an out-of-band eviction sweep, hard
  sheds allocation-bearing writes/serves with the typed RETRYABLE
  ``ResourceExhaustedError`` (size code -4 on the wire),
* reactor load shedding (``server.acceptBacklog``): over-backlog accepts get
  a best-effort ServerBusy frame and a typed client-side error,
* the acceptance chaos scenario: one primary STALLED (not killed) — hedged
  fetches complete bit-identically from replicas with zero deadline expiries.

Every knob defaults off/0 = the byte-identical wire and store (golden frames
pinned by tests/test_obs.py::TestGoldenFramesUnchanged).
"""

import os
import socket
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock
from sparkucx_tpu.core.definitions import (
    AmId,
    FRAME_HEADER_SIZE,
    unpack_frame_header,
)
from sparkucx_tpu.core.operation import (
    OperationStatus,
    ResourceExhaustedError,
    TransportError,
)
from sparkucx_tpu.shuffle.reader import TpuShuffleReader
from sparkucx_tpu.shuffle.resolver import ring_neighbors
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.peer import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    PeerTransport,
)


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
    return ts, addrs


def _close_all(ts):
    for t in ts:
        t.close()


def _chaos_seed(default):
    """Payload seed for the acceptance scenarios: CI's chaos matrix re-runs
    them with ``SPARKUCX_TPU_CHAOS_SEED={1,2,3}`` to prove gray-failure
    recovery is seed-independent, not a golden-path accident."""
    return int(os.environ.get("SPARKUCX_TPU_CHAOS_SEED", default))


def _stage(t, shuffle_id, num_mappers, num_reducers, seed=0):
    rng = np.random.default_rng(seed)
    t.store.create_shuffle(shuffle_id, num_mappers, num_reducers)
    payloads = {}
    for m in range(num_mappers):
        w = t.store.map_writer(shuffle_id, m)
        for r in range(num_reducers):
            data = rng.integers(0, 256, size=200 + 37 * (m + r), dtype=np.uint8).tobytes()
            payloads[(m, r)] = data
            w.write_partition(r, data)
        w.commit()
    return payloads


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
        max_blocks_per_request=1,
        sender_of=lambda m: 1,
        replica_of=lambda primary: ring_neighbors(primary, executors, 1),
        **kw,
    )


# ---------------------------------------------------------------------------
# knob parsing + byte-identical defaults
# ---------------------------------------------------------------------------


class TestGrayKnobs:
    def test_knob_parsing_from_spark_conf(self):
        conf = TpuShuffleConf.from_spark_conf(
            {
                "spark.shuffle.tpu.fetch.hedgeMs": "40",
                "spark.shuffle.tpu.fetch.hedgeMaxMs": "250",
                "spark.shuffle.tpu.breaker.failureThreshold": "3",
                "spark.shuffle.tpu.breaker.cooldownMs": "500",
                "spark.shuffle.tpu.store.softWatermark": "64m",
                "spark.shuffle.tpu.store.hardWatermark": "128m",
                "spark.shuffle.tpu.server.acceptBacklog": "2048",
            }
        )
        assert conf.fetch_hedge_ms == 40
        assert conf.fetch_hedge_max_ms == 250
        assert conf.breaker_failure_threshold == 3
        assert conf.breaker_cooldown_ms == 500
        assert conf.store_soft_watermark == 64 * 1024 * 1024
        assert conf.store_hard_watermark == 128 * 1024 * 1024
        assert conf.server_accept_backlog == 2048

    def test_defaults_are_off(self):
        """Every gray-failure knob defaults to 0/off: no hedges, no breaker
        trips, no watermarks, no shedding — the byte-identical plane."""
        conf = TpuShuffleConf()
        assert conf.fetch_hedge_ms == 0
        assert conf.fetch_hedge_max_ms == 0
        assert conf.breaker_failure_threshold == 0
        assert conf.breaker_cooldown_ms == 1000  # latent until threshold > 0
        assert conf.store_soft_watermark == 0
        assert conf.store_hard_watermark == 0
        assert conf.server_accept_backlog == 0


# ---------------------------------------------------------------------------
# peer health scoring + circuit breakers
# ---------------------------------------------------------------------------


class TestBreaker:
    def _transport(self, **conf_kw):
        conf_kw.setdefault("staging_capacity_per_executor", 1 << 20)
        return PeerTransport(TpuShuffleConf(**conf_kw), executor_id=0)

    def test_scoring_without_threshold_never_trips(self):
        t = self._transport()
        try:
            for _ in range(50):
                t.record_peer_failure(7, "synthetic")
            assert t.breaker_state(7) == BREAKER_CLOSED
            assert t.breaker_allows(7)
            snap = t.health_snapshot()[7]
            assert snap["failures"] == 50 and snap["trips"] == 0
            assert snap["error_ewma"] > 0.9  # EWMA converged toward 1.0
        finally:
            t.close()

    def test_trip_cooldown_half_open_probe_close(self):
        t = self._transport(breaker_failure_threshold=3, breaker_cooldown_ms=50)
        try:
            t.record_peer_failure(7)
            t.record_peer_failure(7)
            assert t.breaker_state(7) == BREAKER_CLOSED  # streak below threshold
            t.record_peer_failure(7)
            assert t.breaker_state(7) == BREAKER_OPEN
            assert not t.breaker_allows(7)  # open rejects inside cooldown
            assert t.health_snapshot()[7]["trips"] == 1
            time.sleep(0.06)
            assert t.breaker_allows(7)  # cooldown elapsed: ONE probe admitted
            assert t.breaker_state(7) == BREAKER_HALF_OPEN
            assert not t.breaker_allows(7)  # second probe rejected in flight
            t.record_peer_success(7, latency_ns=1_000_000)
            assert t.breaker_state(7) == BREAKER_CLOSED
            assert t.breaker_allows(7)
            snap = t.health_snapshot()[7]
            assert snap["consecutive_failures"] == 0
            assert snap["latency_ewma_ns"] == 1_000_000
        finally:
            t.close()

    def test_failed_probe_reopens_with_fresh_cooldown(self):
        t = self._transport(breaker_failure_threshold=2, breaker_cooldown_ms=40)
        try:
            t.record_peer_failure(3)
            t.record_peer_failure(3)
            time.sleep(0.05)
            assert t.breaker_allows(3)  # half-open probe
            t.record_peer_failure(3)  # probe failed
            assert t.breaker_state(3) == BREAKER_OPEN
            assert not t.breaker_allows(3)  # cooldown restarted
            assert t.health_snapshot()[3]["trips"] == 2
        finally:
            t.close()

    def test_success_resets_streak(self):
        t = self._transport(breaker_failure_threshold=3)
        try:
            t.record_peer_failure(5)
            t.record_peer_failure(5)
            t.record_peer_success(5)
            t.record_peer_failure(5)
            t.record_peer_failure(5)
            assert t.breaker_state(5) == BREAKER_CLOSED  # streak broken at 2
        finally:
            t.close()

    def test_health_view_rollup(self):
        t = self._transport(breaker_failure_threshold=1, breaker_cooldown_ms=60_000)
        try:
            assert t._health_view() == {}  # nothing scored yet: empty family
            t.record_peer_success(1, latency_ns=2_000_000)
            t.record_peer_failure(2)
            view = t._health_view()
            assert view["peers"] == 2
            assert view["open"] == 1 and view["half_open"] == 0
            assert view["successes"] == 1 and view["failures"] == 1
            assert view["trips"] == 1
            assert view["latency_ewma_ns_max"] == 2_000_000
            # the roll-up rides the metrics registry as the `health` family
            text = t.metrics.prometheus_text()
            assert "sparkucx_tpu_health_open" in text
        finally:
            t.close()

    def test_wire_failure_feeds_breaker_and_routes_to_replica(self):
        """A dead primary trips the breaker via the wire-observation path, and
        the reader's candidate filter skips the open breaker — the replica
        serves without burning the primary's full deadline again."""
        ts, _ = _cluster(
            3,
            replication_factor=1,
            wire_timeout_ms=5000,
            breaker_failure_threshold=1,
            breaker_cooldown_ms=60_000,
        )
        try:
            payloads = _stage(ts[1], 0, 2, 3, seed=11)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            faults.kill_executor(ts[1])
            reader = _reader(ts[0], payloads, 2, 3, executors=[0, 1, 2])
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads  # bit-identical through the failover
            assert ts[0].breaker_state(1) == BREAKER_OPEN
            assert ts[0].health_snapshot()[1]["failures"] >= 1
            assert reader.metrics.failovers >= 1
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# memory-pressure watermarks + load shedding (store / pool / wire)
# ---------------------------------------------------------------------------


class TestMemoryPressure:
    def test_hard_watermark_sheds_staging_write_typed(self):
        ts, _ = _cluster(1, store_hard_watermark=512)
        try:
            ts[0].store.create_shuffle(1, 1, 1)
            w = ts[0].store.map_writer(1, 0)
            with pytest.raises(ResourceExhaustedError) as ei:
                w.write_partition(0, b"x" * 600)
            e = ei.value
            assert isinstance(e, TransportError)  # old catch-sites still work
            assert e.requested >= 600
            assert e.watermark == 512
            assert "store hard watermark" in str(e)
            # the shed write left the store exactly as it was
            assert ts[0].store.memory_pressure_bytes() == 0
        finally:
            _close_all(ts)

    def test_soft_watermark_kicks_single_flight_sweep(self):
        from sparkucx_tpu.service.eviction import EvictionManager

        ts, _ = _cluster(1, store_soft_watermark=256)
        try:
            ts[0].store.eviction = EvictionManager(ts[0].store)
            _stage(ts[0], 2, 1, 2, seed=3)  # crosses 256 B of staged bytes
            stats = ts[0].store.watermark_stats()
            assert stats["watermark_sweeps"] >= 1
            assert stats["pressure_bytes"] > 256
        finally:
            _close_all(ts)

    def test_soft_watermark_without_eviction_manager_is_inert(self):
        ts, _ = _cluster(1, store_soft_watermark=256)
        try:
            payloads = _stage(ts[0], 2, 1, 2, seed=3)  # no manager: no sweep
            assert ts[0].store.watermark_stats()["watermark_sweeps"] == 0
            for (m, r), data in payloads.items():
                assert ts[0].store.read_block(2, m, r) == data
        finally:
            _close_all(ts)

    def test_pool_budget_sheds_slab_growth_typed(self):
        from sparkucx_tpu.memory.pool import MemoryPool

        pool = MemoryPool(TpuShuffleConf(store_hard_watermark=1))
        try:
            with pytest.raises(ResourceExhaustedError, match="memory pool hard watermark"):
                pool.get(64)
        finally:
            pool.close()

    def test_pool_budget_zero_is_unbounded(self):
        from sparkucx_tpu.memory.pool import MemoryPool

        pool = MemoryPool(TpuShuffleConf())
        try:
            mb = pool.get(64)
            assert mb.size == 64
            mb.close()
        finally:
            pool.close()

    def test_replica_put_shed_discards_without_ack(self):
        """A pressured replica holder drops the REPLICA_PUT (no ack) instead
        of dying: the pusher's replication_wait reports unsettled, exactly
        like the sever case, and both executors stay serviceable."""
        ts, _ = _cluster(2, replication_factor=1)
        try:
            faults.arm(
                "store.mem_pressure",
                faults.fail(ResourceExhaustedError(detail="injected pressure")),
                match={"site": "put_replica"},
            )
            payloads = _stage(ts[0], 5, 1, 1)
            ts[0].store.seal(5)
            assert not ts[0].replication_wait(5, timeout=0.7)
            assert ts[1].store.replica_view(5, 0, 0) is None
            # the holder itself is fine — primary reads still serve
            assert ts[0].store.read_block(5, 0, 0) == payloads[(0, 0)]
        finally:
            _close_all(ts)

    def test_shed_restage_retries_and_recovers(self):
        """Acceptance: under an injected hard-watermark shed the client gets
        the typed RETRYABLE error over the wire (size code -4), backs off,
        retries, and completes bit-identically — no OOM, no hang."""
        from sparkucx_tpu.service.eviction import EvictionManager

        ts, _ = _cluster(3, replication_factor=1, wire_timeout_ms=5000)
        try:
            payloads = _stage(ts[1], 0, 2, 3, seed=_chaos_seed(9))
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            ts[1].store.eviction = EvictionManager(ts[1].store)
            while ts[1].store.round_tier(0, 0) != "disk":
                assert ts[1].store.demote_round(0, 0) is not None
            # first restage attempt hits (injected) memory pressure: the
            # serve fails typed-retryable; the reader's backoff retry lands
            # after the pressure "cleared" (times=1) and restages fine
            faults.arm(
                "store.mem_pressure",
                faults.fail(ResourceExhaustedError(detail="injected pressure")),
                times=1,
                match={"site": "restage_round"},
            )
            reader = _reader(ts[0], payloads, 2, 3, executors=[0, 1, 2])
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads  # bit-identical through the shed
            assert faults.fired["store.mem_pressure"] == 1
            assert reader.metrics.blocks_retried + reader.metrics.failovers >= 1
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# reactor load shedding (server.acceptBacklog -> ServerBusy)
# ---------------------------------------------------------------------------


class TestAcceptShedding:
    def test_reactor_sheds_over_backlog_with_busy_frame(self):
        from sparkucx_tpu.service.reactor import Reactor

        r = Reactor(workers=1, name="test-shed", accept_backlog=1)
        srv = socket.socket()
        try:
            srv.bind(("127.0.0.1", 0))
            srv.listen(16)
            addr = srv.getsockname()

            def serve_once(conn):
                return bool(conn.recv(64))

            r.add_listener(srv, lambda c: r.add_connection(c, serve_once))
            first = socket.create_connection(addr, timeout=5)
            deadline = time.monotonic() + 5
            while r.num_connections < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert r.num_connections == 1  # resident, inside the backlog
            second = socket.create_connection(addr, timeout=5)
            second.settimeout(5)
            hdr = b""
            while len(hdr) < FRAME_HEADER_SIZE:
                chunk = second.recv(FRAME_HEADER_SIZE - len(hdr))
                if not chunk:
                    break
                hdr += chunk
            am_id, hlen, blen = unpack_frame_header(hdr)
            assert am_id == AmId.SERVER_BUSY  # typed busy reply...
            assert hlen == 0 and blen == 0  # ...headerless and bodyless
            assert second.recv(1) == b""  # ...then an immediate close
            assert r.stats()["sheds"] == 1
            assert r.num_connections == 1  # the resident conn was untouched
            first.close()
            second.close()
        finally:
            r.close()
            srv.close()

    def test_shed_fetch_fails_typed_retryable(self):
        """End to end over the peer plane: a raw connection parks inside the
        backlog, the transport's fetch connection is shed, and the in-flight
        request dies with the RETRYABLE ResourceExhaustedError — not the
        generic connection-lost TransportError."""
        ts, addrs = _cluster(2, server_accept_backlog=1)
        try:
            host, _, port = addrs[1].decode().rpartition(":")
            parked = socket.create_connection((host, int(port)), timeout=5)
            reactor = ts[1].server._reactor
            deadline = time.monotonic() + 5
            while reactor.num_connections < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert reactor.num_connections == 1
            buf = _buf(64)
            req = ts[0].fetch_block(1, 0, 0, 0, buf)
            deadline = time.monotonic() + 5
            while not req.completed() and time.monotonic() < deadline:
                ts[0].progress()
                time.sleep(0.002)
            assert req.completed()
            res = req.wait(1)
            assert res.status == OperationStatus.FAILURE
            assert isinstance(res.error, ResourceExhaustedError)
            assert "accept backlog" in str(res.error)
            parked.close()
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# hedged fetches
# ---------------------------------------------------------------------------


class TestHedgedFetches:
    def test_hedge_delay_clamped_between_floor_and_ceiling(self):
        ts, _ = _cluster(1)
        try:
            payloads = {(0, 0): b"x" * 64}
            r = _reader(
                ts[0], payloads, 1, 1, executors=[0],
                fetch_hedge_ms=40, fetch_hedge_max_ms=100,
            )
            delay = r._hedge_delay_ns()
            assert 40 * 1_000_000 <= delay <= 100 * 1_000_000
            off = _reader(ts[0], payloads, 1, 1, executors=[0])
            assert off._hedge_delay_ns() == 0  # default: hedging off
        finally:
            _close_all(ts)

    def test_stalled_primary_hedge_wins_bit_identical(self):
        """The acceptance chaos scenario: the primary is STALLED (gray), not
        killed — every frame it serves sleeps well past the hedge delay.
        Hedged fetches complete from the replica ring bit-identically, with
        zero deadline expiries and the stall never dominating the read."""
        ts, _ = _cluster(3, replication_factor=1, wire_timeout_ms=10_000)
        try:
            payloads = _stage(ts[1], 0, 2, 3, seed=_chaos_seed(42))
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            # stall ONLY the primary's serving plane (executor 1); the faults
            # registry is process-global, so the match key pins one server
            faults.arm("peer.server.frame", faults.stall(0.25), match={"executor": 1})
            reader = _reader(
                ts[0], payloads, 2, 3, executors=[0, 1, 2],
                fetch_deadline_ms=5000,
                fetch_hedge_ms=40, fetch_hedge_max_ms=60,
            )
            t0 = time.monotonic()
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            elapsed = time.monotonic() - t0
            assert got == payloads  # bit-identical from the replica holders
            m = reader.metrics
            assert m.hedges_issued >= 1
            assert m.hedge_wins >= 1
            assert m.fetch_timeouts == 0  # zero deadline expiries
            # a hedge's bytes arrive from another executor: copied, never borrowed
            assert (m.copied_blocks, m.resident_blocks) == (len(payloads), 0)
            # 6 windows x 0.25 s of stall would be >= 1.5 s un-hedged; hedges
            # must keep the read well under the sum of the stalls
            assert elapsed < 1.5
        finally:
            _close_all(ts)

    def test_healthy_cluster_hedges_lose_quietly(self):
        """With hedging on but nobody straggling slower than the hedge delay,
        any hedge that does fire loses to the primary and is quarantined —
        the output is untouched and nothing leaks."""
        ts, _ = _cluster(3, replication_factor=1)
        try:
            payloads = _stage(ts[1], 0, 2, 3, seed=8)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            reader = _reader(
                ts[0], payloads, 2, 3, executors=[0, 1, 2],
                fetch_hedge_ms=2000, fetch_hedge_max_ms=2000,
            )
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads
            assert reader.metrics.hedge_wins == 0  # primary always beat 2 s
        finally:
            _close_all(ts)

    def test_hedging_off_by_default(self):
        ts, _ = _cluster(3, replication_factor=1)
        try:
            payloads = _stage(ts[1], 0, 1, 2, seed=4)
            ts[1].store.seal(0)
            assert ts[1].replication_wait(0, timeout=10.0)
            reader = _reader(ts[0], payloads, 1, 2, executors=[0, 1, 2])
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads
            assert reader.metrics.hedges_issued == 0
        finally:
            _close_all(ts)

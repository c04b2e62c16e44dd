"""Popularity-aware serving tier: hot-block fanout + serve-side read cache.

Pins the PR's contracts:

* per-block fetch-rate EWMAs (``serve.hotThresholdFetchesPerSec``): promote
  on a fetch storm, demote on cooling with hysteresis (demote edge = half the
  promote edge), idle-entry GC — all on an injectable clock,
* the bounded serve-side decoded-block cache (``serve.cacheBytes``):
  byte-budgeted LRU above the eviction tiers, charged against the owning
  tenant's quota, evictions release their charges,
* hot promotion widens the replica set beyond ``replication.factor`` ring
  successors (``serve.hotReplicas``) over the existing REPLICA_PUT plane and
  advertises the holder set over HOT_SET_PULL; cool-down drops only the
  advertisement (replicas never fall below the fault-tolerance floor),
* reader-side load spreading: deterministic-per-reader rotation over the
  advertised holders, hedges prefer a holder DIFFERENT from the executor the
  straggling fetch actually targeted,
* the encoded-chunk pool is LRU under ``compress.cacheBytes`` with
  hit/miss/eviction counters,
* the chaos lane: one hot-block holder killed mid-storm, reads stay
  bit-identical,
* every knob defaults off = byte-identical wire + store (the golden frames
  of tests/test_obs.py::TestGoldenFramesUnchanged stay pinned).
"""

import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.definitions import AmId, pack_hot_set, unpack_hot_set
from sparkucx_tpu.core.operation import OperationStatus, TransportError
from sparkucx_tpu.service.eviction import ServeCache
from sparkucx_tpu.service.tenants import TenantRegistry
from sparkucx_tpu.shuffle.reader import TpuShuffleReader
from sparkucx_tpu.shuffle.resolver import ring_neighbors, widened_ring_neighbors
from sparkucx_tpu.service.popularity import BlockPopularity
from sparkucx_tpu.store.hbm_store import HbmBlockStore
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


def _fetch_one(t, peer, sid, m, r, size, timeout=5.0):
    buf = _buf(size)
    req = t.fetch_block(peer, sid, m, r, buf)
    deadline = time.monotonic() + timeout
    while not req.completed() and time.monotonic() < deadline:
        t.progress()
    res = req.wait(1)
    assert res.status == OperationStatus.SUCCESS, str(res.error)
    return buf.host_view()[:size].tobytes()


def _storm(t, peer, sid, m, r, size, rounds=6):
    """Hot loop on one block: back-to-back fetches push its rate EWMA far
    past any CI-realistic threshold."""
    out = None
    for _ in range(rounds):
        out = _fetch_one(t, peer, sid, m, r, size)
    return out


# ---------------------------------------------------------------------------
# knobs: parsing + defaults-off
# ---------------------------------------------------------------------------


class TestServeKnobs:
    def test_knob_parsing_from_spark_conf(self):
        conf = TpuShuffleConf.from_spark_conf(
            {
                "spark.shuffle.tpu.serve.hotThresholdFetchesPerSec": "25",
                "spark.shuffle.tpu.serve.hotReplicas": "3",
                "spark.shuffle.tpu.serve.cacheBytes": "4m",
                "spark.shuffle.tpu.serve.holdersTtlMs": "100",
                "spark.shuffle.tpu.compress.cacheBytes": "2m",
            }
        )
        assert conf.serve_hot_threshold_fetches_per_sec == 25.0
        assert conf.serve_hot_replicas == 3
        assert conf.serve_cache_bytes == 4 << 20
        assert conf.serve_holders_ttl_ms == 100
        assert conf.compress_cache_bytes == 2 << 20

    def test_defaults_are_off(self):
        """Threshold 0 = no tracker, no HOT_SET_PULL traffic, no serve cache;
        the compress pool cap keeps its historical 128 MiB default, the
        holder-set TTL its historical 250 ms."""
        conf = TpuShuffleConf()
        assert conf.serve_hot_threshold_fetches_per_sec == 0.0
        assert conf.serve_cache_bytes == 0
        assert conf.compress_cache_bytes == 128 << 20
        assert conf.serve_hot_replicas == 4  # inert while the threshold is 0
        assert conf.serve_holders_ttl_ms == 250  # inert while the threshold is 0

    def test_validation_rejects_negative(self):
        with pytest.raises(ValueError):
            TpuShuffleConf(serve_hot_threshold_fetches_per_sec=-1).validate()
        with pytest.raises(ValueError):
            TpuShuffleConf(serve_cache_bytes=-1).validate()
        with pytest.raises(ValueError):
            TpuShuffleConf(compress_cache_bytes=-1).validate()
        with pytest.raises(ValueError):
            TpuShuffleConf(serve_holders_ttl_ms=-1).validate()

    def test_holders_ttl_governs_pull_rate(self, monkeypatch):
        """The hot_holders cache honors ``serve.holdersTtlMs``: a long TTL
        serves the cached table without a HOT_SET_PULL round-trip; TTL 0
        means every call re-pulls (the freshest-possible setting)."""
        ts = _cluster(
            2, serve_hot_threshold_fetches_per_sec=5.0, serve_holders_ttl_ms=60_000
        )
        try:
            pulls = []
            real_pull = ts[1]._pull

            def counting_pull(eid, am_id, timeout=1.0):
                if am_id == AmId.HOT_SET_PULL:
                    pulls.append(eid)
                return real_pull(eid, am_id, timeout=timeout)

            monkeypatch.setattr(ts[1], "_pull", counting_pull)
            ts[1].hot_holders(0, 0)
            ts[1].hot_holders(0, 0)
            assert len(pulls) == 1  # second call inside the TTL: cached

            ts[1].conf.serve_holders_ttl_ms = 0
            ts[1].hot_holders(0, 0)
            ts[1].hot_holders(0, 0)
            assert len(pulls) == 3  # TTL 0: every call round-trips
        finally:
            _close_all(ts)

    def test_default_transport_has_no_popularity_plane(self):
        ts = _cluster(1)
        try:
            assert ts[0].popularity is None
            assert ts[0].store.serve_cache is None
            assert ts[0].hot_holders(0, 0) == []  # tier off: no pull, ever
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# BlockPopularity: EWMA promote/demote on an injected clock
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.ns


class TestBlockPopularity:
    def test_storm_promotes_once_per_shuffle(self):
        clk = _Clock()
        pop = BlockPopularity(100.0, now_ns=clk)
        hot, trans = pop.observe(7, 0, 0)  # first sighting only records
        assert (hot, trans) == (False, [])
        clk.ns += 1_000_000  # 1 ms apart = 1000 fetches/sec instantaneous
        hot, trans = pop.observe(7, 0, 0)
        assert hot and trans == [(7, True)]  # ewma = 0.25 * 1000 >= 100
        clk.ns += 1_000_000
        hot, trans = pop.observe(7, 0, 1)  # second block heats up
        assert trans == []  # no first sighting yet
        clk.ns += 1_000_000
        hot, trans = pop.observe(7, 0, 1)
        assert hot and trans == []  # shuffle already hot: no new transition
        assert pop.is_hot(7) and pop.hot_shuffles() == [7]
        snap = pop.snapshot()
        assert snap["promotions"] == 2 and snap["hot_blocks"] == 2
        assert snap["hot_shuffles"] == 1

    def test_slow_fetches_never_promote(self):
        clk = _Clock()
        pop = BlockPopularity(100.0, now_ns=clk)
        for _ in range(50):
            clk.ns += 1_000_000_000  # 1/sec, threshold 100/sec
            hot, trans = pop.observe(3, 0, 0)
            assert not hot and trans == []
        assert not pop.is_hot(3)

    def test_cooling_demotes_with_hysteresis(self):
        clk = _Clock()
        pop = BlockPopularity(100.0, now_ns=clk)
        pop.observe(7, 0, 0)
        clk.ns += 1_000_000
        assert pop.observe(7, 0, 0)[0]  # hot at ewma 250
        # 5 ms of silence: effective rate min(250, 200) stays over the
        # demote edge (50) -> hysteresis holds the block hot
        assert pop.sweep(clk.ns + 5_000_000) == []
        assert pop.is_hot(7)
        # 100 ms of silence: effective rate 10 < 50 -> the shuffle's last
        # hot block cools and the demote transition fires
        assert pop.sweep(clk.ns + 100_000_000) == [(7, False)]
        assert not pop.is_hot(7)
        assert pop.snapshot()["demotions"] == 1

    def test_idle_cold_entries_are_forgotten(self):
        clk = _Clock()
        pop = BlockPopularity(100.0, now_ns=clk)
        pop.observe(1, 0, 0)
        assert pop.snapshot()["tracked_blocks"] == 1
        pop.sweep(clk.ns + 61 * 1_000_000_000)  # past _IDLE_GC_NS
        assert pop.snapshot()["tracked_blocks"] == 0

    def test_maybe_sweep_is_rate_limited(self):
        clk = _Clock()
        pop = BlockPopularity(100.0, now_ns=clk)
        pop.observe(7, 0, 0)
        clk.ns += 1_000_000
        pop.observe(7, 0, 0)
        clk.ns += 200_000_000_000  # everything long cold
        assert pop.maybe_sweep() == [(7, False)]  # first scan runs
        pop.observe(7, 1, 1)
        clk.ns += 500_000  # within the 1 s interval
        assert pop.maybe_sweep() == []  # rate-limited: no scan

    def test_threshold_zero_is_inert(self):
        pop = BlockPopularity(0.0, now_ns=_Clock())
        assert pop.observe(1, 0, 0) == (False, [])
        assert pop.maybe_sweep() == []
        assert pop.snapshot()["tracked_blocks"] == 0


# ---------------------------------------------------------------------------
# ServeCache: byte-budgeted LRU + tenant quota interplay
# ---------------------------------------------------------------------------


class TestServeCache:
    def test_lru_eviction_order_and_evicted_list(self):
        c = ServeCache(100)
        assert c.put((0, 0, 0), b"x" * 40) == []
        assert c.put((0, 0, 1), b"y" * 40) == []
        assert c.get((0, 0, 0)) == b"x" * 40  # refreshes (0,0,0) to MRU
        evicted = c.put((0, 0, 2), b"z" * 40)  # (0,0,1) is now LRU
        assert evicted == [((0, 0, 1), 40)]
        assert c.get((0, 0, 1)) is None
        assert c.get((0, 0, 0)) is not None
        assert c.used_bytes == 80 and len(c) == 2

    def test_oversized_block_rejected(self):
        c = ServeCache(10)
        assert c.put((0, 0, 0), b"a" * 11) == []
        assert len(c) == 0 and c.snapshot()["cache_rejects"] == 1

    def test_replace_refunds_previous_bytes(self):
        c = ServeCache(100)
        c.put((0, 0, 0), b"a" * 30)
        evicted = c.put((0, 0, 0), b"b" * 50)
        # the replaced payload's bytes come back so the caller releases them
        assert ((0, 0, 0), 30) in evicted
        assert c.used_bytes == 50 and c.get((0, 0, 0)) == b"b" * 50

    def test_invalidate_shuffle_drops_only_that_shuffle(self):
        c = ServeCache(1000)
        c.put((1, 0, 0), b"a" * 10)
        c.put((2, 0, 0), b"b" * 20)
        dropped = c.invalidate_shuffle(1)
        assert dropped == [((1, 0, 0), 10)]
        assert c.get((2, 0, 0)) is not None and c.used_bytes == 20

    def test_store_offer_charges_and_releases_tenant(self):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, serve_cache_bytes=600
        )
        store = HbmBlockStore(conf)
        try:
            reg = TenantRegistry(default_quota_bytes=1 << 20)
            reg.register("appA")
            store.tenants = reg
            store.create_shuffle(5, 1, 1, app_id="appA")
            base = reg.usage("appA")
            assert store.serve_cache_offer(5, 0, 0, b"p" * 500)
            assert reg.usage("appA") == base + 500
            # the next offer LRU-evicts the first entry: its charge comes back
            assert store.serve_cache_offer(5, 0, 1, b"q" * 400)
            assert reg.usage("appA") == base + 400
            arr, off, ln = store.serve_cache_get(5, 0, 1)
            assert bytes(arr[off : off + ln]) == b"q" * 400
        finally:
            store.close()

    def test_store_offer_respects_quota(self):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, serve_cache_bytes=1 << 20
        )
        store = HbmBlockStore(conf)
        try:
            reg = TenantRegistry(default_quota_bytes=100)
            reg.register("appB")
            store.tenants = reg
            store.create_shuffle(6, 1, 1, app_id="appB")
            used = reg.usage("appB")
            # no headroom for 200 bytes: the offer fails closed, no charge
            assert not store.serve_cache_offer(6, 0, 0, b"r" * 200)
            assert reg.usage("appB") == used
            assert store.serve_cache_get(6, 0, 0) is None
        finally:
            store.close()

    def test_remove_shuffle_invalidates_without_double_release(self):
        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 20, serve_cache_bytes=1 << 20
        )
        store = HbmBlockStore(conf)
        try:
            reg = TenantRegistry(default_quota_bytes=1 << 20)
            reg.register("appC")
            store.tenants = reg
            store.create_shuffle(7, 1, 1, app_id="appC")
            assert store.serve_cache_offer(7, 0, 0, b"s" * 300)
            store.remove_shuffle(7)
            # the blanket shuffle release already covered the cache charge;
            # a double release would drive usage negative
            assert reg.usage("appC") == 0
            assert store.serve_cache_get(7, 0, 0) is None
        finally:
            store.close()


# ---------------------------------------------------------------------------
# HOT_SET_PULL wire schema
# ---------------------------------------------------------------------------


class TestHotSetWire:
    def test_pack_unpack_roundtrip(self):
        table = {3: [0, 2, 5], 1: [4], 9: []}
        assert unpack_hot_set(pack_hot_set(table)) == {3: [0, 2, 5], 1: [4], 9: []}
        assert unpack_hot_set(pack_hot_set({})) == {}

    def test_pack_is_deterministic_sorted(self):
        a = pack_hot_set({2: [1, 0], 1: [3]})
        b = pack_hot_set({1: [3], 2: [0, 1]})
        assert a == b  # sorted shuffles, sorted holders: canonical bytes

    def test_am_id_pinned(self):
        assert AmId.HOT_SET_PULL == 14


# ---------------------------------------------------------------------------
# reader-side spreading + hedge-target choice
# ---------------------------------------------------------------------------


class _FakeReq:
    def completed(self):
        return False


class _FakeTransport:
    """Just enough surface for the hedge/spread unit paths."""

    executor_id = 0

    def __init__(self):
        self.hedged_to = []

    def fetch_block(self, executor_id, sid, m, r, buf):
        self.hedged_to.append(executor_id)
        return _FakeReq()


def _bare_reader(executor_id, holders_of=None, replica_of=None, **kw):
    payload_len = 64
    return TpuShuffleReader(
        _FakeTransport(),
        executor_id,
        0,
        0,
        1,
        4,
        block_sizes=lambda m, r: payload_len,
        sender_of=lambda m: 1,
        holders_of=holders_of,
        replica_of=replica_of,
        **kw,
    )


class TestSpreadAndHedgeTargets:
    def test_spread_rotation_is_deterministic_per_reader(self):
        holders = {1: [1, 2, 3]}
        r5 = _bare_reader(5, holders_of=lambda p, sid: holders[p])
        r6 = _bare_reader(6, holders_of=lambda p, sid: holders[p])
        bid = ShuffleBlockId(0, 2, 0)
        # (executor + map + reduce) % len: reader 5 -> holders[1]=2,
        # reader 6 -> holders[2]=3 — neighbors land on different holders
        assert r5._spread_target(bid) == 2
        assert r6._spread_target(bid) == 3
        assert r5._spread_target(bid) == r5._spread_target(bid)  # stable

    def test_spread_falls_back_to_primary(self):
        bid = ShuffleBlockId(0, 0, 0)
        assert _bare_reader(5)._spread_target(bid) == 1  # no holders_of
        r = _bare_reader(5, holders_of=lambda p, sid: [1])
        assert r._spread_target(bid) == 1  # singleton set: primary
        r = _bare_reader(5, holders_of=lambda p, sid: (_ for _ in ()).throw(TransportError("x")))
        assert r._spread_target(bid) == 1  # pull failure: primary

    def test_spread_never_targets_self(self):
        r = _bare_reader(2, holders_of=lambda p, sid: [1, 2, 3])
        for m in range(4):
            for rid in range(4):
                assert r._spread_target(ShuffleBlockId(0, m, rid)) != 2

    def test_hedge_prefers_non_actual_holder(self):
        """Satellite contract: with >1 holder the hedge goes to a DIFFERENT
        executor than the straggling fetch actually targeted — pinned to the
        deterministic rotation over the admissible candidates."""
        r = _bare_reader(
            5,
            holders_of=lambda p, sid: [1, 2, 3],
            replica_of=lambda p: ring_neighbors(p, [1, 2, 3], 1),
        )
        bid = ShuffleBlockId(0, 2, 0)
        actual = r._spread_target(bid)  # reader 5 -> holder 2
        assert actual == 2
        r._window_targets[bid] = actual
        hedges = {}
        r._issue_hedges([(bid, None, _FakeReq())], hedges)
        assert 0 in hedges
        _, _, target = hedges[0]
        # admissible = [1, 3] (holders minus the actual target); rotation
        # (5 + 2 + 0) % 2 = 1 -> executor 3
        assert target == 3
        assert target != actual
        assert r.transport.hedged_to == [3]
        assert r.metrics.hedges_issued == 1

    def test_hedge_falls_back_to_ring_when_no_advertisement(self):
        r = _bare_reader(
            0, replica_of=lambda p: ring_neighbors(p, [0, 1, 2], 1)
        )
        bid = ShuffleBlockId(0, 0, 0)  # primary 1, actual 1, ring successor 2
        hedges = {}
        r._issue_hedges([(bid, None, _FakeReq())], hedges)
        assert hedges[0][2] == 2

    def test_hedge_never_races_actual_target_or_self(self):
        r = _bare_reader(
            3, holders_of=lambda p, sid: [1, 3], replica_of=lambda p: [3]
        )
        bid = ShuffleBlockId(0, 0, 0)
        r._window_targets[bid] = 1
        hedges = {}
        # candidates reduce to {1 (actual), 3 (self)}: nothing admissible
        r._issue_hedges([(bid, None, _FakeReq())], hedges)
        assert hedges == {}


# ---------------------------------------------------------------------------
# encoded-chunk pool counters (LRU details live in test_compress.py)
# ---------------------------------------------------------------------------


class TestEncodedPoolCounters:
    def test_hit_miss_eviction_counters_export(self):
        ts = _cluster(2, wire_compress_codec="rle")
        try:
            payloads = _stage(ts[0], 1, 1, 2, seed=3)
            ts[0].store.seal(1)
            for _ in range(2):
                for (m, r), p in sorted(payloads.items()):
                    assert _fetch_one(ts[1], 0, 1, m, r, len(p)) == p
            snap = ts[0].server.compress_snapshot()
            assert snap["cache_misses"] >= 2  # first pass encodes
            assert snap["cache_hits"] >= 2  # second pass serves the pool
            assert snap["cache_evictions"] == 0  # default cap: no pressure
            # and the counters ride the existing compress metrics family
            text = ts[0].metrics.prometheus_text()
            assert "compress" in text and "cache_misses" in text
        finally:
            _close_all(ts)

    def test_cache_bytes_zero_disables_pool(self):
        ts = _cluster(2, wire_compress_codec="rle", compress_cache_bytes=0)
        try:
            payloads = _stage(ts[0], 1, 1, 1, seed=4)
            ts[0].store.seal(1)
            p = payloads[(0, 0)]
            assert _fetch_one(ts[1], 0, 1, 0, 0, len(p)) == p
            assert _fetch_one(ts[1], 0, 1, 0, 0, len(p)) == p
            snap = ts[0].server.compress_snapshot()
            assert snap["cache_hits"] == 0  # pool off: every fetch re-encodes
            assert len(ts[0].server._encoded_pool) == 0
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# the lifecycle: storm -> promote -> widen -> spread -> cool -> demote
# ---------------------------------------------------------------------------


def _serve_cluster(n=4, **kw):
    kw.setdefault("replication_factor", 1)
    # 1 fetch/sec: any back-to-back loopback storm promotes even on a
    # heavily loaded CI worker, while one-shot fetches stay cold
    kw.setdefault("serve_hot_threshold_fetches_per_sec", 1.0)
    kw.setdefault("serve_hot_replicas", 2)
    kw.setdefault("serve_cache_bytes", 1 << 20)
    return _cluster(n, **kw)


class TestPopularityLifecycle:
    def test_storm_promotes_widens_and_serves_bit_identical(self):
        ts = _serve_cluster()
        try:
            payloads = _stage(ts[0], 0, 1, 2, seed=11)
            ts[0].store.seal(0)
            assert ts[0].replication_wait(0, timeout=10.0)
            # fault-tolerance floor: base ring successor (executor 1) only
            assert ts[1].store.replica_view(0, 0, 0) is not None
            assert ts[2].store.replica_view(0, 0, 0) is None

            p = payloads[(0, 0)]
            got = _storm(ts[3], 0, 0, 0, 0, len(p))
            assert got == p  # storm payloads bit-identical throughout

            assert ts[0].popularity.is_hot(0)
            snap = ts[0]._serve_view()
            assert snap["promotions"] >= 1 and snap["advertised_hot_shuffles"] == 1

            # the widen push replicated the round onto the EXTRA holder
            assert ts[0].replication_wait(0, timeout=10.0)
            assert ts[2].store.replica_view(0, 0, 0) is not None

            # the primary advertises the full holder set over HOT_SET_PULL
            assert ts[3].hot_holders(0, 0) == [0, 1, 2]

            # every advertised holder serves the block bit-identically
            for holder in (1, 2):
                assert _fetch_one(ts[3], holder, 0, 0, 0, len(p)) == p
        finally:
            _close_all(ts)

    def test_hot_block_pins_in_serve_cache(self):
        ts = _serve_cluster()
        try:
            payloads = _stage(ts[0], 0, 1, 1, seed=12)
            ts[0].store.seal(0)
            p = payloads[(0, 0)]
            assert _storm(ts[3], 0, 0, 0, 0, len(p), rounds=8) == p
            snap = ts[0].store.serve_cache.snapshot()
            assert snap["cache_entries"] >= 1  # admitted on promotion
            assert snap["cache_hits"] >= 1  # later storm fetches hit it
            assert snap["cache_used_bytes"] == len(p)
        finally:
            _close_all(ts)

    def test_readers_spread_load_across_holders(self):
        ts = _serve_cluster()
        try:
            num_reducers = 6
            payloads = _stage(ts[0], 0, 1, num_reducers, seed=13)
            ts[0].store.seal(0)
            assert ts[0].replication_wait(0, timeout=10.0)
            for r in range(num_reducers):
                _storm(ts[3], 0, 0, 0, r, len(payloads[(0, r)]), rounds=4)
            assert ts[0].replication_wait(0, timeout=10.0)  # widen settled
            assert ts[3].hot_holders(0, 0) == [0, 1, 2]

            reader = TpuShuffleReader(
                ts[3],
                executor_id=3,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_reducers,
                num_mappers=1,
                block_sizes=lambda m, r: len(payloads[(m, r)]),
                max_blocks_per_request=2,
                sender_of=lambda m: 0,
                holders_of=ts[3].hot_holders,
                fetch_retries=2,
                fetch_deadline_ms=5000,
                fetch_backoff_ms=10,
            )
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads  # spread fetches stay bit-identical
            # the rotation actually used more than one holder
            assert len(set(reader._window_targets.values())) > 1
            assert set(reader._window_targets.values()) <= {0, 1, 2}
        finally:
            _close_all(ts)

    def test_cool_down_demotes_and_drops_advertisement(self):
        ts = _serve_cluster()
        try:
            payloads = _stage(ts[0], 0, 1, 1, seed=14)
            ts[0].store.seal(0)
            p = payloads[(0, 0)]
            _storm(ts[3], 0, 0, 0, 0, len(p))
            assert ts[0].popularity.is_hot(0)
            assert ts[3].hot_holders(0, 0)

            # silence, observed through a shifted clock: the sweep demotes
            pop = ts[0].popularity
            real = time.monotonic_ns
            pop._now_ns = lambda: real() + 120 * 1_000_000_000
            ts[0].server.sweep_popularity()
            assert not pop.is_hot(0)
            assert pop.snapshot()["demotions"] >= 1
            assert ts[0]._serve_view()["advertised_hot_shuffles"] == 0

            # past the reader-side TTL the advertisement is gone...
            time.sleep(ts[3].conf.serve_holders_ttl_ms / 1e3 + 0.1)
            assert ts[3].hot_holders(0, 0) == []
            # ...but the widened replicas persist (never below the floor),
            # and the primary still serves the block bit-identically
            assert ts[2].store.replica_view(0, 0, 0) is not None
            assert _fetch_one(ts[3], 0, 0, 0, 0, len(p)) == p
        finally:
            _close_all(ts)

    def test_defaults_off_no_advertisement_no_tracking(self):
        ts = _cluster(3, replication_factor=1)
        try:
            payloads = _stage(ts[0], 0, 1, 1, seed=15)
            ts[0].store.seal(0)
            assert ts[0].replication_wait(0, timeout=10.0)
            p = payloads[(0, 0)]
            assert _storm(ts[2], 0, 0, 0, 0, len(p)) == p
            assert ts[0].popularity is None  # nothing tracked
            assert ts[0]._serve_view() == {}
            assert ts[2].hot_holders(0, 0) == []
            assert ts[2].store.replica_view(0, 0, 0) is None  # no widen push
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# chaos lane: one hot-block holder dies mid-storm
# ---------------------------------------------------------------------------


class TestHotHolderChaos:
    def test_holder_killed_mid_storm_reads_stay_bit_identical(self):
        ts = _serve_cluster(wire_timeout_ms=3000)
        try:
            num_reducers = 6
            payloads = _stage(ts[0], 0, 1, num_reducers, seed=21)
            ts[0].store.seal(0)
            assert ts[0].replication_wait(0, timeout=10.0)
            for r in range(num_reducers):
                _storm(ts[3], 0, 0, 0, r, len(payloads[(0, r)]), rounds=4)
            assert ts[0].replication_wait(0, timeout=10.0)
            assert ts[3].hot_holders(0, 0) == [0, 1, 2]

            # one widened holder dies mid-storm; spread fetches that land on
            # it fail over through the reader's retry/failover path
            faults.kill_executor(ts[2])
            reader = TpuShuffleReader(
                ts[3],
                executor_id=3,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_reducers,
                num_mappers=1,
                block_sizes=lambda m, r: len(payloads[(m, r)]),
                max_blocks_per_request=1,
                sender_of=lambda m: 0,
                holders_of=ts[3].hot_holders,
                replica_of=lambda primary: ring_neighbors(primary, [0, 1, 2, 3], 1),
                fetch_retries=3,
                fetch_deadline_ms=3000,
                fetch_backoff_ms=10,
            )
            got = {}
            for blk in reader.fetch_blocks():
                got[(blk.block_id.map_id, blk.block_id.reduce_id)] = bytes(blk.data)
                blk.release()
            assert got == payloads  # graceful degradation, bit-identical
        finally:
            _close_all(ts)


# ---------------------------------------------------------------------------
# placement helper
# ---------------------------------------------------------------------------


class TestWidenedRingNeighbors:
    def test_base_plus_extra_partition(self):
        members = [0, 1, 2, 3, 4]
        base, extra = widened_ring_neighbors(0, members, 1, 3)
        assert base == [1] and extra == [2, 3]
        assert base == ring_neighbors(0, members, 1)

    def test_hot_factor_never_narrows_below_floor(self):
        members = [0, 1, 2, 3]
        base, extra = widened_ring_neighbors(0, members, 2, 1)
        assert base == [1, 2] and extra == []

    def test_degenerate_rings(self):
        assert widened_ring_neighbors(0, [0], 1, 4) == ([], [])
        assert widened_ring_neighbors(9, [0, 1], 1, 4) == ([], [])  # non-member

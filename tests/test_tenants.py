"""Multi-tenant shuffle service tests (ROADMAP 4).

Pins the subsystem's four contracts:

* **Registry + admission control** — per-app registration with HBM byte
  quotas, charge/release accounting at region-allocation time, typed
  ``TenantQuotaExceededError`` / ``UnknownTenantError``, per-tenant
  shuffle-id namespaces (``sid_for`` / ``translate``), per-tenant CreditGates.
* **Tiered eviction** — epoch/LRU demotion of sealed rounds
  (HBM -> host -> disk) through ``HbmBlockStore.demote_round``, transparent
  restage-on-fetch, footprint-ordered restage planning (arXiv:2112.01075),
  ``eviction_stats`` telemetry — all bit-identical at every tier.
* **Serving plane** — the shared-selector Reactor multiplexes many idle
  connections over a bounded worker pool; the tenant ``app_id`` rides the
  FETCH_BLOCK_REQ extension (absent by default: golden single-tenant frames
  unchanged) and tenant errors come back as addressed size codes the client
  maps to the typed exceptions — fail-fast, never retried.
* **Quota x eviction interplay** — demotion to disk returns the tenant's
  HBM bytes, restage re-charges FIRST, so an over-quota tenant's cold fetch
  fails typed while the round stays serveable on disk.
"""

import os
import socket
import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import (
    OperationStatus,
    TenantQuotaExceededError,
    TransportError,
    UnknownTenantError,
)
from sparkucx_tpu.service.eviction import EvictionManager
from sparkucx_tpu.service.reactor import Reactor
from sparkucx_tpu.service.tenants import TENANT_SID_BASE, TenantRegistry
from sparkucx_tpu.shuffle.reader import TpuShuffleReader
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.transport.peer import (
    PeerTransport,
    pack_batch_fetch_req,
    unpack_batch_fetch_req,
    unpack_fetch_req_app_id,
)
from sparkucx_tpu.transport.pipeline import CreditGate

ALIGN = 128


def _buf(n):
    return MemoryBlock(np.zeros(n, dtype=np.uint8), size=n)


def _wait(t, req, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not req.completed() and time.monotonic() < deadline:
        t.progress()
        time.sleep(0.001)
    return req.wait(1)


# ---------------------------------------------------------------------------
# tenant registry + admission control
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_register_and_resolve(self):
        reg = TenantRegistry(default_quota_bytes=1000)
        t = reg.register("app-a")
        assert t.hbm_quota_bytes == 1000  # default applied
        assert reg.register("app-b", hbm_quota_bytes=5).hbm_quota_bytes == 5
        assert reg.resolve("app-a") is t
        assert reg.known("app-a") and not reg.known("ghost")
        assert reg.app_ids() == ["app-a", "app-b"]

    def test_unknown_tenant_typed(self):
        reg = TenantRegistry()
        with pytest.raises(UnknownTenantError) as ei:
            reg.resolve("ghost")
        assert ei.value.app_id == "ghost"
        assert isinstance(ei.value, TransportError)  # old catch-sites work

    def test_charge_release_usage(self):
        reg = TenantRegistry()
        reg.register("a", hbm_quota_bytes=100)
        reg.charge("a", 0, 60)
        assert reg.usage("a") == 60
        reg.charge("a", 0, 40)  # exactly at quota admits
        with pytest.raises(TenantQuotaExceededError) as ei:
            reg.charge("a", 7, 1)
        e = ei.value
        assert (e.app_id, e.shuffle_id) == ("a", 7)
        assert (e.requested, e.used, e.quota) == (1, 100, 100)
        reg.release("a", 30)
        assert reg.usage("a") == 70
        reg.charge("a", 0, 30)  # headroom restored

    def test_zero_quota_is_unlimited(self):
        reg = TenantRegistry()
        reg.register("a")  # default quota 0
        reg.charge("a", 0, 1 << 40)
        assert reg.usage("a") == 1 << 40

    def test_release_tolerates_unknown_and_floor(self):
        reg = TenantRegistry()
        reg.release("ghost", 10)  # cleanup path must never raise
        reg.register("a", hbm_quota_bytes=10)
        reg.release("a", 99)
        assert reg.usage("a") == 0  # floored, never negative

    def test_sid_namespace_isolated_per_tenant(self):
        reg = TenantRegistry()
        reg.register("a")
        reg.register("b")
        sa = reg.sid_for("a", 0)
        sb = reg.sid_for("b", 0)
        assert sa >= TENANT_SID_BASE and sb >= TENANT_SID_BASE
        assert sa != sb  # same local id, disjoint internal ids
        assert reg.sid_for("a", 0) == sa  # get-or-allocate is stable
        assert reg.translate("a", 0) == sa
        assert reg.translate("b", 0) == sb

    def test_translate_unknown_local_sid_passes_through(self):
        # known tenant + never-allocated local id: untranslated, so the store
        # reports its usual unknown-shuffle error (retryable block-not-found
        # on the wire), unlike the typed fail-fast tenant errors
        reg = TenantRegistry()
        reg.register("a")
        assert reg.translate("a", 42) == 42

    def test_translate_unknown_tenant_raises(self):
        reg = TenantRegistry()
        with pytest.raises(UnknownTenantError):
            reg.translate("ghost", 0)
        with pytest.raises(UnknownTenantError):
            reg.sid_for("ghost", 0)

    def test_reregister_keeps_usage_updates_budget(self):
        reg = TenantRegistry()
        reg.register("a", hbm_quota_bytes=100)
        reg.charge("a", 0, 80)
        t = reg.register("a", hbm_quota_bytes=200)  # executor restart
        assert t.used_bytes == 80 and t.hbm_quota_bytes == 200

    def test_unregister_drops_charges_and_sids(self):
        reg = TenantRegistry()
        reg.register("a")
        sid = reg.sid_for("a", 0)
        reg.charge("a", 0, 50)
        reg.unregister("a")
        reg.unregister("a")  # idempotent
        assert not reg.known("a")
        reg.register("a")
        assert reg.usage("a") == 0
        assert reg.sid_for("a", 0) != sid  # namespace was reclaimed

    def test_gate_per_tenant(self):
        reg = TenantRegistry(default_credit_bytes=1 << 20)
        reg.register("a")
        reg.register("b", credit_bytes=0)
        ga = reg.gate("a")
        assert isinstance(ga, CreditGate)
        assert reg.gate("a") is ga  # lazily created once
        assert reg.gate("b") is None  # no budget -> no gating
        with pytest.raises(UnknownTenantError):
            reg.gate("ghost")

    def test_stats_snapshot(self):
        reg = TenantRegistry()
        reg.register("a", hbm_quota_bytes=100)
        reg.sid_for("a", 0)
        reg.sid_for("a", 1)
        reg.charge("a", 0, 10)
        assert reg.stats() == {
            "a": {"used_bytes": 10, "quota_bytes": 100, "num_shuffles": 2}
        }


class TestStoreAdmission:
    def _store(self, capacity=1 << 20):
        return HbmBlockStore(
            TpuShuffleConf(
                staging_capacity_per_executor=capacity, block_alignment=ALIGN
            )
        )

    def test_write_charges_quota(self):
        s = self._store()
        reg = TenantRegistry()
        s.tenants = reg
        reg.register("a", hbm_quota_bytes=1 << 20)
        sid = reg.sid_for("a", 0)
        s.create_shuffle(sid, 1, 1, app_id="a")
        w = s.map_writer(sid, 0)
        w.write_partition(0, b"x" * 300)
        w.commit()
        assert reg.usage("a") >= 300  # padded region bytes claimed
        s.close()

    def test_over_quota_write_raises_typed_and_isolates_neighbor(self):
        s = self._store()
        reg = TenantRegistry()
        s.tenants = reg
        reg.register("small", hbm_quota_bytes=256)
        reg.register("big", hbm_quota_bytes=1 << 20)
        sid_small = reg.sid_for("small", 0)
        sid_big = reg.sid_for("big", 0)
        s.create_shuffle(sid_small, 1, 1, app_id="small")
        s.create_shuffle(sid_big, 1, 1, app_id="big")
        with pytest.raises(TenantQuotaExceededError) as ei:
            w = s.map_writer(sid_small, 0)
            w.write_partition(0, b"x" * 4096)
        assert ei.value.app_id == "small"
        # the neighbor tenant is unaffected by small's rejection
        w = s.map_writer(sid_big, 0)
        w.write_partition(0, b"y" * 4096)
        w.commit()
        assert s.read_block(sid_big, 0, 0) == b"y" * 4096
        assert reg.usage("big") >= 4096
        s.close()

    def test_create_shuffle_unknown_tenant_raises(self):
        s = self._store()
        s.tenants = TenantRegistry()
        with pytest.raises(UnknownTenantError):
            s.create_shuffle(TENANT_SID_BASE, 1, 1, app_id="ghost")
        s.close()

    def test_remove_shuffle_releases_charge(self):
        s = self._store()
        reg = TenantRegistry()
        s.tenants = reg
        reg.register("a", hbm_quota_bytes=1 << 20)
        sid = reg.sid_for("a", 0)
        s.create_shuffle(sid, 1, 1, app_id="a")
        w = s.map_writer(sid, 0)
        w.write_partition(0, b"x" * 1000)
        w.commit()
        assert reg.usage("a") > 0
        s.remove_shuffle(sid)
        assert reg.usage("a") == 0
        s.close()

    def test_untenanted_shuffle_never_charged(self):
        # tenants registry attached but app_id omitted: the single-tenant
        # path, byte-identical behavior, no admission checks
        s = self._store()
        reg = TenantRegistry()
        s.tenants = reg
        reg.register("a", hbm_quota_bytes=1)
        s.create_shuffle(0, 1, 1)
        w = s.map_writer(0, 0)
        w.write_partition(0, b"x" * 4096)
        w.commit()
        assert reg.usage("a") == 0
        s.close()


# ---------------------------------------------------------------------------
# tiered eviction: demote / restage / plan / stats
# ---------------------------------------------------------------------------


def _cpu_device():
    import jax

    return jax.devices("cpu")[0]


def _demote_to_disk(s, sid, round_idx=0):
    """Demote one round all the way down (1 tier from host, 2 from hbm)."""
    while s.round_tier(sid, round_idx) != "disk":
        assert s.demote_round(sid, round_idx) is not None
    return s.round_tier(sid, round_idx)


def _sealed_store(
    payload=b"", num_blocks=2, capacity=1 << 20, app=None, reg=None, device=None
):
    """One sealed single-round shuffle; returns (store, sid, oracle).
    With ``device`` the seal stages to a jax.Array (the 'hbm' tier even on
    the CPU backend); without, payloads stay host-resident ('host')."""
    s = HbmBlockStore(
        TpuShuffleConf(staging_capacity_per_executor=capacity, block_alignment=ALIGN),
        device=device,
    )
    if reg is not None:
        s.tenants = reg
    sid = reg.sid_for(app, 0) if app is not None else 0
    s.create_shuffle(sid, 1, num_blocks, app_id=app)
    w = s.map_writer(sid, 0)
    oracle = {}
    rng = np.random.default_rng(3)
    for r in range(num_blocks):
        data = payload or rng.integers(0, 256, size=500 + 37 * r, dtype=np.uint8).tobytes()
        oracle[r] = data
        w.write_partition(r, data)
    w.commit()
    s.seal(sid)
    return s, sid, oracle


class TestTieredEviction:
    def test_demote_descends_tiers_and_serves_each(self):
        s, sid, oracle = _sealed_store(device=_cpu_device())
        try:
            assert s.round_tier(sid, 0) == "hbm"
            assert s.demote_round(sid, 0) == "hbm->host"
            assert s.round_tier(sid, 0) == "host"
            for r, want in oracle.items():
                assert s.read_block(sid, 0, r) == want
            assert s.demote_round(sid, 0) == "host->disk"
            assert s.round_tier(sid, 0) == "disk"
            for r, want in oracle.items():
                assert s.read_block(sid, 0, r) == want  # memmap tier serves
            assert s.demote_round(sid, 0) is None  # floor reached
        finally:
            s.close()

    def test_restage_round_trip_bit_identical(self):
        s, sid, oracle = _sealed_store()
        try:
            _demote_to_disk(s, sid)
            assert s.restage_round(sid, 0)
            assert s.round_tier(sid, 0) == "host"
            for r, want in oracle.items():
                assert s.read_block(sid, 0, r) == want
            assert not s.restage_round(sid, 0)  # already resident
        finally:
            s.close()

    def test_unsealed_rounds_are_not_candidates(self):
        s = HbmBlockStore(
            TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=ALIGN)
        )
        try:
            s.create_shuffle(0, 1, 1)
            w = s.map_writer(0, 0)
            w.write_partition(0, b"live")
            w.commit()
            assert s.eviction_candidates() == []
            assert s.demote_round(0, 0) is None
        finally:
            s.close()

    def test_manager_epoch_demotes_lru_first(self):
        s, sid_cold, oracle_cold = _sealed_store(device=_cpu_device())
        try:
            s.create_shuffle(1, 1, 1)
            w = s.map_writer(1, 0)
            w.write_partition(0, b"hot" * 100)
            w.commit()
            s.seal(1)
            ev = EvictionManager(s)
            s.eviction = ev
            assert s.read_block(1, 0, 0) == b"hot" * 100  # bump hot's LRU clock
            assert ev.run_epoch(max_demotions=1) == 1
            assert s.round_tier(sid_cold, 0) == "host"  # never-fetched went first
            assert s.round_tier(1, 0) == "hbm"
            # a full sweep demotes everything one more tier each epoch
            assert ev.run_epoch() == 2
            assert s.round_tier(sid_cold, 0) == "disk"
            assert s.round_tier(1, 0) == "host"
            assert ev.eviction_stats()["demotions"] == 3
            for r, want in oracle_cold.items():
                assert s.read_block(sid_cold, 0, r) == want
        finally:
            s.close()

    def test_restage_on_fetch_from_disk(self):
        s, sid, oracle = _sealed_store()
        try:
            ev = EvictionManager(s)
            s.eviction = ev
            _demote_to_disk(s, sid)
            assert s.read_block(sid, 0, 0) == oracle[0]  # fetch restages...
            assert s.round_tier(sid, 0) == "host"  # ...the whole round to RAM
            stats = ev.eviction_stats()
            assert stats["restages"] == 1
            assert stats["restage_p99_ns"] > 0
        finally:
            s.close()

    def test_restage_plan_orders_by_footprint(self):
        s = HbmBlockStore(
            TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=ALIGN)
        )
        try:
            for sid, size in ((0, 4096), (1, 256), (2, 1024)):
                s.create_shuffle(sid, 1, 1)
                w = s.map_writer(sid, 0)
                w.write_partition(0, b"x" * size)
                w.commit()
                s.seal(sid)
            ev = EvictionManager(s)
            s.eviction = ev
            for _ in range(2):
                ev.run_epoch()  # everything to disk
            plan = ev.restage_plan([(0, 0), (1, 0), (2, 0)])
            # ascending staged footprint: peak transient staging grows slowest
            assert plan == [(1, 0), (2, 0), (0, 0)]
            assert ev.restage_all(0) == 1
            assert s.round_tier(0, 0) == "host"
        finally:
            s.close()

    def test_background_epochs_demote_without_manual_sweeps(self):
        s, sid, oracle = _sealed_store()
        ev = EvictionManager(s, epoch_ms=20)
        s.eviction = ev
        try:
            ev.start()
            deadline = time.monotonic() + 10
            while s.round_tier(sid, 0) != "disk" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert s.round_tier(sid, 0) == "disk"
            assert s.read_block(sid, 0, 0) == oracle[0]
        finally:
            ev.close()
            s.close()


class TestQuotaEvictionInterplay:
    def test_demote_to_disk_releases_quota_restage_recharges(self):
        reg = TenantRegistry()
        reg.register("a", hbm_quota_bytes=1 << 20)
        s, sid, oracle = _sealed_store(app="a", reg=reg, device=_cpu_device())
        try:
            charged = reg.usage("a")
            assert charged > 0
            assert s.demote_round(sid, 0) == "hbm->host"  # still RAM: charged
            assert reg.usage("a") == charged
            assert s.demote_round(sid, 0) == "host->disk"  # bytes returned
            assert reg.usage("a") == 0
            assert s.restage_round(sid, 0)
            assert reg.usage("a") == charged
        finally:
            s.close()

    def test_over_quota_restage_fails_typed_round_stays_on_disk(self):
        reg = TenantRegistry()
        reg.register("a", hbm_quota_bytes=1 << 20)
        s, sid, oracle = _sealed_store(app="a", reg=reg)
        ev = EvictionManager(s)
        s.eviction = ev
        try:
            _demote_to_disk(s, sid)
            reg.register("a", hbm_quota_bytes=16)  # shrink below the round
            with pytest.raises(TenantQuotaExceededError):
                s.read_block(sid, 0, 0)  # restage-on-fetch hits admission
            assert s.round_tier(sid, 0) == "disk"  # round survived, on disk
            reg.register("a", hbm_quota_bytes=1 << 20)  # headroom restored
            assert s.read_block(sid, 0, 0) == oracle[0]
            assert s.round_tier(sid, 0) == "host"
        finally:
            s.close()


# ---------------------------------------------------------------------------
# the reactor serving plane
# ---------------------------------------------------------------------------


class TestReactor:
    def _echo_reactor(self, workers=2):
        r = Reactor(workers, name="test-reactor")
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(128)
        addr = srv.getsockname()

        def serve_once(conn):
            data = conn.recv(64)
            if not data:
                return False
            conn.sendall(data.upper())
            return True

        def on_accept(conn):
            conn.setblocking(True)
            r.add_connection(conn, serve_once)

        r.add_listener(srv, on_accept)
        return r, addr

    def test_many_connections_one_loop(self):
        r, addr = self._echo_reactor(workers=4)
        try:
            socks = [socket.create_connection(addr, timeout=5) for _ in range(32)]
            for i, c in enumerate(socks):  # every held connection serves...
                c.sendall(b"m%03d" % i)
            for i, c in enumerate(socks):
                assert c.recv(64) == b"M%03d" % i
            for i, c in enumerate(socks):  # ...and re-arms for the next frame
                c.sendall(b"x%03d" % i)
                assert c.recv(64) == b"X%03d" % i
            deadline = time.monotonic() + 5
            while r.num_connections < 32 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert r.num_connections == 32
            for c in socks:
                c.close()
            deadline = time.monotonic() + 5
            while r.num_connections > 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert r.num_connections == 0  # EOF drops, on the loop's clock
        finally:
            r.close()

    def test_on_close_runs_once_on_drop(self):
        r = Reactor(1, name="test-reactor-drop")
        dropped = []
        a, b = socket.socketpair()
        try:
            r.add_connection(b, lambda c: False, on_close=dropped.append)
            a.sendall(b"wake")
            deadline = time.monotonic() + 5
            while not dropped and time.monotonic() < deadline:
                time.sleep(0.01)
            assert dropped == [b]
        finally:
            a.close()
            r.close()

    def test_close_is_idempotent_and_rejects_new_work(self):
        r, addr = self._echo_reactor()
        r.close()
        r.close()
        with pytest.raises(RuntimeError, match="closed"):
            r.add_connection(socket.socket(), lambda c: False)


# ---------------------------------------------------------------------------
# wire: the self-describing tenant extension + typed addressed errors
# ---------------------------------------------------------------------------


class TestWireExtension:
    def test_default_frames_byte_identical(self):
        # golden pin: no app_id -> EXACTLY the pre-tenant request bytes
        bids = [ShuffleBlockId(1, 2, 3), ShuffleBlockId(4, 5, 6)]
        import struct

        want = struct.pack("<Q", 9) + struct.pack("<I", 2)
        for b in bids:
            want += struct.pack("<iii", b.shuffle_id, b.map_id, b.reduce_id)
        golden = pack_batch_fetch_req(9, bids)
        assert golden == want
        assert unpack_fetch_req_app_id(golden, 2) is None

    def test_extension_roundtrip_invisible_to_triple_parser(self):
        bids = [ShuffleBlockId(0, 1, 2)]
        hdr = pack_batch_fetch_req(5, bids, app_id="app-x")
        assert unpack_fetch_req_app_id(hdr, 1) == "app-x"
        tag, parsed = unpack_batch_fetch_req(hdr)
        assert tag == 5 and parsed == bids  # ext residue ignored
        assert hdr[: len(pack_batch_fetch_req(5, bids))] == pack_batch_fetch_req(5, bids)

    def test_malformed_extension_reads_as_absent(self):
        bids = [ShuffleBlockId(0, 1, 2)]
        base = pack_batch_fetch_req(5, bids)
        import struct

        assert unpack_fetch_req_app_id(base + b"\x01", 1) is None  # truncated len
        assert unpack_fetch_req_app_id(
            base + struct.pack("<I", 99) + b"ab", 1
        ) is None  # length overruns
        assert unpack_fetch_req_app_id(
            base + struct.pack("<I", 0), 1
        ) is None  # empty app_id


def _tenant_server(apps, payload_of, num_blocks=2, workers=2):
    """Tenants-enabled server with one sealed shuffle per app; returns
    (server transport, registry, addr, {app: {reduce: payload}})."""
    conf = TpuShuffleConf(
        tenants_enabled=True,
        server_workers=workers,
        staging_capacity_per_executor=1 << 20,
        wire_timeout_ms=5000,
    )
    reg = TenantRegistry()
    srv = PeerTransport(conf, executor_id=1)
    srv.store.tenants = reg
    addr = srv.init()
    oracle = {}
    for app in apps:
        reg.register(app, hbm_quota_bytes=1 << 20)
        sid = reg.sid_for(app, 0)
        srv.store.create_shuffle(sid, 1, num_blocks, app_id=app)
        w = srv.store.map_writer(sid, 0)
        oracle[app] = {}
        for r in range(num_blocks):
            data = payload_of(app, r)
            oracle[app][r] = data
            w.write_partition(r, data)
        w.commit()
        srv.store.seal(sid)
    return srv, reg, addr, oracle


def _tenant_client(addr, app_id, executor_id=7):
    conf = TpuShuffleConf(
        tenants_enabled=True,
        staging_capacity_per_executor=1 << 20,
        wire_timeout_ms=5000,
    )
    c = PeerTransport(conf, executor_id=executor_id)
    c.app_id = app_id
    c.init()
    c.add_executor(1, addr)
    return c


class TestWireMultiTenant:
    def test_eight_apps_fetch_their_own_namespaces(self):
        apps = [f"app-{i}" for i in range(8)]
        payload_of = lambda app, r: (app.encode() + b":%d:" % r) * 40
        srv, reg, addr, oracle = _tenant_server(apps, payload_of)
        clients = []
        try:
            clients = [
                _tenant_client(addr, app, executor_id=10 + i)
                for i, app in enumerate(apps)
            ]
            reqs = []
            for c in clients:
                for r in (0, 1):
                    buf = _buf(len(oracle[c.app_id][r]))
                    # tenant-LOCAL shuffle id 0: every app names the same id,
                    # the server's registry translation keeps them disjoint
                    req = c.fetch_block(1, 0, 0, r, buf)
                    reqs.append((c, r, buf, req))
            for c, r, buf, req in reqs:
                res = _wait(c, req)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                assert buf.host_view()[: buf.size].tobytes() == oracle[c.app_id][r]
        finally:
            for c in clients:
                c.close()
            srv.close()

    def test_three_apps_stream_concurrently_each_its_own_bytes(self):
        """Three applications drain their own four 64 KiB blocks at the same
        time, each through its own client and a reader on the tenant-LOCAL
        shuffle id 0: every one gets its own bytes, whole and in order."""
        apps = [f"app-{i}" for i in range(3)]
        size = 64 << 10
        payload_of = lambda app, r: (app.encode() + b":%d:" % r) * (size // 8)
        srv, reg, addr, oracle = _tenant_server(apps, payload_of, num_blocks=4, workers=3)
        clients = []
        try:
            clients = [
                _tenant_client(addr, app, executor_id=20 + i) for i, app in enumerate(apps)
            ]
            got = {}

            def drain(c):
                reader = TpuShuffleReader(
                    c,
                    executor_id=c.executor_id,
                    shuffle_id=0,
                    start_partition=0,
                    end_partition=4,
                    num_mappers=1,
                    block_sizes=lambda m, r: size,
                    max_blocks_per_request=1,
                    sender_of=lambda m: 1,
                    fetch_deadline_ms=10_000,
                )
                blocks = {}
                for blk in reader.fetch_blocks():
                    blocks[blk.block_id.reduce_id] = bytes(blk.data)
                    blk.release()
                got[c.app_id] = blocks

            threads = [threading.Thread(target=drain, args=(c,)) for c in clients]
            for th in threads:
                th.start()
            for th in threads:
                th.join(60)
            assert got == oracle
            # serving charged no tenant: each still holds what it staged
            assert {app: u["used_bytes"] for app, u in reg.stats().items()} == {
                app: 4 * size for app in apps
            }
        finally:
            for c in clients:
                c.close()
            srv.close()

    def test_unknown_tenant_fails_typed_over_wire(self):
        srv, reg, addr, oracle = _tenant_server(["app-a"], lambda a, r: b"x" * 100)
        ghost = None
        try:
            ghost = _tenant_client(addr, "ghost")
            buf = _buf(100)
            res = _wait(ghost, ghost.fetch_block(1, 0, 0, 0, buf))
            assert res.status == OperationStatus.FAILURE
            assert isinstance(res.error, UnknownTenantError)
            assert res.error.app_id == "ghost"
            assert "rejected the fetch" in str(res.error)
        finally:
            if ghost is not None:
                ghost.close()
            srv.close()

    def test_untenanted_client_on_tenant_server_compat(self):
        # app_id=None -> no wire extension -> untranslated sid: the golden
        # single-tenant path keeps working against a tenants-enabled server
        srv, reg, addr, _ = _tenant_server(["app-a"], lambda a, r: b"x" * 100)
        plain = None
        try:
            srv.store.create_shuffle(5, 1, 1)  # untenanted global sid
            w = srv.store.map_writer(5, 0)
            w.write_partition(0, b"single-tenant" * 10)
            w.commit()
            plain = _tenant_client(addr, None)
            buf = _buf(130)
            res = _wait(plain, plain.fetch_block(1, 5, 0, 0, buf))
            assert res.status == OperationStatus.SUCCESS, str(res.error)
            assert buf.host_view()[: buf.size].tobytes() == b"single-tenant" * 10
            # and a tenant-namespaced sid is invisible without the extension
            buf2 = _buf(100)
            res2 = _wait(plain, plain.fetch_block(1, 0, 0, 0, buf2))
            assert res2.status == OperationStatus.FAILURE
            assert not isinstance(
                res2.error, (UnknownTenantError, TenantQuotaExceededError)
            )  # plain block-not-found, the retryable kind
        finally:
            if plain is not None:
                plain.close()
            srv.close()

    def test_quota_exceeded_restage_fails_typed_then_recovers(self):
        srv, reg, addr, oracle = _tenant_server(
            ["app-a"], lambda a, r: b"Q" * 600, num_blocks=2
        )
        client = None
        try:
            ev = EvictionManager(srv.store)
            srv.store.eviction = ev
            sid = reg.translate("app-a", 0)
            _demote_to_disk(srv.store, sid)
            assert reg.usage("app-a") == 0
            reg.register("app-a", hbm_quota_bytes=16)  # no restage headroom
            client = _tenant_client(addr, "app-a")
            buf = _buf(600)
            res = _wait(client, client.fetch_block(1, 0, 0, 0, buf))
            assert res.status == OperationStatus.FAILURE
            assert isinstance(res.error, TenantQuotaExceededError)
            assert res.error.app_id == "app-a"
            # headroom restored: restage-on-fetch serves bit-identical bytes
            reg.register("app-a", hbm_quota_bytes=1 << 20)
            buf2 = _buf(600)
            res2 = _wait(client, client.fetch_block(1, 0, 0, 0, buf2))
            assert res2.status == OperationStatus.SUCCESS, str(res2.error)
            assert buf2.host_view()[: buf2.size].tobytes() == oracle["app-a"][0]
            assert ev.eviction_stats()["restages"] >= 1
        finally:
            if client is not None:
                client.close()
            srv.close()

    def test_reader_fails_fast_on_tenant_errors_no_retries(self):
        # satellite (b): typed tenant errors abort the whole fetch loop
        # immediately — retrying or failing over cannot help, every replica
        # enforces the same registry
        srv, reg, addr, oracle = _tenant_server(["app-a"], lambda a, r: b"x" * 100)
        ghost = None
        try:
            ghost = _tenant_client(addr, "ghost")
            reader = TpuShuffleReader(
                ghost,
                executor_id=ghost.executor_id,
                shuffle_id=0,
                start_partition=0,
                end_partition=2,
                num_mappers=1,
                block_sizes=lambda m, r: 100,
                max_blocks_per_request=1,
                sender_of=lambda m: 1,
                replica_of=lambda p: [1],  # a "replica" that would also reject
                fetch_retries=5,
                fetch_deadline_ms=10_000,
                fetch_backoff_ms=200,
            )
            t0 = time.monotonic()
            with pytest.raises(UnknownTenantError):
                list(reader.fetch_blocks())
            assert time.monotonic() - t0 < 5  # fail-fast, not retried to deadline
            assert reader.metrics.failovers == 0
        finally:
            if ghost is not None:
                ghost.close()
            srv.close()

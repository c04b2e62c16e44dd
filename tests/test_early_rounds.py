"""A multi-round job's completed RAM rounds go to their store's device when
they become final and the exchange takes them from there (``hbm_store
._EarlyRounds``, PR 57): the received bytes and the size matrices are those
of the same shuffle with every round put by the exchange, the host round
stays the shuffle's backing store, and what the exchange did not take is let
go and counted — whatever the plan, the receive mode, the interleaving of
writers and the way the exchange ends.

The CPU mesh at a few KiB a round: bytes and counts, no rate."""

import gc
import os
import sys
import threading
import types

import jax
import numpy as np
import pytest

import sparkucx_tpu.store.writer as store_writer
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import ResourceExhaustedError, TransportError
from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.testing import faults
from sparkucx_tpu.transport.tpu import TpuShuffleCluster
from sparkucx_tpu.utils.trace import TRACER

ALIGN = 128
STAGING = 1 << 14  # a round: 128 rows of 128 B
EARLY = ("early_round_puts", "early_round_bytes", "early_rounds_dropped")


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.reset()


@pytest.fixture
def recording():
    before = TRACER.recording
    TRACER.recording = True
    TRACER.clear()
    yield
    TRACER.recording = before
    TRACER.clear()


def round_puts():
    return [e for e in TRACER.events if e["name"] == "store.round_put"]


def cluster_of(n, staging=STAGING, **conf):
    return TpuShuffleCluster(
        TpuShuffleConf(num_executors=n, block_alignment=ALIGN, staging_capacity_per_executor=staging, **conf),
        num_executors=n,
    )


def blocks_of(seed, mappers=6, reducers=8, lo=500, hi=3000):
    rng = np.random.default_rng(seed)
    return {
        (m, r): rng.integers(0, 256, int(rng.integers(lo, hi)), dtype=np.uint8).tobytes()
        for m in range(mappers) for r in range(reducers)
    }


def write_job(cluster, sid, blocks):
    """Every map task of ``blocks`` written and committed, one after another."""
    mappers = 1 + max(m for m, _ in blocks)
    reducers = 1 + max(r for _, r in blocks)
    meta = cluster.create_shuffle(sid, mappers, reducers)
    for m in range(mappers):
        t = cluster.transport(meta.map_owner[m])
        writer = t.store.map_writer(sid, m)
        for r in range(reducers):
            writer.write_partition(r, blocks[(m, r)])
        t.commit_block(writer.commit().pack())
    return meta


def stats(cluster, *keys):
    rows = [t.store.write_stats() for t in cluster.transports]
    return [tuple(row[k] for k in keys) for row in rows]


def rise(after, before):
    return [tuple(a - b for a, b in zip(x, y)) for x, y in zip(after, before)]


def received(cluster, sid):
    """What the exchange left: every round's shards cut to the rows received,
    the size matrices, and the shards kept on the device cut the same."""
    meta = cluster.meta(sid)
    row = cluster.row_bytes
    host = dev = None
    if meta.recv_shards is not None:
        host = [
            [bytes(np.asarray(shard).reshape(-1)[: int(sizes[c].sum()) * row]) for c, shard in enumerate(shards)]
            for shards, sizes in zip(meta.recv_shards, meta.recv_sizes)
        ]
    if meta.recv_device is not None:
        dev = [
            [np.asarray(shard)[: int(sizes[c].sum())].tobytes() for c, shard in enumerate(shards)]
            for shards, sizes in zip(meta.recv_device, meta.recv_sizes)
        ]
    return host, [sizes.tolist() for sizes in meta.recv_sizes], dev


def every_block_reads_back(cluster, meta, sid, blocks):
    for (m, r), data in blocks.items():
        view, length = cluster.locate_received_block(meta.owner_of_reduce(r), sid, m, r)
        assert bytes(view[:length]) == data


def by_the_exchange(n, blocks, **conf):
    """The same shuffle with every round put by the exchange: a store's first
    job writes into fresh pages, which are never put early."""
    cluster = cluster_of(n, **conf)
    before = stats(cluster, *EARLY)
    write_job(cluster, 0, blocks)
    cluster.run_exchange(0)
    assert stats(cluster, *EARLY) == before
    assert "early_bytes" not in cluster.stats.counters("exchange.assemble")
    return received(cluster, 0)


def live_device_bytes():
    gc.collect()
    return sum(int(a.nbytes) for a in jax.live_arrays())


@pytest.mark.parametrize("keep_device", [False, True], ids=["host-recv", "keep-device"])
@pytest.mark.parametrize("mode", ["array", "memmap"])
@pytest.mark.parametrize("n", [1, 4])
def test_rounds_put_early_are_exchanged_bit_identically(n, mode, keep_device, recording):
    """The second job of a store finds its round buffers on the free list:
    every round but the live one is on the device before the seal, the
    exchange puts only the last, and what every executor received is what it
    receives when the exchange puts every round."""
    conf = dict(host_recv_mode=mode, keep_device_recv=keep_device)
    blocks = blocks_of(n)
    expect = by_the_exchange(n, blocks, **conf)
    cluster = cluster_of(n, **conf)
    write_job(cluster, 0, blocks)  # the job before: its rounds go back to the free list
    assert round_puts() == []
    cluster.run_exchange(0)
    cluster.remove_shuffle(0)
    before = stats(cluster, *EARLY)
    meta = write_job(cluster, 1, blocks)
    rounds = [t.store.num_rounds(1) for t in cluster.transports]
    assert max(rounds) > 2
    round_bytes = STAGING // n // ALIGN * ALIGN * n
    assert rise(stats(cluster, *EARLY), before) == [(r - 1, (r - 1) * round_bytes, 0) for r in rounds]
    assert sorted((e["args"]["executor"], e["args"]["round"]) for e in round_puts()) == [
        (eid, rnd) for eid, r in enumerate(rounds) for rnd in range(r - 1)]
    assert all(e["args"]["bytes"] == round_bytes and e["args"]["shuffle_id"] == 1 for e in round_puts())
    # the host rounds are what ``seal`` hands on: the copies ride beside them
    cluster.run_exchange(1)
    for t in cluster.transports:
        assert all(isinstance(p, np.ndarray) for p in t.store._state(1).sealed_payload)
        assert t.store._state(1).early_rounds.copies == {} and t.store._early_round_bytes == 0
    assert received(cluster, 1) == expect
    every_block_reads_back(cluster, meta, 1, blocks)
    assert rise(stats(cluster, *EARLY), before) == [(r - 1, (r - 1) * round_bytes, 0) for r in rounds]
    counters = cluster.stats.counters("exchange.assemble")
    assert counters["early_bytes"] == sum(r - 1 for r in rounds) * round_bytes
    # after the exchange: the pull fallback and the replica push read the host round
    for (m, r), data in blocks.items():
        assert cluster.transport(meta.map_owner[m]).store.read_block(1, m, r) == data
    for t in cluster.transports:
        for _rnd, entries, body in t.store.replica_source(1):
            assert bytes(body) == b"".join(blocks[(m, r)] for m, r, _ in entries)
    cluster.remove_shuffle(1)
    assert stats(cluster, "pool_dropped_busy") == [(0,)] * n


@pytest.mark.parametrize("conf", [dict(slot_quota_rows=16), dict(staging=3 * (1 << 12))], ids=["quota-chunked", "odd-slot"])
@pytest.mark.parametrize("n", [1, 4])
def test_a_plan_whose_window_is_not_the_staging_slot_puts_the_host_rounds(n, conf):
    """A quota-chunked plan and a staging slot that is no power of two cut
    their windows out of the host rounds as ever: the early copies are let
    go before the first submit, counted, and nothing is cut on the device."""
    blocks = blocks_of(20 + n, mappers=8)
    expect = by_the_exchange(n, blocks, **conf)
    cluster = cluster_of(n, **conf)
    write_job(cluster, 0, blocks)
    cluster.run_exchange(0)
    cluster.remove_shuffle(0)
    before = stats(cluster, *EARLY, "released_device_bytes")
    meta = write_job(cluster, 1, blocks)
    puts = rise(stats(cluster, *EARLY, "released_device_bytes"), before)
    assert sum(p[0] for p in puts) > 0
    cluster.run_exchange(1)
    after = rise(stats(cluster, *EARLY, "released_device_bytes"), before)
    assert after == [(p[0], p[1], p[0], p[1]) for p in puts]
    assert "early_bytes" not in cluster.stats.counters("exchange.assemble")
    assert received(cluster, 1) == expect
    every_block_reads_back(cluster, meta, 1, blocks)


def store_of(capacity=STAGING, rounds=4, device=True, **conf):
    """A store of a long-lived executor whose free list holds ``rounds``
    round buffers: the job before rolled as many."""
    store = HbmBlockStore(
        TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=capacity, **conf),
        device=jax.devices()[0] if device else None,
    )
    store.create_shuffle(99, 1, rounds)
    writer = store.map_writer(99, 0)
    for r in range(rounds):
        writer.write_partition(r, b"\x01" * (capacity - ALIGN))
    writer.commit()
    assert store.num_rounds(99) == rounds and store.write_stats()["early_round_puts"] == 0
    store.remove_shuffle(99)
    return store


def early(store):
    row = store.write_stats()
    return tuple(row[k] for k in EARLY)


def copies(store, sid):
    """The early copies of ``sid`` as ``{round: bytes on the device}``."""
    rounds = store._state(sid).early_rounds
    return {} if rounds is None else {r: np.asarray(a).tobytes() for r, a in rounds.copies.items()}


def test_a_round_received_in_place_across_its_rollover_is_put_after_the_receive_ends():
    """A partition reserved in round 0 is still being received when another
    writer's block rolls the round: round 0 is not put by that block, nor by
    any other, until the receive has ended — and then holds its bytes."""
    store = store_of()
    store.create_shuffle(0, 3, 4)
    slow = store.map_writer(0, 0)
    slow.open_partition(0)
    view = slow.reserve(1000)
    other = store.map_writer(0, 1)
    other.write_partition(0, b"\x02" * (STAGING - 2048))  # round 0 beside the reservation
    other.write_partition(1, b"\x03" * 4096)  # rolls round 0 with the receive in flight
    assert store.num_rounds(0) == 2 and store._state(0).early_rounds.open == {0}
    other.write_partition(2, b"\x04" * 100)
    assert early(store) == (0, 0, 0)
    view[:] = b"\x05" * 1000
    slow.end_receive(1000, True)
    assert store._state(0).early_rounds.ready and early(store) == (0, 0, 0)
    slow.close_partition()  # the record that finds the round ready puts it
    assert early(store) == (1, STAGING, 0)
    host = store._state(0).prev_rounds[0][0]
    assert copies(store, 0) == {0: host.tobytes()} and bytes(host[:1000]) == b"\x05" * 1000
    slow.commit(), other.commit()
    sealed = store.seal(0)
    assert [type(p) for p, _ in sealed] == [np.ndarray, np.ndarray]
    assert np.asarray(store.take_early_round(0, 0)).tobytes() == sealed[0][0].tobytes()
    assert store.take_early_round(0, 0) is None and store.take_early_round(0, 1) is None
    store.close()


def test_a_lost_receive_settles_its_round_too():
    """A body cut short leaves a hole; its round is final all the same and
    the next record puts it."""
    store = store_of()
    store.create_shuffle(0, 2, 4)
    slow = store.map_writer(0, 0)
    slow.open_partition(0)
    slow.reserve(1000)
    other = store.map_writer(0, 1)
    other.write_partition(0, b"\x02" * (STAGING - 2048))
    other.write_partition(1, b"\x03" * 4096)
    slow.end_receive(400, False)
    assert early(store) == (0, 0, 0)
    other.write_partition(2, b"\x04" * 100)
    assert early(store) == (1, STAGING, 0)
    store.close()


class HeldCopies:
    """``store_writer._copy_chunks`` with a gate a thread (the pattern of
    ``tests/store/test_unlocked_copy.py``)."""

    def __init__(self, monkeypatch):
        self.real = store_writer._copy_chunks
        self.arrived = threading.Event()
        self.gates = {}
        monkeypatch.setattr(store_writer, "_copy_chunks", self)

    def __call__(self, staging, start, chunks):
        gate = self.gates.get(threading.current_thread().name)
        if gate is not None:
            self.arrived.set()
            assert gate.wait(30)
        self.real(staging, start, chunks)


def test_a_buffered_copy_still_landing_at_the_rollover_holds_its_round(monkeypatch):
    """Four writer threads, one of them held inside its copy into round 0
    while the others roll the round and two more: round 0 is put only once
    that copy has ended, by the held writer's own record; the rounds rolled
    meanwhile are put behind the others."""
    held = HeldCopies(monkeypatch)
    held.gates["slot-0"] = threading.Event()
    store = store_of(rounds=6)
    store.create_shuffle(0, 4, 8)
    errors = []

    def task(m, sizes):
        try:
            writer = store.map_writer(0, m)
            for r, size in enumerate(sizes):
                writer.write_partition(r, bytes([m + 1]) * size)
            writer.commit()
        except BaseException as e:  # the thread's boundary
            errors.append(e)

    opened = [store.map_writer(0, 3)]  # a fourth slot's task stays open: copies leave the lock
    first = threading.Thread(target=task, args=(0, [3000]), name="slot-0")
    first.start()
    assert held.arrived.wait(30)
    others = [threading.Thread(target=task, args=(m, [6000] * 6), name=f"slot-{m}") for m in (1, 2)]
    for t in others:
        t.start()
    for t in others:
        t.join(30)
    assert not errors and not any(t.is_alive() for t in others)
    state = store._state(0)
    assert store.num_rounds(0) >= 4 and state.early_rounds.open == {0} and state.inflight == {0: 1}
    put_so_far = early(store)[0]
    assert put_so_far == store.num_rounds(0) - 2 and 0 not in copies(store, 0)
    held.gates["slot-0"].set()
    first.join(30)
    assert not first.is_alive() and not errors
    assert early(store) == (put_so_far + 1, (put_so_far + 1) * STAGING, 0)
    opened[0].commit()
    assert copies(store, 0) == {r: state.prev_rounds[r][0].tobytes() for r in range(store.num_rounds(0) - 1)}
    store.close()


def test_many_writers_keep_one_owner_and_put_every_round_once(monkeypatch):
    """More writer threads than cores, the interpreter switching often: never
    two threads inside a round's put at once, every completed round put once,
    each copy the host round byte for byte."""
    inside, overlap, calls = [], [], []
    real = HbmBlockStore._put_round

    def watched(self, payload, behind, used, alignment):
        inside.append(threading.get_ident())
        if len(inside) > 1:
            overlap.append(tuple(inside))
        try:
            calls.append(1)
            return real(self, payload, behind, used, alignment)
        finally:
            inside.pop()

    monkeypatch.setattr(HbmBlockStore, "_put_round", watched)
    store = store_of(rounds=24)
    mappers, reducers = 12, 6
    store.create_shuffle(0, mappers, reducers)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    errors = []
    try:
        def work(m):
            try:
                rng = np.random.default_rng(m)
                writer = store.map_writer(0, m)
                for r in range(reducers):
                    writer.write_partition(r, rng.integers(0, 256, int(rng.integers(1000, 4000)), dtype=np.uint8).tobytes())
                writer.commit()
            except BaseException as e:  # the thread's boundary
                errors.append(e)

        threads = [threading.Thread(target=work, args=(m,)) for m in range(mappers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(interval)
    state = store._state(0)
    rounds = store.num_rounds(0)
    assert rounds > 6 and not overlap
    store.seal(0)  # waits an owner out; nothing is put after it
    assert len(calls) == early(store)[0] <= rounds - 1
    assert early(store)[0] >= rounds - 2  # the last rollover's round may have found no record after it
    assert copies(store, 0) == {r: state.prev_rounds[r][0].tobytes() for r in copies(store, 0)}
    assert store._early_round_bytes == early(store)[0] * STAGING
    store.close()
    assert store._early_round_bytes == 0


def roll(store, sid, rounds, reducers=8):
    """One writer rolls ``rounds`` rounds of shuffle ``sid``."""
    store.create_shuffle(sid, 1, reducers)
    writer = store.map_writer(sid, 0)
    for r in range(rounds):
        writer.write_partition(r, bytes([r + 1]) * (STAGING - ALIGN))
    return writer


@pytest.mark.parametrize("what", ["disk-arm", "shm", "device-written", "no-device", "fresh-pages"])
def test_what_is_not_a_held_ram_round_of_a_store_with_a_device_is_not_put_early(what):
    conf = {"disk-arm": dict(max_host_pool_bytes=0), "shm": dict(use_shm_staging=True, shm_namespace=f"early_rounds_{os.getpid()}")}.get(what, {})
    if what == "fresh-pages":
        store = HbmBlockStore(
            TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=STAGING), device=jax.devices()[0])
    else:
        store = store_of(rounds=1 if what == "shm" else 4, device=what != "no-device", **conf)
    try:
        if what == "shm":
            store.create_shuffle(0, 1, 2)
            writer = store.map_writer(0, 0)
            writer.write_partition(0, b"\x01" * (STAGING - ALIGN))
            with pytest.raises(TransportError, match="shm staging"):
                writer.write_partition(1, b"\x01" * 4096)
        elif what == "device-written":
            store.create_shuffle(0, 1, 4)
            writer = store.map_writer(0, 0)
            rows = STAGING // ALIGN - 1
            for r in range(3):
                writer.write_partition_device(r, jax.numpy.full((rows, ALIGN // 4), r + 1, dtype=jax.numpy.int32))
            writer.commit()
            assert store.num_rounds(0) == 3
        else:
            roll(store, 0, 3).commit()
            assert store.num_rounds(0) == 3
        state = store._state(0)
        assert early(store) == (0, 0, 0) and (state.early_rounds is None or not state.early_rounds.copies)
        if what != "shm":
            store.seal(0)
            assert store.take_early_round(0, 0) is None
    finally:
        store.close()


@pytest.mark.parametrize("error", [jax.errors.JaxRuntimeError, TypeError], ids=["the-runtimes", "this-codes"])
def test_a_put_that_raises_costs_the_early_copies_and_not_the_write(error):
    """A put the runtime refuses (fault point ``store.round_put``) is logged
    and the write goes on; any other error goes up through the block that ran
    into it, recorded before.  The copies made so far are let go and counted,
    no later round is put, and the exchange's rounds are the host's."""
    store = store_of(rounds=6)
    try:
        faults.arm("store.round_put", faults.fail(error("RESOURCE_EXHAUSTED: injected")), match={"round": 2})
        store.create_shuffle(0, 1, 8)
        writer = store.map_writer(0, 0)
        raised = 0
        for r in range(6):
            try:
                writer.write_partition(r, bytes([r + 1]) * (STAGING - ALIGN))
            except TypeError:
                raised += 1
        writer.commit()
        assert raised == (error is TypeError) and faults.fired["store.round_put"] == 1
        assert store.num_rounds(0) == 6
        assert early(store) == (2, 2 * STAGING, 2) and store._early_round_bytes == 0
        assert store.write_stats()["released_device_bytes"] == 2 * STAGING
        sealed = store.seal(0)
        assert [bytes(p.reshape(-1).view(np.uint8)[:1]) for p, _ in sealed] == [bytes([r + 1]) for r in range(6)]
        assert all(store.take_early_round(0, r) is None for r in range(6))
    finally:
        store.close()


def test_the_watermark_gate_refuses_an_early_put_and_not_the_write():
    """Site ``round_put`` of ``store.mem_pressure``: no early put, the rounds
    stay queued, and once the pressure is gone the next record puts them."""
    store = store_of(rounds=6)
    try:
        with faults.injected_faults():
            faults.arm(
                "store.mem_pressure", faults.fail(ResourceExhaustedError(detail="injected pressure")),
                match={"site": "round_put"},
            )
            writer = roll(store, 0, 4)
            assert faults.fired["store.mem_pressure"] > 0 and early(store) == (0, 0, 0)
        writer.write_partition(4, b"\x07" * 100)
        assert early(store) == (3, 3 * STAGING, 0)
    finally:
        store.close()


def test_the_early_copies_of_a_store_stay_under_its_ram_round_budget():
    """With the disk tier off a shuffle's RAM rounds have no bound of their
    own: the early copies keep to ``max_host_pool_bytes`` all the same."""
    store = store_of(rounds=6, spill_to_disk=False, max_host_pool_bytes=6 * STAGING)
    store._ram_budget = 2 * STAGING  # what a small host's MemAvailable would leave
    try:
        roll(store, 0, 6).commit()
        assert early(store) == (2, 2 * STAGING, 0) and store._early_round_bytes == 2 * STAGING
        assert sorted(store._state(0).early_rounds.copies) == [0, 1]
    finally:
        store.close()


def test_a_device_with_no_room_leaves_the_round_to_the_exchange():
    """``memory_stats`` where the runtime gives one: a round that would not
    fit beside what is in use is not put; the CPU backend gives none: put."""
    store = store_of(rounds=4)
    assert store.device.memory_stats() is None and store._device_has_room(1 << 40)
    real = store.device
    try:
        store.device = types.SimpleNamespace(
            memory_stats=lambda: {"bytes_limit": 10 * STAGING, "bytes_in_use": 9 * STAGING + 1})
        assert not store._device_has_room(STAGING) and store._device_has_room(STAGING - 1)
        roll(store, 0, 3)
        assert early(store) == (0, 0, 0) and store._early_round_bytes == 0
    finally:
        store.device = real
        store.close()


@pytest.mark.parametrize("how", ["remove-before-the-exchange", "close", "release"])
def test_copies_nobody_took_are_released_and_counted(how):
    store = store_of(rounds=4)
    assert store.write_stats()["pool_held_bytes"] == 4 * STAGING
    base = live_device_bytes()
    roll(store, 0, 4).commit()
    assert early(store) == (3, 3 * STAGING, 0) and live_device_bytes() == base + 3 * STAGING
    released = store.write_stats()["released_device_bytes"]
    if how == "release":
        store.seal(0)
        store.release_early_rounds(0)
    else:
        store.remove_shuffle(0) if how.startswith("remove") else store.close()
    assert early(store) == (3, 3 * STAGING, 3) and store._early_round_bytes == 0
    assert store.write_stats()["released_device_bytes"] == released + 3 * STAGING
    assert live_device_bytes() == base
    if how.startswith("remove"):  # the round buffers went back to the free list: nothing referred to them
        assert store.write_stats()["pool_dropped_busy"] == 0
        assert store.write_stats()["pool_held_bytes"] == 4 * STAGING
    store.close()


def test_an_aborted_exchange_releases_what_it_did_not_take():
    """A submit that raises at round 2: rounds 0 and 1 went to the exchange,
    the copies of the rounds never submitted are let go with the error."""
    blocks = blocks_of(7)
    cluster = cluster_of(1)
    write_job(cluster, 0, blocks)
    cluster.run_exchange(0)
    cluster.remove_shuffle(0)
    write_job(cluster, 1, blocks)
    store = cluster.transport(0).store
    puts = early(store)[0]
    assert puts == store.num_rounds(1) - 1 > 3
    released = store.write_stats()["released_device_bytes"]
    faults.arm("exchange.submit", faults.fail(TransportError("injected")), match={"round": 2})
    with pytest.raises(TransportError, match="injected"):
        cluster.run_exchange(1)
    assert early(store) == (puts, puts * STAGING, puts - 2) and store._early_round_bytes == 0
    assert store.write_stats()["released_device_bytes"] == released + (puts - 2) * STAGING
    for (m, r), data in blocks.items():  # the host rounds are what they were
        assert store.read_block(1, m, r) == data
    cluster.remove_shuffle(1)


@pytest.mark.parametrize("mode", ["array", "memmap"])
def test_a_degraded_rerun_reads_host_rounds_and_releases_every_early_copy(mode):
    """An executor dies at the submit of round 1: the copies no submit took
    are let go on every store and the re-run — host rounds, restaged rounds —
    delivers the bytes of the undisturbed job."""
    conf = dict(elastic=True, replication_factor=1, host_recv_mode=mode, staging=4 * 4096)
    rng = np.random.default_rng(5)
    blocks = {(m, r): rng.integers(0, 256, 2000, dtype=np.uint8).tobytes() for m in range(8) for r in range(8)}
    cluster = cluster_of(4, **conf)
    write_job(cluster, 0, blocks)
    cluster.run_exchange(0)
    cluster.remove_shuffle(0)
    meta = write_job(cluster, 1, blocks)
    puts = [s[0] for s in stats(cluster, "early_round_puts")]
    assert all(p >= 1 for p in puts)
    survivors = [t.store for t in cluster.transports if t.executor_id != 2]
    faults.arm(
        "exchange.submit", lambda **ctx: faults.kill_executor(cluster.transport(2)), times=1, match={"round": 1})
    cluster.run_exchange(1)
    assert cluster.elastic_stats["recoveries"] == 1
    for store in survivors:
        row = store.write_stats()
        assert row["early_rounds_dropped"] == row["early_round_puts"] - 1  # round 0 went to the full mesh
        assert store._early_round_bytes == 0 and store._state(1).early_rounds.copies == {}
    every_block_reads_back(cluster, meta, 1, blocks)

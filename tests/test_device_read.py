"""``TpuShuffleReader.read_device()``: a reduce task's blocks read on the device
through the normal reader, against ``read()`` and against the records written;
that tasks of different sizes share their executables; and that a removed
shuffle lets go of its HBM and its staging buffer at once.

The CPU mesh: counts and bytes, no rate."""

import gc
import re
import weakref

import jax
import numpy as np
import pytest

from benchmark.counters import CompileCounter
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import DeviceRead

ALIGN = 128
FAULT_COUNTERS = ("blocks_retried", "failovers", "fetch_timeouts", "hedges_issued")


def device_conf(staging, executors, **kw):
    return TpuShuffleConf(keep_device_recv=True, host_recv_mode="device", block_alignment=ALIGN,
                          staging_capacity_per_executor=staging, num_executors=executors, **kw)


def write_job(mgr, sid, mappers, reducers, seed, skip=lambda m, r: False, max_bytes=6000):
    """One job's map side and its exchange; returns {(map, reduce): bytes written}."""
    rng = np.random.default_rng(seed)
    mgr.register_shuffle(sid, mappers, reducers)
    want = {}
    for m in range(mappers):
        writer = mgr.get_writer(sid, m)
        for r in range(reducers):
            if skip(m, r):
                continue
            data = rng.integers(0, 256, size=int(rng.integers(1, max_bytes)), dtype=np.uint8).tobytes()
            want[(m, r)] = data
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(data)
        writer.commit_all_partitions()
    mgr.run_exchange(sid)
    return want


def blocks_of(got: DeviceRead):
    """{(map, reduce): bytes} of a device read, brought to the host."""
    host = np.asarray(got.packed).reshape(-1).view(np.uint8)
    row_bytes = got.packed.shape[1] * 4
    return {
        (bid.map_id, bid.reduce_id): host[row * row_bytes : row * row_bytes + length].tobytes()
        for (row, length), bid in zip(got.table.tolist(), got.block_ids)
    }


def raw_blocks(payload):
    """A deserializer that yields the block itself: ``read()`` then gives one
    item a block, in fetch order."""
    return [bytes(payload)]


# reducer 3 gets no block at all; mapper 2 writes nothing for reducers 0-1;
# mapper 4 writes nothing whatever
def skip(m, r):
    return r == 3 or m == 4 or (m == 2 and r < 2)


@pytest.mark.parametrize("executors, staging, rounds", [
    (1, 1 << 20, "one"), (4, 1 << 20, "one"), (1, 1 << 15, "several"), (4, 1 << 15, "several"),
], ids=["1x-one-round", "4x-one-round", "1x-several-rounds", "4x-several-rounds"])
def test_read_device_equals_read_and_the_records_written(executors, staging, rounds):
    mappers, reducers = 6, 8
    with TpuShuffleManager(device_conf(staging, executors), num_executors=executors) as mgr:
        want = write_job(mgr, 0, mappers, reducers, seed=executors * 100 + staging, skip=skip)
        meta = mgr.cluster.meta(0)
        assert (len(meta.recv_sizes) == 1) == (rounds == "one")
        assert meta.recv_shards is None  # no host copy of the received bytes
        spanning = 0
        for r in range(reducers):
            reader = mgr.get_reader(0, r, r + 1)
            got = reader.read_device()
            mine = sorted((m, rr) for m, rr in want if rr == r)
            assert [(b.map_id, b.reduce_id) for b in got.block_ids] == mine  # (reduce, map) order
            assert got.table.shape == (len(mine), 2) and got.table.dtype == np.int64
            assert got.packed.dtype == np.int32 and got.packed.shape[1] == ALIGN // 4
            owner = mgr.cluster.transport(meta.owner_of_reduce(r))
            assert got.packed.devices() == {owner.device}
            # byte for byte: the records written, and what read() gives
            assert blocks_of(got) == {key: want[key] for key in mine}
            host_reader = mgr.get_reader(0, r, r + 1, deserializer=raw_blocks)
            assert sorted(host_reader.read()) == sorted(want[key] for key in mine)
            # the same counts as the host read; no fault path exists here
            assert reader.metrics.remote_blocks_fetched == len(mine) == host_reader.metrics.remote_blocks_fetched
            assert reader.metrics.remote_bytes_read == sum(len(want[k]) for k in mine)
            assert reader.metrics.remote_bytes_read == host_reader.metrics.remote_bytes_read
            assert not any(getattr(reader.metrics, name) for name in FAULT_COUNTERS)
            if len(mine):
                rows = int((-(-got.table[:, 1] // ALIGN)).sum())
                spanning += got.packed.shape[0] > 1 << max(rows - 1, 0).bit_length()
        if rounds == "several":
            assert spanning  # some task's packed result holds more than one round's bucket
        assert mgr.get_reader(0, 3, 4).read_device().packed.shape == (0, ALIGN // 4)


def test_fetch_blocks_device_over_an_executors_whole_share_equals_the_plain_groupby(groupbytest):
    """GroupByTest's records held on the device (one round, received shards
    kept), then every block an executor owns gathered in ONE
    ``fetch_blocks_device``: the packed buffer stays on that executor's
    device, only the platform's own gather ran, and what it holds is the
    plain GroupBy's."""
    from sparkucx_tpu.core.block import ShuffleBlockId
    from sparkucx_tpu.shuffle.reader import default_deserializer

    records, n = groupbytest.records(4), 2
    with TpuShuffleManager(device_conf(8 << 20, n), num_executors=n) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        cluster = mgr.cluster
        meta = cluster.meta(0)
        assert len(meta.recv_sizes) == 1  # the whole share is one gather source
        checks = [records.check(r, full=True) for r in range(records.reducers)]
        for e in range(n):
            transport = cluster.transport(e)
            assert all(rnd[e].devices() == {transport.device} for rnd in meta.recv_device)
            start, end = meta.peer_ranges[e]
            bids = [ShuffleBlockId(0, m, r) for r in range(start, end) for m in records.mappers_of(r)]
            packed, entries = transport.fetch_blocks_device(bids)
            assert packed.devices() == {transport.device}
            host = np.asarray(packed).reshape(-1).view(np.uint8)
            for (row, length), bid in zip(entries.tolist(), bids):
                at = row * cluster.row_bytes
                for key, value in default_deserializer(memoryview(host[at : at + length])):
                    checks[bid.reduce_id].add(key, value)
        assert all(c.ok() for c in checks) and records.complete(checks)
        ran = cluster.executed_lowerings()
        assert set(ran["exchange"]) == {"dense"} and set(ran["gather"]) == {"xla"}


def test_a_range_of_several_partitions_is_one_packed_buffer():
    with TpuShuffleManager(device_conf(1 << 20, 2), num_executors=2) as mgr:
        want = write_job(mgr, 0, 5, 8, seed=7, skip=skip)
        start, end = mgr.cluster.meta(0).peer_ranges[1]
        assert end - start >= 2
        got = mgr.get_reader(0, start, end).read_device()
        mine = sorted(((m, r) for m, r in want if start <= r < end), key=lambda k: (k[1], k[0]))
        assert [(b.map_id, b.reduce_id) for b in got.block_ids] == mine
        assert blocks_of(got) == {key: want[key] for key in mine}


@pytest.mark.parametrize("case", ["another-executor", "across-two-owners"])
def test_a_range_this_executor_does_not_own_raises_the_typed_error(case):
    with TpuShuffleManager(device_conf(1 << 20, 2), num_executors=2) as mgr:
        write_job(mgr, 0, 3, 8, seed=1)
        (s0, e0), (s1, e1) = mgr.cluster.meta(0).peer_ranges
        if case == "another-executor":
            reader = mgr.get_reader(0, s1, e1, executor_id=0)
        else:
            reader = mgr.get_reader(0, e0 - 1, s1 + 1)
        with pytest.raises(TransportError, match="owned by"):
            reader.read_device()


def test_without_retained_shards_it_raises_the_typed_error():
    conf = TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=1 << 20, num_executors=1)
    with TpuShuffleManager(conf, num_executors=1) as mgr:
        want = write_job(mgr, 0, 2, 3, seed=2)
        with pytest.raises(TransportError, match="keep_device_recv"):
            mgr.get_reader(0, 0, 1).read_device()
        # the host forms are untouched by the conf
        assert sorted(mgr.get_reader(0, 0, 1, deserializer=raw_blocks).read()) == sorted(
            v for (m, r), v in want.items() if r == 0)


def test_before_the_exchange_it_raises_the_typed_error():
    with TpuShuffleManager(device_conf(1 << 20, 1), num_executors=1) as mgr:
        mgr.register_shuffle(0, 1, 2)
        writer = mgr.get_writer(0, 0)
        with writer.get_partition_writer(0).open_stream() as stream:
            stream.write(b"x" * 300)
        writer.commit_all_partitions()
        with pytest.raises(TransportError, match="not exchanged"):
            mgr.get_reader(0, 0, 1).read_device()


def test_two_hundred_tasks_of_different_totals_share_a_handful_of_executables():
    """The packed result is the gather's power-of-two bucket, never a slice to
    the task's own total: a job of 200 tasks builds a bounded number of
    executables, and a second job of other sizes builds none."""
    reducers, mappers = 200, 7
    compiles = CompileCounter()
    with TpuShuffleManager(device_conf(1 << 24, 1), num_executors=1) as mgr:
        totals = set()
        for sid in (0, 1):
            want = write_job(mgr, sid, mappers, reducers, seed=40 + sid, max_bytes=9000)
            mark = compiles.snapshot()
            for r in range(reducers):
                got = mgr.get_reader(sid, r, r + 1).read_device()
                totals.add(int((-(-got.table[:, 1] // ALIGN)).sum()))
                if r % 50 == 0:
                    assert blocks_of(got) == {k: v for k, v in want.items() if k[1] == r}
            built = compiles.since(mark)["compiles"]
            if sid == 0:
                assert len(totals) > 100  # the tasks really differ
                assert 1 <= built <= 12, built
            else:
                assert built == 0, built
            mgr.unregister_shuffle(sid)
        # 8-block plans into 128- to 512-row buckets: a few gather executables
        assert 1 <= len(mgr.cluster.executed_lowerings()["gather"]) <= 4


def family(text, name):
    rows = re.findall(rf'^sparkucx_tpu_{name}_(\w+)\{{\w+="([^"]+)"\}} (\S+)$', text, re.MULTILINE)
    return {(metric, label): float(value) for metric, label, value in rows}


def test_device_read_family_counts_once_a_task():
    with TpuShuffleManager(device_conf(1 << 16, 2), num_executors=2) as mgr:
        want = write_job(mgr, 0, 6, 8, seed=5, skip=skip)
        meta = mgr.cluster.meta(0)
        assert len(meta.recv_sizes) > 1
        gathers = {0: 0, 1: 0}
        for r in range(8):
            got = mgr.get_reader(0, r, r + 1).read_device()
            rounds = {meta.mapper_infos[b.map_id].round_of(b.reduce_id) for b in got.block_ids}
            gathers[meta.owner_of_reduce(r)] += len(rounds)
        rows = family(mgr.cluster.metrics_text(), "deviceread")
        for e, (start, end) in enumerate(meta.peer_ranges):
            mine = [k for k in want if start <= k[1] < end]
            label = str(e)
            assert rows[("tasks_total", label)] == end - start  # the empty reducer's task too
            assert rows[("blocks_total", label)] == len(mine)
            assert rows[("bytes_total", label)] == sum(len(want[k]) for k in mine)
            assert rows[("rows_total", label)] == sum(-(-len(want[k]) // ALIGN) for k in mine)
            assert rows[("gathers_total", label)] == gathers[e]
            assert rows[("locate_ns_total", label)] > 0
        assert sum(gathers.values()) > 7  # some of the seven non-empty tasks read two rounds


@pytest.mark.parametrize("executors", [1, 4])
@pytest.mark.parametrize("budget", [0, (1 << 20) - 1, 1 << 31],
                         ids=["buffer-over-the-budget", "the-floors-buffer", "default-budget"])
def test_a_removed_shuffle_holds_no_device_array_and_no_staging_without_a_collection(executors, budget):
    """What the deployment holds a shuffle — the sealed round and the received
    shards in HBM, the host staging buffer — is released at
    ``unregister_shuffle``, with the collector off.  A staging buffer over the
    store's RAM budget is freed there and then where the store keeps nothing
    (``max_host_pool_bytes=0``); one that fits — or is the store's own staging
    size, over a budget it alone exceeds (the HBM-held job's 4 GiB round: the
    free list's floor) — is the store's alone from then on, all zeros, on its
    free list, and the next shuffle's staging."""
    gc.collect()
    gc.disable()
    try:
        conf = device_conf(1 << 20, executors, max_host_pool_bytes=budget)
        with TpuShuffleManager(conf, num_executors=executors) as mgr:
            before = {id(a) for a in jax.live_arrays()}
            kept = []
            for sid in (0, 1):
                want = write_job(mgr, sid, 5, 8, seed=sid)
                staging = [weakref.ref(t.store._state(sid)._staging) for t in mgr.cluster.transports]
                if sid and budget:
                    assert [id(ref()) for ref in staging] == kept  # the job before's buffer, job after job
                states = [weakref.ref(t.store._state(sid)) for t in mgr.cluster.transports]
                held = [a for a in jax.live_arrays() if id(a) not in before]
                assert held and all(ref() is not None for ref in staging)
                held_bytes = sum(a.nbytes for rnd in mgr.cluster.meta(sid).recv_device for a in rnd)
                released = sum(t.store.write_stats()["released_device_bytes"] for t in mgr.cluster.transports)
                del held
                for r in range(8):
                    got = mgr.get_reader(sid, r, r + 1).read_device()  # readers, and their results, come and go
                    if sid:  # byte-exact out of a buffer the job before filled with other bytes
                        assert blocks_of(got) == {k: v for k, v in want.items() if k[1] == r}
                del got
                mgr.unregister_shuffle(sid)
                assert [a.shape for a in jax.live_arrays() if id(a) not in before] == []
                for ref, t in zip(staging, mgr.cluster.transports):
                    free = t.store._free_rounds.get(1 << 20, [])
                    if not budget:
                        assert ref() is None and not free
                    else:
                        assert [id(buf) for buf in free] == [id(ref())] and not ref().any()
                kept = [id(ref()) for ref in staging]
                assert [ref() for ref in states] == [None] * executors
                now = sum(t.store.write_stats()["released_device_bytes"] for t in mgr.cluster.transports)
                assert now - released >= held_bytes > 0
            rows = family(mgr.cluster.metrics_text(), "store")
            assert sum(v for (metric, _), v in rows.items() if metric == "released_device_bytes_total") == now
            for e, t in enumerate(mgr.cluster.transports):
                stats = t.store.write_stats()
                over = 2 if budget == (1 << 20) - 1 else 0  # one a removal, kept by the floor and not the budget
                assert rows[("pool_kept_over_budget_total", str(e))] == stats["pool_kept_over_budget"] == over
                assert rows[("pool_held_bytes_total", str(e))] == stats["pool_held_bytes"] == (1 << 20 if budget else 0)
                assert (stats["pool_hits"], stats["pool_misses"], stats["pool_dropped_busy"]) == (
                    (1, 1, 0) if budget else (0, 2, 0))
    finally:
        gc.enable()


def test_a_late_reader_of_a_removed_shuffle_is_refused_cleanly():
    """The state object a reader resolved before the removal no longer serves
    the released round: a typed refusal, never zeros or an index error."""
    conf = TpuShuffleConf(block_alignment=ALIGN, staging_capacity_per_executor=1 << 20, num_executors=1)
    with TpuShuffleManager(conf, num_executors=1) as mgr:
        write_job(mgr, 0, 2, 2, seed=9)
        store = mgr.cluster.transports[0].store
        state = store._state(0)
        mgr.unregister_shuffle(0)
        assert state.removed and state.staging is None and not state.sealed
        with pytest.raises(TransportError):
            store.read_block(0, 0, 0)


@pytest.mark.parametrize("executors", [1, 2])
def test_a_round_larger_than_one_put_goes_in_pieces_and_equals_the_staging(monkeypatch, executors):
    """``seal`` hands ``device_put`` at most ``SEAL_PUT_PIECE_BYTES`` a call
    (one call of 4 GiB ran 16 times slower on the chip's host than the same
    bytes in pieces); pieces that hold no used row are not put, and the
    sealed round is the staging buffer byte for byte."""
    import sparkucx_tpu.store.hbm_store as hbm_store

    piece = 1 << 14
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", piece)
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: (puts.append(int(x.nbytes)), real_put(x, *a, **k))[1])
    staging = 700 * ALIGN
    with TpuShuffleManager(device_conf(staging, executors), num_executors=executors) as mgr:
        mgr.register_shuffle(0, 4, 6)
        rng = np.random.default_rng(executors)
        for m in range(4):
            writer = mgr.get_writer(0, m)
            for r in range(6):
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(rng.integers(0, 256, size=int(rng.integers(1, 2500)), dtype=np.uint8).tobytes())
            writer.commit_all_partitions()
        store = mgr.cluster.transports[0].store
        state = store._state(0)
        host = state.staging.copy()
        used = int(state.region_used.sum())
        puts.clear()
        [(payload, sizes)] = store.seal(0)
        assert isinstance(payload, jax.Array) and payload.devices() == {store.device}
        assert (np.asarray(payload).reshape(-1).view(np.uint8) == host).all()
        assert max(puts) <= piece and len(puts) > 1
        # less than the whole buffer crossed: the padding's pieces stayed behind
        assert used <= sum(puts) < host.nbytes
        assert sizes.tolist() == (state.region_used // ALIGN).tolist()


def test_concurrent_device_reads_lose_no_count():
    """More reader threads than cores on one cluster, the interpreter
    switching often: every task, block and byte is counted once, and every
    task's bytes are its own."""
    import sys
    import threading

    reducers, mappers, rounds = 8, 6, 5
    with TpuShuffleManager(device_conf(1 << 20, 2), num_executors=2) as mgr:
        want = write_job(mgr, 0, mappers, reducers, seed=13)
        errors = []

        def work(r):
            try:
                for _ in range(rounds):
                    got = mgr.get_reader(0, r, r + 1).read_device()
                    assert blocks_of(got) == {k: v for k, v in want.items() if k[1] == r}
            except Exception as e:  # surfaced below: a thread's exception is otherwise lost
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(r % reducers,)) for r in range(2 * reducers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not any(t.is_alive() for t in threads)
        rows = mgr.cluster.device_read_stats()
        assert sum(row["tasks"] for row in rows) == 2 * reducers * rounds
        assert sum(row["blocks"] for row in rows) == 2 * rounds * len(want)
        assert sum(row["bytes"] for row in rows) == 2 * rounds * sum(len(v) for v in want.values())

"""The spans and counters inside the three host intervals that were opaque:
the map-side write (``store.rollover`` / ``store.spill`` and the ``store``
metrics family), the round's submit lane (``exchange.assemble`` /
``exchange.h2d`` / ``exchange.collective`` under ``exchange.pipeline.submit``)
and the daemon's side of a frame (``daemon.<op>`` and the ``daemon`` family);
and, opened by clock marks handed to ``Tracer.record_spans`` (PR 36), a daemon
frame by phase, the connection's wait for its client and a reduce task's
``read.window``.

Counts and nesting on the CPU mesh; no duration here is a rate."""

import collections
import re
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.shuffle.daemon import WRITE_PHASES_EVERY, DaemonClient, ShuffleDaemon
from sparkucx_tpu.transport.tpu import TpuShuffleCluster
from sparkucx_tpu.utils.trace import TRACER

SUBMIT_CHILDREN = ("exchange.assemble", "exchange.h2d", "exchange.collective")


@pytest.fixture
def tracer():
    """The process-wide tracer, cleared; back to what it was afterwards."""
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.clear()
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def run_shuffle(cluster, shuffle_id, mappers, reducers, block_bytes):
    """One shuffle through the store and the exchange; returns the bytes written."""
    cluster.create_shuffle(shuffle_id, mappers, reducers)
    written = 0
    for m in range(mappers):
        t = cluster.transport(cluster.meta(shuffle_id).map_owner[m])
        w = t.store.map_writer(shuffle_id, m)
        for r in range(reducers):
            data = np.full(block_bytes, (m * reducers + r) % 251, np.uint8).tobytes()
            w.write_partition(r, data)
            written += len(data)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(shuffle_id)
    return written


def spans(tracer, *names):
    return [e for e in tracer.events if e["ph"] == "X" and (not names or e["name"] in names)]


def inside(child, parent):
    return parent["ts"] <= child["ts"] and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def family(text, name):
    """``{(metric, label value): value}`` of one family of a Prometheus text."""
    rows = re.findall(rf'^sparkucx_tpu_{name}_(\w+)\{{\w+="([^"]+)"\}} (\S+)$', text, re.MULTILINE)
    return {(metric, label): float(value) for metric, label, value in rows}


#: where a completed round goes: ``disk-tier`` has no RAM tier of rounds
#: (``max_host_pool_bytes=0``: every rollover spills once, the store of before
#: it), ``ram-tier`` is the default conf, whose budget holds every round here
TIERS = {"disk-tier": {"max_host_pool_bytes": 0}, "ram-tier": {}}


@pytest.fixture(params=list(TIERS))
def multi_round(request, tracer):
    """Two executors, 4 MiB of staging each, 8 mappers x 2 reducers x 900 KB:
    every executor rolls its staging over, with full tracing on.  Once with
    every completed round on the disk tier, once with every one in RAM."""
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 22, block_alignment=128, num_executors=2,
                          **TIERS[request.param])
    cluster = TpuShuffleCluster(conf, num_executors=2)
    tracer.enable()
    written = run_shuffle(cluster, 0, mappers=8, reducers=2, block_bytes=900_000)
    tracer.disable()
    cluster.tier = request.param
    return cluster, written


def test_one_rollover_span_per_rollover_per_executor(multi_round, tracer):
    cluster, _ = multi_round
    rollovers = spans(tracer, "store.rollover")
    by_executor = collections.Counter(e["args"]["executor"] for e in rollovers)
    for t in cluster.transports:
        stats = t.store.write_stats()
        assert stats["rollovers"] >= 1 and by_executor[t.executor_id] == stats["rollovers"]
        # the region that overflowed held two 900 KB blocks of its 2 MiB
        tails = [e["args"]["tail_bytes"] for e in rollovers if e["args"]["executor"] == t.executor_id]
        assert sum(tails) == stats["rollover_tail_bytes"] and set(tails) == {(1 << 21) - 2 * 900_096}
        assert stats["largest_block_bytes"] == 900_000
    for e in rollovers:
        assert set(e["args"]) == {"shuffle_id", "round", "executor", "bytes", "tail_bytes"}
        assert e["args"]["bytes"] > 0
    # rounds are numbered from 0 per executor, one span each
    for t in cluster.transports:
        rounds = sorted(e["args"]["round"] for e in rollovers if e["args"]["executor"] == t.executor_id)
        assert rounds == list(range(len(rounds)))


def test_spill_is_the_child_of_its_rollover(multi_round, tracer):
    cluster, _ = multi_round
    rollovers, spills = spans(tracer, "store.rollover"), spans(tracer, "store.spill")
    if cluster.tier == "ram-tier":  # under the budget the disk arm never runs
        assert rollovers and not spills
        assert all(t.store._spill_dir is None for t in cluster.transports)
        return
    assert len(spills) == len(rollovers)  # no RAM tier: every rollover spills once
    by_id = {e["span_id"]: e for e in rollovers}
    for spill in spills:
        parent = by_id[spill["parent_id"]]
        assert inside(spill, parent)
        assert spill["args"]["round"] == parent["args"]["round"]
        assert spill["args"]["bytes"] == parent["args"]["bytes"]


def test_no_spill_span_without_the_disk_tier(tracer):
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128,
                          num_executors=2, spill_to_disk=False)
    cluster = TpuShuffleCluster(conf, num_executors=2)
    tracer.enable()
    run_shuffle(cluster, 0, mappers=4, reducers=2, block_bytes=300_000)
    assert spans(tracer, "store.rollover") and not spans(tracer, "store.spill")
    stats = cluster.transports[0].store.write_stats()
    assert stats["rollovers"] >= 1 and stats["spilled_bytes"] == 0 and stats["spill_ns"] == 0
    # a round that stays in RAM is its own buffer: nothing recycled, nothing zeroed
    assert stats["recycled_rounds"] == 0 and stats["zeroed_bytes"] == 0
    rows = family(cluster.metrics_text(), "store")
    assert rows[("recycled_rounds_total", "0")] == 0 and rows[("zeroed_bytes_total", "0")] == 0


def test_submit_lane_children_nest_and_cover_the_parent(multi_round, tracer):
    submits = spans(tracer, "exchange.pipeline.submit")
    assert len(submits) >= 2  # a multi-round shuffle
    children = spans(tracer, *SUBMIT_CHILDREN)
    covered = 0.0
    for submit in submits:
        mine = [c for c in children if c["parent_id"] == submit["span_id"]]
        assert sorted(c["name"] for c in mine) == sorted(SUBMIT_CHILDREN)
        assert all(inside(c, submit) for c in mine)
        # in the lane's order, none overlapping
        mine.sort(key=lambda c: c["ts"])
        assert [c["name"] for c in mine] == list(SUBMIT_CHILDREN)
        assert all(a["ts"] + a["dur"] <= b["ts"] for a, b in zip(mine, mine[1:]))
        assert {c["args"]["round"] for c in mine} == {submit["args"]["round"]}
        covered += sum(c["dur"] for c in mine)
    assert covered >= 0.9 * sum(s["dur"] for s in submits)
    assert len(children) == 3 * len(submits)  # none outside a submit lane


@pytest.mark.parametrize("name", ["exchange.assemble", "exchange.h2d"])
def test_submit_lane_children_carry_the_rounds_bytes(multi_round, tracer, name):
    cluster, _ = multi_round
    for e in spans(tracer, name):
        assert set(e["args"]) == {"shuffle_id", "round", "chunk", "bytes"}
        # a round is every executor's whole staging bucket, padding included
        assert e["args"]["bytes"] >= 2 * (1 << 22)


def test_d2h_is_once_a_sub_round_under_the_drain_and_names_its_landing(tracer):
    """``exchange.d2h`` (PR 43): still once a sub-round, child of
    ``exchange.pipeline.drain``, with the bytes that cross and how they
    landed — on the CPU backend no landing block is kept: ``fresh``."""
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 22, block_alignment=128, num_executors=2)
    cluster = TpuShuffleCluster(conf, num_executors=2)
    tracer.enable()
    run_shuffle(cluster, 0, mappers=8, reducers=2, block_bytes=900_000)
    tracer.disable()
    drains = spans(tracer, "exchange.pipeline.drain")
    d2h = spans(tracer, "exchange.d2h")
    assert len(d2h) == len(drains) >= 2
    by_id = {d["span_id"]: d for d in drains}
    counters = cluster.stats.counters("exchange.d2h")
    for e in d2h:
        assert set(e["args"]) == {"shuffle_id", "round", "chunk", "bytes", "landing"}
        assert e["args"]["landing"] == "fresh"
        assert inside(e, by_id[e["parent_id"]]) and by_id[e["parent_id"]]["args"]["round"] == e["args"]["round"]
    assert sum(e["args"]["bytes"] for e in d2h) == counters["moved_bytes"]
    assert counters["kept_shards"] == 0
    assert counters["fresh_shards"] + counters["skipped_shards"] == 2 * len(d2h)
    cluster.remove_shuffle(0)


def test_store_family_counts_what_was_written(multi_round):
    cluster, written = multi_round
    rows = family(cluster.metrics_text(), "store")
    executors = {label for _, label in rows}
    assert executors == {"0", "1"}
    assert sum(rows[("staged_bytes_total", e)] for e in executors) == written
    assert sum(rows[("staged_blocks_total", e)] for e in executors) == 8 * 2
    for e in executors:
        stats = cluster.transports[int(e)].store.write_stats()
        assert rows[("rollovers_total", e)] == stats["rollovers"] >= 1
        assert rows[("zeroed_bytes_total", e)] == stats["zeroed_bytes"] == stats["spilled_bytes"]
        assert rows[("pool_misses_total", e)] == stats["pool_misses"] == stats["rollovers"] + 1 - stats["recycled_rounds"]
        assert rows[("pool_hits_total", e)] == rows[("pool_dropped_busy_total", e)] == 0
        assert rows[("pool_held_bytes_total", e)] == stats["pool_held_bytes"] == 0  # nothing removed yet
        if cluster.tier == "disk-tier":
            # every rollover kept its buffer and zeroed what it spilled
            assert rows[("recycled_rounds_total", e)] == stats["recycled_rounds"] == stats["rollovers"]
            assert rows[("spilled_bytes_total", e)] == stats["spilled_bytes"] > 0
            assert rows[("ram_rounds_total", e)] == stats["ram_rounds"] == 0
            # the spill is inside the rollover
            assert 0 < rows[("spill_ns_total", e)] <= rows[("rollover_ns_total", e)]
        else:
            # every round was handed on as it was: nothing spilled, kept or zeroed
            assert rows[("ram_rounds_total", e)] == stats["ram_rounds"] == stats["rollovers"]
            assert rows[("recycled_rounds_total", e)] == rows[("spilled_bytes_total", e)] == 0
            assert rows[("spill_ns_total", e)] == 0 < rows[("rollover_ns_total", e)]
        assert rows[("copy_ns_total", e)] > 0  # the copies were timed
    cluster.remove_shuffle(0)
    rows = family(cluster.metrics_text(), "store")
    for e in executors:
        stats = cluster.transports[int(e)].store.write_stats()
        rounds = stats["rollovers"] + 1
        held = 0 if cluster.tier == "disk-tier" else (rounds - stats["pool_dropped_busy"]) * (1 << 22)
        assert rows[("pool_held_bytes_total", e)] == stats["pool_held_bytes"] == held


def test_a_retry_attempt_stages_and_counts_nothing(tracer):
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=1)
    cluster = TpuShuffleCluster(conf, num_executors=1)
    store = cluster.transports[0].store
    cluster.create_shuffle(0, 1, 1)
    first = store.map_writer(0, 0)
    first.write_partition(0, b"x" * 1000)
    first.commit()
    before = store.write_stats()
    retry = store.map_writer(0, 0)
    assert retry.is_retry_discard
    retry.write_partition(0, b"y" * 1000)
    retry.commit()
    after = store.write_stats()
    assert before["staged_blocks"] == after["staged_blocks"] == 1
    assert before["staged_bytes"] == after["staged_bytes"] == 1000


@pytest.mark.parametrize("tier", list(TIERS))
def test_concurrent_writers_lose_no_count(tier):
    """More writer threads than cores on one store, the interpreter switching
    often: every block and byte is counted, none twice."""
    import sys
    import threading

    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=1,
                          **TIERS[tier])
    cluster = TpuShuffleCluster(conf, num_executors=1)
    store = cluster.transports[0].store
    writers, blocks, size = 16, 40, 3000  # 1.9 MB through 1 MiB of staging: rollovers too
    cluster.create_shuffle(0, writers, blocks)
    errors = []

    def work(map_id):
        try:
            w = store.map_writer(0, map_id)
            for r in range(blocks):
                w.write_partition(r, bytes([map_id]) * size)
            w.commit()
        except Exception as e:  # surfaced below: a thread's exception is otherwise lost
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(m,)) for m in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    stats = store.write_stats()
    assert stats["staged_blocks"] == writers * blocks and stats["staged_bytes"] == writers * blocks * size
    assert stats["rollovers"] == store.num_rounds(0) - 1 >= 1 and stats["copy_ns"] > 0
    if tier == "disk-tier":
        assert stats["recycled_rounds"] == stats["rollovers"] and stats["ram_rounds"] == 0
        assert stats["spilled_bytes"] > 0 and 0 < stats["spill_ns"] <= stats["rollover_ns"]
    else:
        assert stats["ram_rounds"] == stats["rollovers"] and stats["recycled_rounds"] == 0
        assert stats["spilled_bytes"] == stats["spill_ns"] == 0 < stats["rollover_ns"]
        assert stats["pool_misses"] == store.num_rounds(0)


# -- the daemon's side of a frame ------------------------------------------

FRAMES = 24  # blocks a daemon job writes: 4 mappers x 6 reducers


def daemon_job(client, shuffle_id, block_bytes=700, fetch=False, frame_a_block=True):
    """One job over the socket; returns the bytes written.  ``frame_a_block``
    flushes the client after every block, so that the job is ``FRAMES``
    ``write_partition`` frames; without it a map task's six blocks ride in
    one frame, sent at its commit."""
    mappers, reducers = 4, FRAMES // 4
    client.create_shuffle(shuffle_id, mappers, reducers)
    written = 0
    for m in range(mappers):
        writer = client.open_map_writer(shuffle_id, m)
        for r in range(reducers):
            data = bytes([(m * reducers + r) % 251]) * block_bytes
            client.write_partition(writer, r, data)
            if frame_a_block:
                client.flush()
            written += len(data)
        client.commit_map(writer)
    if fetch:  # the reduce side as the JVM shim runs it: fetches only, one a reducer
        for r in range(reducers):
            got = client.fetch_blocks([ShuffleBlockId(shuffle_id, m, r) for m in range(mappers)])
            assert [bytes(b) for b in got] == [bytes([(m * reducers + r) % 251]) * block_bytes for m in range(mappers)]
    else:
        client.run_exchange(shuffle_id)
    client.remove_shuffle(shuffle_id)
    return written


@pytest.fixture
def daemon():
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=1)
    served = ShuffleDaemon(conf, num_executors=1, port=0)
    client = DaemonClient(served.address)
    yield served, client
    client.close()
    # the serving thread closes a frame's span some time after the client has
    # its reply: let it end, or that span lands in the next test's ring
    deadline = time.monotonic() + 10
    while served.stage_stats()["connections"] and time.monotonic() < deadline:
        time.sleep(0.001)
    served.close()


def test_daemon_family_counts_the_frames_sent(daemon, tracer):
    served, client = daemon
    written = daemon_job(client, 0)
    text = client.metrics_text()  # op 26, over the same socket
    rows = family(text, "daemon")
    assert rows[("frames_total", "write_partition")] == FRAMES
    assert rows[("body_bytes_total", "write_partition")] == written
    assert rows[("frames_total", "open_map_writer")] == rows[("frames_total", "commit_map")] == 4
    for op in ("create_shuffle", "run_exchange", "remove_shuffle"):
        assert rows[("frames_total", op)] == 1
    for (metric, op), value in rows.items():
        if metric == "serve_ns_total":
            # the ack's send is part of the frame's time on the clock
            assert 0 < rows[("ack_ns_total", op)] <= value
    # the daemon's store counted the same blocks
    store = family(text, "store")
    assert store[("staged_blocks_total", "0")] == FRAMES and store[("staged_bytes_total", "0")] == written


def test_concurrent_connections_lose_no_frame_count(daemon, tracer):
    """One serving thread a connection, one counter table: four clients at
    once count four jobs' frames."""
    import threading

    served, first = daemon
    clients = [first] + [DaemonClient(served.address) for _ in range(3)]
    errors = []

    def work(client, shuffle_id):
        try:
            daemon_job(client, shuffle_id)
        except Exception as e:  # surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=work, args=(c, i)) for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and not any(t.is_alive() for t in threads)
        rows = {row["op"]: row for row in served.op_stats()}
        assert rows["write_partition"]["frames"] == 4 * FRAMES
        assert rows["write_partition"]["body_bytes"] == 4 * FRAMES * 700
        assert rows["commit_map"]["frames"] == 16 and rows["run_exchange"]["frames"] == 4
    finally:
        for c in clients[1:]:
            c.close()


def test_daemon_spans_only_under_full_tracing(daemon, tracer):
    served, client = daemon
    assert tracer.recording and not tracer.enabled  # the cluster's flight recorder
    daemon_job(client, 0)
    assert not [e for e in spans(tracer) if e["name"].startswith("daemon.")]
    tracer.clear()
    tracer.enable()
    daemon_job(client, 1)
    tracer.disable()
    names = collections.Counter(e["name"] for e in spans(tracer) if e["name"].startswith("daemon."))
    assert names["daemon.write_partition"] == FRAMES
    assert names["daemon.open_map_writer"] == names["daemon.commit_map"] == 4
    assert names["daemon.run_exchange"] == 1
    # the exchange the daemon ran for the client is inside its frame
    [frame] = spans(tracer, "daemon.run_exchange")
    [superstep] = spans(tracer, "exchange.superstep")
    assert inside(superstep, frame) and superstep["parent_id"] == frame["span_id"]


@pytest.mark.parametrize("blocks", [8, 64])
def test_recording_alone_pays_nothing_per_block(tracer, blocks):
    """Untraced is not off: the flight recorder keeps ``recording`` on.  A
    one-round job then adds the two new submit-lane children per round and
    chunk to the ring, and nothing per block."""
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2)
    cluster = TpuShuffleCluster(conf, num_executors=2)
    assert tracer.recording and not tracer.enabled
    tracer.clear()
    # The ring is the process's own: an earlier test's cluster may still run
    # threads that write to it.  Count this shuffle's events — those that name
    # it, and those under its superstep's trace.
    sid = 7000 + blocks
    run_shuffle(cluster, sid, mappers=blocks // 2, reducers=2, block_bytes=500)
    [superstep] = [e for e in spans(tracer, "exchange.superstep") if e["args"]["shuffle_id"] == sid]
    names = collections.Counter(
        e["name"] for e in spans(tracer)
        if e["trace_id"] == superstep["trace_id"] or e.get("args", {}).get("shuffle_id") == sid
    )
    submits = names["exchange.pipeline.submit"]
    assert submits == 1  # one round, one chunk
    assert names["exchange.assemble"] == names["exchange.h2d"] == names["exchange.collective"] == submits
    # a one-round job is put on the chip at its seal: once an executor, not a block
    assert names.pop("store.seal_put") == 2
    # the map-side write: its parent span once a map task (PR 50), its
    # children and a block by phase full tracing's alone
    assert names.pop("write.task") == blocks // 2
    assert not [n for n in names if n.startswith(("store.", "daemon.", "write."))]
    # the same events whatever the number of blocks
    assert sum(names.values()) == sum(1 for _ in names), names
    # a reduce task's read: its ``read.window`` is the recorder's, once a
    # window; the children PR 36 opened inside it are full tracing's alone
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader

    tracer.clear()
    meta = cluster.meta(sid)
    records = 0
    for r in range(2):
        owner = meta.owner_of_reduce(r)
        reader = TpuShuffleReader(cluster.transport(owner), owner, sid, r, r + 1, blocks // 2,
                                  block_sizes=lambda m, r: 500, deserializer=lambda payload: [bytes(payload)])
        records += sum(1 for _ in reader.read())
    assert records == blocks
    read_names = collections.Counter(e["name"] for e in spans(tracer) if e["name"].startswith("read."))
    assert set(read_names) == {"read.window"} and read_names["read.window"] == -(-blocks // 2 // 50) * 2


def test_recording_alone_pays_nothing_per_frame(daemon, tracer):
    served, client = daemon
    assert tracer.recording and not tracer.enabled
    tracer.clear()
    daemon_job(client, 0)
    first = collections.Counter(e["name"] for e in spans(tracer))
    tracer.clear()
    daemon_job(client, 1, block_bytes=70)
    second = collections.Counter(e["name"] for e in spans(tracer))
    # the daemon's first shuffle allocates its staging (PR 50: the span of a
    # ``pool_misses``); the second takes the removed first's from the free list
    assert first.pop("store.round_buffer.fresh") == 1
    assert first.pop("write.task") == second.pop("write.task") == 4  # once a map task
    assert first == second and max(first.values()) == 1  # one of each, none per frame
    assert first["exchange.assemble"] == first["exchange.h2d"] == 1
    # nor does a frame's phase or a connection's turn: a job with its reduce
    # side over the socket records no ``daemon.<op>`` and nothing under one
    tracer.clear()
    daemon_job(client, 2, fetch=True)
    assert served.op_stats()[2]["op"] == "fetch_block" and served.op_stats()[2]["frames"] == FRAMES // 4
    third = collections.Counter(e["name"] for e in spans(tracer))
    assert not [n for n in third if n.startswith(("daemon.write_partition", "daemon.fetch_block", "daemon.client_turn", "read.window."))]
    assert not served._turns  # the per-connection marks exist under full tracing only


# -- a daemon frame by phase, and the connection's wait for its client ------

WRITE_PHASES = tuple("daemon.write_partition." + p for p in ("meta", "admit", "body", "record", "ack"))
FETCH_PHASES = ("daemon.fetch_block.locate", "daemon.fetch_block.send")


def ns(event):
    """An event's bounds back on the tracer's clock, whole nanoseconds."""
    return round(event["ts"] * 1e3), round((event["ts"] + event["dur"]) * 1e3)


def assert_partition(parent, children, names):
    """The children are the parent's, carry ``names`` in order and partition
    it: no gap, no overlap, the durations add up within 2 us."""
    assert [c["name"] for c in children] == list(names)
    assert all((c["trace_id"], c["parent_id"], c["tid"]) == (parent["trace_id"], parent["span_id"], parent["tid"])
               for c in children)
    cuts = [ns(c) for c in children]
    assert cuts[0][0] == ns(parent)[0] and cuts[-1][1] == ns(parent)[1]
    assert all(a[1] == b[0] for a, b in zip(cuts, cuts[1:]))
    assert all(lo <= hi for lo, hi in cuts)
    assert abs(sum(c["dur"] for c in children) - parent["dur"]) <= 2.0  # us


def children_of(tracer, parent):
    return sorted((e for e in spans(tracer) if e["parent_id"] == parent["span_id"]), key=lambda e: e["ts"])


def frames_of(tracer):
    """The ``daemon.<op>`` frame spans (not their phases or turns), in time."""
    return sorted((e for e in spans(tracer) if e["name"].count(".") == 1 and e["name"].startswith("daemon.")
                   and not e["name"].startswith("daemon.stage_")), key=lambda e: e["ts"])


#: the daemon's two serving planes: a thread a connection, or the reactor's pool
PLANES = {"threads": {}, "reactor": {"server_workers": 2}}


@pytest.fixture(params=list(PLANES))
def plane(request):
    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=1,
                          **PLANES[request.param])
    served = ShuffleDaemon(conf, num_executors=1, port=0)
    clients = [DaemonClient(served.address) for _ in range(2)]
    yield served, clients
    for c in clients:
        c.close()
    served.close()


def test_write_phases_partition_a_sampled_frame_and_skip_the_rest(daemon, tracer):
    served, client = daemon
    tracer.enable()
    daemon_job(client, 0)
    tracer.disable()
    writes = [f for f in frames_of(tracer) if f["name"] == "daemon.write_partition"]
    assert len(writes) == FRAMES
    sampled = [i for i in range(FRAMES) if i % WRITE_PHASES_EVERY == 1]
    assert sampled[0] == 1 and len(sampled) >= 2  # from the second frame on: it has a frame before it
    for i, frame in enumerate(writes):
        # the frame whose block is the staging's first allocates it inside its span
        children = [c for c in children_of(tracer, frame) if c["name"] != "store.round_buffer.fresh"]
        if i in sampled:
            assert_partition(frame, children, WRITE_PHASES)
        else:
            assert not children
    # none but a write or a fetch is opened, and nothing is recorded twice
    names = collections.Counter(e["name"] for e in spans(tracer))
    assert all(names[p] == len(sampled) for p in WRITE_PHASES)
    assert names["daemon.client_turn.write_partition"] == len(sampled)
    assert not [n for n in names if n.startswith("daemon.") and n.count(".") > 1
                and not n.startswith(("daemon.write_partition.", "daemon.client_turn."))]


def test_the_phases_of_a_frame_of_several_blocks_partition_it_too(daemon, tracer):
    """A map task's six blocks in one frame: four ``write_partition`` frames
    a job, the sampled one still cut in five — ``admit`` / ``body`` /
    ``record`` the sums of its blocks' shares — and the always-on row counts
    the blocks beside the frames."""
    served, client = daemon
    tracer.enable()
    written = daemon_job(client, 0, frame_a_block=False)
    tracer.disable()
    writes = [f for f in frames_of(tracer) if f["name"] == "daemon.write_partition"]
    assert len(writes) == 4  # one a map task, sent at its commit
    for i, frame in enumerate(writes):
        children = [c for c in children_of(tracer, frame) if c["name"] != "store.round_buffer.fresh"]
        if i % WRITE_PHASES_EVERY == 1:
            assert_partition(frame, children, WRITE_PHASES)
            # six blocks were admitted, received and recorded inside: none of the three is empty
            assert all(c["dur"] > 0 for c in children[1:4])
        else:
            assert not children
    assert len(spans(tracer, "daemon.client_turn.write_partition")) == 1
    row = {r["op"]: r for r in served.op_stats()}["write_partition"]
    assert (row["frames"], row["blocks"], row["body_bytes"]) == (4, FRAMES, written)


def test_fetch_phases_partition_every_frame(daemon, tracer):
    served, client = daemon
    tracer.enable()
    daemon_job(client, 0, fetch=True)
    tracer.disable()
    fetches = [f for f in frames_of(tracer) if f["name"] == "daemon.fetch_block"]
    assert len(fetches) == FRAMES // 4  # one a reducer
    for frame in fetches:
        assert_partition(frame, [c for c in children_of(tracer, frame) if c["name"] in FETCH_PHASES], FETCH_PHASES)
    # the exchange the first fetch ran at the stage boundary is inside its ``locate``
    [stage] = spans(tracer, "daemon.stage_exchange")
    first_locate = children_of(tracer, fetches[0])[0]
    assert first_locate["name"] == "daemon.fetch_block.locate" and inside(stage, first_locate)
    assert len(spans(tracer, "daemon.client_turn.fetch_block")) == len(fetches)


def test_client_turn_ends_where_its_frame_begins(daemon, tracer):
    """One connection: a turn is a root span that runs from the end of the
    frame before it to the begin of the frame it is named for, so the
    connection's frames and turns tile its time."""
    served, client = daemon
    tracer.enable()
    daemon_job(client, 0, fetch=True)
    tracer.disable()
    frames = frames_of(tracer)
    assert len({f["tid"] for f in frames}) == 1  # one connection, one serving thread
    begins = {ns(f)[0]: i for i, f in enumerate(frames)}
    turns = [e for e in spans(tracer) if e["name"].startswith("daemon.client_turn.")]
    assert turns
    for turn in turns:
        assert turn["parent_id"] == 0 and turn["trace_id"] not in {f["trace_id"] for f in frames}
        i = begins[ns(turn)[1]]  # ends where a frame begins ...
        assert turn["name"] == "daemon.client_turn." + frames[i]["name"].split(".", 1)[1]  # ... and is named for it
        assert i > 0 and ns(turn)[0] == ns(frames[i - 1])[1]  # begins where the frame before ended


def test_client_turns_and_write_samples_are_kept_per_connection(plane, tracer):
    """Two connections on either serving plane: each counts its own
    ``write_partition`` frames, and a connection that paused has a turn that
    runs from its own frame before, over the pause and whatever the other
    connection did meanwhile, to its next frame.  Judged by order and
    containment on the tracer's clock, never by a duration: the daemon closes
    a frame some time after the client has its reply, so a pause that starts
    at the reply is longer than the turn by as much as the serving thread
    waited (7 ms under six busy workers), and only a frame's begin is ordered
    against another connection's calls."""
    served, (a, b) = plane
    # the turns below are judged fetch to fetch: no ``offer_landing`` frame
    # (a same-host client's, after its first reply) between them
    a._may_offer = b._may_offer = False
    mappers, reducers = 2, 11
    a.create_shuffle(0, mappers, reducers)
    writers = [a.open_map_writer(0, 0), b.open_map_writer(0, 1)]
    tracer.enable()
    for r in range(reducers):  # frame about: 22 frames, 11 a connection
        for client, writer in zip((a, b), writers):
            client.write_partition(writer, r, bytes([r]) * 300)
            client.flush()
    for client, writer in zip((a, b), writers):
        client.commit_map(writer)
    block = [ShuffleBlockId(0, 0, 0)]
    assert bytes(a.fetch_blocks(block)[0]) == bytes([0]) * 300
    for _ in range(500):  # the daemon closes a frame after its reply is sent: wait for that
        if spans(tracer, "daemon.fetch_block"):
            break
        time.sleep(0.01)
    paused_from = time.perf_counter_ns()  # the tracer's clock
    time.sleep(0.05)
    paused_until = time.perf_counter_ns()
    for _ in range(3):
        b.fetch_blocks(block)
    a.fetch_blocks(block)  # a's turn began before the pause; b's frames begin inside it
    for client in (a, b):
        # a frame's phases are recorded after its reply is sent, before the
        # connection's next frame is read: one more frame each, and they are in
        client.stats(0)
    tracer.disable()
    names = collections.Counter(e["name"] for e in spans(tracer))
    per_connection = len([i for i in range(reducers) if i % WRITE_PHASES_EVERY == 1])
    in_common = len([i for i in range(2 * reducers) if i % WRITE_PHASES_EVERY == 1])
    assert names["daemon.write_partition.meta"] == 2 * per_connection != in_common
    # every fetch is a round trip, so the frames' order is the calls': a, b, b, b, a
    first, *theirs, last = [f for f in frames_of(tracer) if f["name"] == "daemon.fetch_block"]
    assert len(theirs) == 3
    turns = {ns(e)[1]: e for e in spans(tracer, "daemon.client_turn.fetch_block")}  # by the frame each ends at
    assert len(turns) == 5
    # a's turn is a's: it begins where a's own frame before ended, not b's,
    # and so holds the pause and the begin of each of b's three frames
    turn = turns[ns(last)[0]]
    assert ns(turn)[0] == ns(first)[1] <= paused_from and ns(turn)[1] >= paused_until
    assert all(ns(turn)[0] <= ns(f)[0] <= ns(turn)[1] for f in theirs)
    # b's turns between its three fetches are its own: each begins where b's
    # frame before ended, which is after the pause — none spans it
    for before, frame in zip(theirs, theirs[1:]):
        assert ns(turns[ns(frame)[0]])[0] == ns(before)[1] >= paused_until
    a.remove_shuffle(0)


def test_the_marks_of_a_connection_go_with_it_and_with_tracing(daemon, tracer):
    served, client = daemon
    tracer.enable()
    second = DaemonClient(served.address)
    second.create_shuffle(9, 1, 1)
    client.create_shuffle(8, 1, 1)
    assert len(served._turns) == 2
    second.close()
    for _ in range(200):  # until the serving thread has seen the close
        if len(served._turns) == 1:
            break
        time.sleep(0.01)
    assert len(served._turns) == 1
    tracer.disable()
    client.remove_shuffle(8)  # the first untraced frame drops what is left
    assert not served._turns


# -- a reduce task's read on the host, opened ---------------------------------

WINDOW_CHILDREN = ("read.window.fetch", "read.window.decode", "read.window.consumer")


@pytest.fixture(params=[50, 2], ids=["one-window-a-task", "pipelined-windows"])
def host_job(request):
    """5 mappers x 8 reducers of 1-3 records a block through a manager; with 2
    blocks a request a task is three windows and the default credit budget
    issues them ahead of consumption (``_fetch_windows_pipelined``)."""
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    from sparkucx_tpu.shuffle.reader import serialize_records

    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2,
                          max_blocks_per_request=request.param)
    mappers, reducers = 5, 8
    written = {}
    with TpuShuffleManager(conf, num_executors=2) as manager:
        manager.register_shuffle(0, mappers, reducers)
        for m in range(mappers):
            writer = manager.get_writer(0, m)
            for r in range(reducers):
                records = [(m * 100 + r * 10 + i, bytes([m, r, i]) * 40) for i in range(1 + (m + r) % 3)]
                written.setdefault(r, []).extend(records)
                with writer.get_partition_writer(r).open_stream() as stream:
                    stream.write(serialize_records(records))
            writer.commit_all_partitions()
        manager.run_exchange(0)
        yield manager, written, -(-mappers // request.param)


def read_all(manager, reducers, slow_ms=0.0):
    """Every reduce task drained through ``read()``; returns the records and
    the readers' byte and record counts."""
    def slow(payload):
        from sparkucx_tpu.shuffle.reader import default_deserializer

        for rec in default_deserializer(payload):
            time.sleep(slow_ms / 1e3)
            yield rec

    got, counts = {}, []
    for r in range(reducers):
        reader = manager.get_reader(0, r, r + 1, deserializer=slow) if slow_ms else manager.get_reader(0, r, r + 1)
        got[r] = []
        for rec in reader.read():
            if slow_ms:
                time.sleep(2 * slow_ms / 1e3)  # the consumer's turn, twice the decoder's
            got[r].append(rec)
        counts.append((reader.metrics.records_read, reader.metrics.remote_bytes_read, reader.metrics.remote_blocks_fetched))
    return got, counts


def test_read_window_children_lie_end_to_end_inside_it(host_job, tracer):
    from sparkucx_tpu.shuffle.reader import WINDOW_TURNS_EVERY

    manager, written, windows_a_task = host_job
    assert tracer.recording and not tracer.enabled
    untraced = read_all(manager, len(written))  # and the count of traced windows starts anew
    assert untraced[0] == written
    assert not [e for e in spans(tracer) if e["name"] in WINDOW_CHILDREN]  # ``enabled``-only
    tracer.clear()
    tracer.enable()
    slow_ms = 3.0  # long against a stall of a loaded host: the sleeps are compared below
    traced = read_all(manager, len(written), slow_ms=slow_ms)
    tracer.disable()
    assert traced == untraced  # records, bytes and blocks read: the same with tracing on
    windows = sorted(spans(tracer, "read.window"), key=lambda e: e["ts"])
    assert len(windows) == len(written) * windows_a_task
    sampled = decode_us = consumer_us = 0
    for i, window in enumerate(windows):
        children = children_of(tracer, window)
        assert all((c["trace_id"], c["tid"], c.get("eid")) == (window["trace_id"], window["tid"], window.get("eid"))
                   for c in children)
        fetch = children[0]
        assert fetch["name"] == "read.window.fetch" and ns(fetch)[0] == ns(window)[0]
        # one real interval, or the two turns (issue, await) of a window issued ahead
        assert fetch.get("args") == (None if windows_a_task == 1 else {"turns": 2})
        if i % WINDOW_TURNS_EVERY:  # one window in five of the process, from the first
            assert len(children) == 1 and inside(fetch, window)
            continue
        sampled += 1
        assert [c["name"] for c in children] == list(WINDOW_CHILDREN)
        _, decode, consumer = children
        # laid end to end after the real child, inside the window: never overlapping
        assert ns(fetch)[1] == ns(decode)[0] and ns(decode)[1] == ns(consumer)[0]
        assert ns(consumer)[1] <= ns(window)[1]
        assert fetch["dur"] + decode["dur"] + consumer["dur"] <= window["dur"] + 1e-3
        # the window's records, and whose turn each sleep was
        turns = decode["args"]["turns"]
        assert consumer["args"] == decode["args"] and turns >= 1
        assert decode["dur"] >= turns * slow_ms * 1e3 and consumer["dur"] >= turns * 2 * slow_ms * 1e3
        decode_us, consumer_us = decode_us + decode["dur"], consumer_us + consumer["dur"]
    assert sampled == -(-len(windows) // WINDOW_TURNS_EVERY)
    # over the sampled windows together: one window's sleeps can be stalled past each other
    assert decode_us < consumer_us
    # summed over the sampled windows of one task a window, ``turns`` are its records
    if windows_a_task == 1:
        for r in range(0, len(written), WINDOW_TURNS_EVERY):
            [decode] = [c for c in children_of(tracer, windows[r]) if c["name"] == "read.window.decode"]
            assert decode["args"]["turns"] == len(written[r])


def test_fetch_blocks_without_read_records_the_fetch_only(host_job, tracer):
    manager, written, windows_a_task = host_job
    for r in range(2):
        list(manager.get_reader(0, r, r + 1).fetch_blocks())  # untraced: the count starts anew
    tracer.clear()
    tracer.enable()
    blocks = [bytes(b.data) for b in manager.get_reader(0, 0, 1).fetch_blocks()]
    tracer.disable()
    assert len(blocks) == 5
    windows = spans(tracer, "read.window")
    assert len(windows) == windows_a_task
    for window in windows:  # the first is a sampled window: nobody took its turns
        [fetch] = children_of(tracer, window)
        assert fetch["name"] == "read.window.fetch" and inside(fetch, window)


def test_a_value_read_outlives_its_fetch_buffer(host_job):
    """A ``groupByKey`` consumer keeps every value ``read()`` yields, and a
    local read decodes straight out of the received shards (no pool buffer
    is taken: there is none to come back): each value is compared only after
    the shuffle is removed, its shards dropped and — where a shard is
    writable host memory — overwritten.  Each value owns its bytes."""
    import gc

    manager, written, _ = host_job
    before = manager.pool.stats()
    got = {r: list(manager.get_reader(0, r, r + 1).read()) for r in written}
    assert manager.pool.stats() == before  # a local read takes no fetch buffer
    shards = [shard for rnd in manager.cluster.meta(0).recv_shards for shard in rnd]
    assert sum(shard.nbytes for shard in shards) >= sum(len(v) for recs in written.values() for _, v in recs)
    manager.unregister_shuffle(0)
    for shard in shards:
        if shard.flags.writeable:
            shard[:] = 0xA5
    del shards
    gc.collect()
    assert got == written
    assert all(type(value) is bytes for records in got.values() for _, value in records)


def test_read_family_counts_how_the_blocks_were_read(host_job):
    """Once a task, not once a block: ``resident_blocks`` / ``resident_bytes``
    / ``copied_blocks`` of the ``read`` kind, through ``metrics_text()``."""
    manager, written, _ = host_job
    _, counts = read_all(manager, len(written))
    text = manager.cluster.metrics_text()

    def read_counter(name):
        [line] = [l for l in text.splitlines()
                  if l.startswith(f"sparkucx_tpu_ops_{name}_total") and 'kind="read"' in l]
        return int(float(line.rsplit(" ", 1)[1]))

    assert read_counter("resident_blocks") == sum(blocks for _, _, blocks in counts) == 5 * 8
    assert read_counter("resident_bytes") == sum(nbytes for _, nbytes, _ in counts)
    assert read_counter("copied_blocks") == 0
    assert "failovers_total" not in text  # the fault counters only where one is not zero


# -- the device read and the single-round seal -------------------------------

READ_CHILDREN = ("read.device.locate", "fetch.device_gather")


def device_job(manager, shuffle_id, mappers, reducers, block_bytes=900):
    manager.register_shuffle(shuffle_id, mappers, reducers)
    for m in range(mappers):
        writer = manager.get_writer(shuffle_id, m)
        for r in range(reducers):
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(bytes([(m * reducers + r) % 251]) * block_bytes)
        writer.commit_all_partitions()
    manager.run_exchange(shuffle_id)


@pytest.fixture
def device_manager():
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2,
                          keep_device_recv=True, host_recv_mode="device")
    with TpuShuffleManager(conf, num_executors=2) as manager:
        yield manager


def test_device_read_spans_nest_once_a_task(device_manager, tracer):
    mappers, reducers = 5, 6
    device_job(device_manager, 0, mappers, reducers)
    tracer.enable()
    for r in range(reducers):
        device_manager.get_reader(0, r, r + 1).read_device()
    tracer.disable()
    tasks = spans(tracer, "read.device")
    assert sorted(e["args"]["reduce_id"] for e in tasks) == list(range(reducers))  # once a task
    children = spans(tracer, *READ_CHILDREN)
    assert len(children) == 2 * reducers  # none outside a task, none per block
    meta = device_manager.cluster.meta(0)
    for task in tasks:
        assert set(task["args"]) == {"shuffle_id", "reduce_id", "blocks", "rows"}
        assert task["args"]["blocks"] == mappers and task["args"]["rows"] == mappers * -(-900 // 128)
        assert task["eid"] == meta.owner_of_reduce(task["args"]["reduce_id"])
        mine = sorted((c for c in children if c["parent_id"] == task["span_id"]), key=lambda c: c["ts"])
        assert [c["name"] for c in mine] == list(READ_CHILDREN)  # the plan, then its upload and the dispatch
        assert all(inside(c, task) for c in mine) and mine[0]["ts"] + mine[0]["dur"] <= mine[1]["ts"]
        assert all(c["args"] == {"shuffle_id": 0, "blocks": mappers} for c in mine)


@pytest.mark.parametrize("blocks", [4, 48])
def test_recording_alone_a_device_read_pays_nothing_per_block(device_manager, tracer, blocks):
    """Three spans a task under the flight recorder, whatever the blocks."""
    assert tracer.recording and not tracer.enabled
    sid = 7100 + blocks
    device_job(device_manager, sid, mappers=blocks, reducers=2, block_bytes=300)
    tracer.clear()
    device_manager.get_reader(sid, 0, 1).read_device()
    names = collections.Counter(
        e["name"] for e in spans(tracer) if e.get("args", {}).get("shuffle_id") == sid
    )
    assert names == {"read.device": 1, "read.device.locate": 1, "fetch.device_gather": 1}


def test_seal_put_is_inside_the_seal_once_an_executor(device_manager, tracer):
    tracer.enable()
    device_job(device_manager, 0, mappers=4, reducers=4)
    tracer.disable()
    [seal] = spans(tracer, "exchange.seal")
    puts = spans(tracer, "store.seal_put")
    assert sorted(e["args"]["executor"] for e in puts) == [0, 1]  # one round: one put an executor
    for put in puts:
        assert put["parent_id"] == seal["span_id"] and inside(put, seal)
        assert put["args"] == {"shuffle_id": 0, "executor": put["args"]["executor"], "bytes": 1 << 20}


def test_no_seal_put_span_when_rounds_seal_on_the_host(multi_round, tracer):
    """Several rounds stay host-resident at the seal; the exchange puts them,
    a round at a time (``exchange.h2d``)."""
    assert spans(tracer, "exchange.seal") and not spans(tracer, "store.seal_put")


def test_released_device_bytes_joins_the_store_family(device_manager):
    device_job(device_manager, 0, mappers=4, reducers=4)
    cluster = device_manager.cluster
    held = {e: sum(int(rnd[e].nbytes) for rnd in cluster.meta(0).recv_device) for e in (0, 1)}
    assert all(family(cluster.metrics_text(), "store")[("released_device_bytes_total", str(e))] == 0 for e in held)
    device_manager.unregister_shuffle(0)
    rows = family(cluster.metrics_text(), "store")
    for e, nbytes in held.items():
        assert rows[("released_device_bytes_total", str(e))] >= nbytes > 0


# -- the device write -------------------------------------------------------------


def device_produced_job(manager, shuffle_id, mappers, reducers, block_bytes=900):
    """``device_job`` with each map task's output handed over as ONE packed
    device array; returns the bytes written."""
    import jax

    lane = 128 // 4
    rows = -(-block_bytes // 128)
    manager.register_shuffle(shuffle_id, mappers, reducers)
    owners = manager.cluster.meta(shuffle_id).map_owner
    for m in range(mappers):
        host = np.zeros((reducers * rows, lane), dtype=np.int32)
        flat = host.reshape(-1).view(np.uint8)
        for r in range(reducers):
            flat[r * rows * 128 : r * rows * 128 + block_bytes] = (m * reducers + r) % 251
        writer = manager.get_writer(shuffle_id, m)
        packed = jax.device_put(host, manager.cluster.transport(owners[m]).device)
        writer.write_partitions_device(packed, list(range(reducers)), [block_bytes] * reducers)
        writer.commit_all_partitions()
    manager.run_exchange(shuffle_id)
    return mappers * reducers * block_bytes


@pytest.fixture
def device_producer():
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, block_alignment=128, num_executors=2,
                          keep_device_recv=True, host_recv_mode="device", device_staging=True)
    with TpuShuffleManager(conf, num_executors=2) as manager:
        yield manager


@pytest.mark.parametrize("full", [True, False], ids=["enabled", "recording-alone"])
def test_device_stage_span_once_a_dispatch_and_no_seal_put(device_producer, tracer, full):
    """One ``store.device_stage`` a map task (a dispatch), with the dispatch's
    blocks and true bytes, whatever the number of blocks; the seal has nothing
    to put.  The same under the flight recorder alone."""
    if full:
        tracer.enable()
    assert tracer.recording
    tracer.clear()
    mappers, reducers = 6, 5
    sid = 7300 + full
    written = device_produced_job(device_producer, sid, mappers, reducers)
    stages = [e for e in spans(tracer, "store.device_stage") if e["args"]["shuffle_id"] == sid]
    assert len(stages) == mappers
    assert all(e["args"]["blocks"] == reducers and e["args"]["bytes"] == reducers * 900 for e in stages)
    assert collections.Counter(e["args"]["executor"] for e in stages) == {0: 3, 1: 3}
    assert not [e for e in spans(tracer, "store.seal_put", "store.rollover") if e["args"]["shuffle_id"] == sid]
    assert [e for e in spans(tracer, "exchange.seal") if e["args"]["shuffle_id"] == sid]
    rows = family(device_producer.cluster.metrics_text(), "store")
    for e in ("0", "1"):
        assert rows[("scatter_dispatches_total", e)] == mappers // 2
        assert rows[("device_staged_blocks_total", e)] == reducers * mappers // 2
        assert rows[("device_staged_bytes_total", e)] == rows[("staged_bytes_total", e)] == written // 2
        assert rows[("device_stage_ns_total", e)] > 0 and rows[("copy_ns_total", e)] == 0


def test_a_host_staged_job_counts_no_device_stage(device_manager, tracer):
    tracer.enable()
    device_job(device_manager, 0, mappers=4, reducers=4)
    assert not spans(tracer, "store.device_stage")
    rows = family(device_manager.cluster.metrics_text(), "store")
    assert all(rows[(name, e)] == 0 for e in ("0", "1") for name in (
        "scatter_dispatches_total", "device_staged_blocks_total", "device_staged_bytes_total",
        "device_stage_ns_total"))


# -- names on the device ----------------------------------------------------


def test_exchange_and_gather_bodies_trace_under_a_named_scope():
    """What a device trace calls the operations: the scope is in every
    operation's ``op_name``; the jitted functions keep their names."""
    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange, make_mesh
    from sparkucx_tpu.ops.pallas_kernels import build_block_gather, build_block_scatter

    fn = build_exchange(make_mesh(2), ExchangeSpec(num_executors=2, send_rows=16, recv_rows=16, impl="dense"))
    text = fn.lower(jax.ShapeDtypeStruct((32, 128), jnp.int32),
                    jax.ShapeDtypeStruct((2, 2), jnp.int32)).as_text(debug_info=True)
    assert "module @jit__exchange_shard_dense" in text and "exchange_dense/all_to_all" in text
    plan = jax.ShapeDtypeStruct((4,), jnp.int32)
    rows = jax.ShapeDtypeStruct((64, 128), jnp.int32)
    gather = build_block_gather(4, 64, impl="xla").lower(plan, plan, plan, rows).as_text(debug_info=True)
    assert "module @jit_block_gather" in gather and "jit(block_gather)/block_gather/gather" in gather
    scatter = build_block_scatter(4, 64, impl="xla").lower(plan, plan, plan, rows, rows).as_text(debug_info=True)
    assert "module @jit_block_scatter" in scatter and "jit(block_scatter)/block_scatter/" in scatter


@pytest.fixture(scope="module")
def v5e():
    """The chip's compiler, for a chip that is described and not attached."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("chips, impl, module, operation", [
    (1, "local", "jit_local_fn", "%block_gather_dma"),
    (4, "ragged", "jit__exchange_shard_ragged", "%ragged_all_to_all"),
    # the shrunk mesh of a degraded recovery: two survivors of the four
    (2, "ragged", "jit__exchange_shard_ragged", "%ragged_all_to_all"),
])
def test_compiled_for_the_chip_the_exchange_keeps_its_names(v5e, chips, impl, module, operation):
    """At a 64 MiB round for the v5e: the module names ``exchange_roofline``
    finds its executables by, the kernel's name on its custom call (was
    ``%_unknown_``), and the scope in the operation's ``op_name``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange

    mesh = Mesh(np.array(v5e.devices[:chips]), ("ex",))
    rows = (64 << 20) // 512
    fn = build_exchange(mesh, ExchangeSpec(num_executors=chips, send_rows=rows, recv_rows=rows, impl=impl))
    sharding = NamedSharding(mesh, P("ex", None))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        text = fn.lower(
            jax.ShapeDtypeStruct((chips * rows, 128), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((chips, chips), jnp.int32, sharding=sharding),
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert text.startswith(f"HloModule {module},")
    [line] = [l for l in text.splitlines() if l.lstrip().startswith(operation + ".")]
    assert f"exchange_{impl}/" in re.search(r'op_name="([^"]*)"', line).group(1)
    if impl == "local":
        assert 'custom_call_target="tpu_custom_call"' in line and "block_gather/block_gather_dma" in line


def test_compiled_for_the_chip_the_block_scatter_appends_in_place(v5e):
    """The device write's one dispatch at the HBM-held configuration's size —
    a (3, 256) plan, a 245,760-row packed task, the 4 GiB staging array — for
    the v5e: the module name a device trace shows it under, the kernel's
    name on its custom call, the scope, and the staging array aliased to the
    result with nothing allocated beside it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sparkucx_tpu.ops.pallas_kernels import build_block_scatter

    rows = (4 << 30) // 512
    scatter = build_block_scatter(256, rows, impl="dma", max_block_rows=2048)

    def block_scatter(plan, src, dst):  # HbmBlockStore._scatter_fn's wrapper
        return scatter(plan[0], plan[1], plan[2], src, dst)

    one = SingleDeviceSharding(v5e.devices[0])
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        compiled = jax.jit(block_scatter, donate_argnums=(2,)).lower(
            jax.ShapeDtypeStruct((3, 256), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((245760, 128), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32, sharding=one),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_block_scatter,")
    [line] = [l for l in text.splitlines() if l.lstrip().startswith(("%block_scatter_dma.", "ROOT %block_scatter_dma."))]
    assert 'custom_call_target="tpu_custom_call"' in line
    assert "block_scatter/block_scatter_dma" in re.search(r'op_name="([^"]*)"', line).group(1)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == memory.output_size_in_bytes == 4 << 30
    assert memory.temp_size_in_bytes == 0


def test_compiled_for_the_chip_the_received_prefix_is_one_named_slice(v5e):
    """The D2H's device-side slice at the small job's size — a 16 MiB prefix
    of a 64 MiB received shard — for the v5e: the module name a device trace
    shows it under, one slice and nothing allocated beside its result."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    cluster = TpuShuffleCluster(TpuShuffleConf(num_executors=1), num_executors=1)
    one = SingleDeviceSharding(v5e.devices[0])
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        compiled = cluster._prefix_fn((16 << 20) // 512).lower(
            jax.ShapeDtypeStruct(((64 << 20) // 512, 128), jnp.int32, sharding=one)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_recv_prefix,")
    assert len([l for l in text.splitlines() if " slice(" in l]) == 1
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 16 << 20 and memory.temp_size_in_bytes == 0


@pytest.mark.parametrize("operator, module", [
    ("aggregate", "jit_grouped_sum_records"), ("semi", "jit_merge_join_records"),
    ("inner", "jit_merge_join_records"),
])
def test_compiled_for_the_chip_the_query_operators_keep_their_names(v5e, operator, module):
    """The reduce task's operators at TPC-H Q18's SF=10 shapes (the
    configuration's ``geometry``: 76,512 / 75,696 / 303,616 record places, 32
    rows a task handed on) for the v5e: the chip's compiler takes them, under
    the module names ``query_aggregate_roofline`` / ``query_join_roofline``
    find their executables by."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from sparkucx_tpu.ops.relational import grouped_sum_records, merge_join_records

    one = SingleDeviceSharding(v5e.devices[0])

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    count = shape()
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        if operator == "aggregate":
            lowered = grouped_sum_records.lower(shape(76512, 4), count, shape(2, dtype=jnp.uint32), key_bytes=8,
                                                value_lane=2, having="gt", out_capacity=32)
        elif operator == "semi":
            lowered = merge_join_records.lower(shape(75696, 8), count, shape(32, 4), count, key_bytes=8,
                                               join_type="left_semi", out_capacity=32)
        else:
            lowered = merge_join_records.lower(shape(303616, 4), count, shape(32, 8), count, key_bytes=8,
                                               join_type="inner", out_capacity=224)
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert text.startswith(f"HloModule {module},")

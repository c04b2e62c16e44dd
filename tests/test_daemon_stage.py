"""The daemon at the stage boundary: task connections side by side, and an
exchange that no client announces.

A reduce task of the JVM shim speaks ``FetchBlock`` only, so the daemon runs a
shuffle's exchange at its first fetch once every map has committed — once,
whoever asks and however many ask at the same time.  The records and the
answers are the upstream gate job's (``benchmark/references/groupby.py``)."""

import queue
import sys
import threading
import time
from contextlib import closing

import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import ShuffleBlockId
from sparkucx_tpu.core.operation import TransportError
from sparkucx_tpu.shuffle.daemon import DaemonClient, ShuffleDaemon

#: every join and wait of this file; a test that passes takes a second or two
TIMEOUT = 60


@pytest.fixture
def switchy():
    """Threads change places often, as on a host with fewer cores than tasks."""
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(before)


@pytest.fixture
def daemon(request):
    conf = TpuShuffleConf(**getattr(request, "param", {}))
    d = ShuffleDaemon(conf, num_executors=1, port=0)
    yield d
    d.close()


def write_maps(daemon, shuffle_id, records, connections=1, commit_last=True):
    """The job's map stage over ``connections`` connections at once, the map
    tasks in index order, each to the connection that frees first."""
    with closing(DaemonClient(daemon.address)) as driver:
        driver.create_shuffle(shuffle_id, records.num_mappers, records.reducers)
    todo = queue.Queue()
    for m in range(records.num_mappers):
        todo.put(m)
    errors = []

    def slot():
        try:
            with closing(DaemonClient(daemon.address)) as client:
                while True:
                    try:
                        m = todo.get_nowait()
                    except queue.Empty:
                        return
                    writer = client.open_map_writer(shuffle_id, m)
                    for r, payload in records.blocks[m]:
                        client.write_partition(writer, r, payload)
                    if commit_last or m != records.num_mappers - 1:
                        client.commit_map(writer)
        except Exception as e:  # the thread's boundary: the test reads it
            errors.append(e)

    run_all([slot] * connections)
    assert not errors, errors


def run_all(targets):
    threads = [threading.Thread(target=t, daemon=True) for t in targets]
    for t in threads:
        t.start()
    for t in threads:
        t.join(TIMEOUT)
    assert not any(t.is_alive() for t in threads), "a thread hangs"


def fetch_all(daemon, shuffle_id, records, connections):
    """Every reduce task's blocks over ``connections`` connections at once (no
    ``RunExchange`` from anyone); returns {(map, reduce): payload or None}."""
    got, errors = {}, []
    start = threading.Barrier(connections)

    def slot(k):
        try:
            with closing(DaemonClient(daemon.address)) as client:
                start.wait(TIMEOUT)
                for r in range(k, records.reducers, connections):
                    mappers = records.mappers_of(r)
                    payloads = client.fetch_blocks([ShuffleBlockId(shuffle_id, m, r) for m in mappers])
                    got.update({(m, r): p for m, p in zip(mappers, payloads)})
        except Exception as e:
            errors.append(e)

    run_all([lambda k=k: slot(k) for k in range(connections)])
    assert not errors, errors
    return got


def written(records):
    return {(m, r): payload for m, parts in enumerate(records.blocks) for r, payload in parts}


def counting(daemon, monkeypatch, before=None):
    """``manager.run_exchange`` counted: calls begun and calls that returned;
    ``before(shuffle_id)`` runs first inside each (a delay, or a failure)."""
    calls = {"begun": 0, "returned": 0, "entered": threading.Event()}
    real = daemon.manager.run_exchange

    def run_exchange(shuffle_id):
        calls["begun"] += 1
        calls["entered"].set()
        if before is not None:
            before(shuffle_id)
        real(shuffle_id)
        calls["returned"] += 1

    monkeypatch.setattr(daemon.manager, "run_exchange", run_exchange)
    return calls


@pytest.mark.parametrize("daemon", [{}, {"server_workers": 3}], ids=["thread-a-connection", "reactor"],
                         indirect=True)
def test_first_fetches_side_by_side_run_one_exchange(daemon, groupbytest, switchy, monkeypatch):
    """Eight reduce-task connections fetch one shuffle at once and nobody
    sends RunExchange: one exchange, every block byte-exact, the others
    waited for it."""
    records = groupbytest.records(4)
    write_maps(daemon, 0, records)
    calls = counting(daemon, monkeypatch, before=lambda sid: time.sleep(0.2))
    got = fetch_all(daemon, 0, records, connections=8)
    assert got == written(records)
    stats = daemon.stage_stats()
    assert calls["begun"] == 1 and stats["stage_exchanges"] == 1
    workers = daemon.conf.server_workers or 8
    assert 1 <= stats["stage_waiters"] <= workers - 1 and stats["stage_wait_ns"] > 0
    assert stats["connections_peak"] >= 8
    text = daemon.manager.cluster.metrics_text()
    assert "sparkucx_tpu_daemon_stage_exchanges_total 1" in text
    assert "sparkucx_tpu_daemon_connections_peak" in text and "sparkucx_tpu_daemon_connections " in text
    # a second shuffle on the same daemon has an exchange of its own
    write_maps(daemon, 1, records)
    assert fetch_all(daemon, 1, records, connections=2) == written(records)
    assert calls["begun"] == 2 and daemon.stage_stats()["stage_exchanges"] == 2


def test_the_stage_spans_are_recorded_without_full_tracing(daemon, groupbytest, monkeypatch):
    """``daemon.stage_exchange`` once a shuffle, ``daemon.stage_wait`` once a
    fetch that waited: under the flight recorder, as ``exchange.superstep``."""
    from sparkucx_tpu.utils.trace import TRACER

    records = groupbytest.records(2)
    write_maps(daemon, 0, records)
    monkeypatch.setattr(TRACER, "enabled", False)  # whatever an earlier test of this process left
    assert TRACER.recording  # the cluster's flight recorder
    TRACER.clear()
    fetch_all(daemon, 0, records, connections=4)
    names = [ev["name"] for ev in TRACER.events if ev.get("ph") == "X"]
    assert names.count("daemon.stage_exchange") == 1 == names.count("exchange.superstep")
    assert names.count("daemon.stage_wait") == daemon.stage_stats()["stage_waiters"]
    assert not any(name.startswith("daemon.fetch") for name in names)  # a span a frame: full tracing only


def test_a_fetch_with_a_map_uncommitted_answers_none_and_starts_nothing(daemon, groupbytest, monkeypatch):
    records = groupbytest.records(3)
    write_maps(daemon, 0, records, commit_last=False)
    calls = counting(daemon, monkeypatch)
    got = fetch_all(daemon, 0, records, connections=3)
    assert set(got.values()) == {None} and calls["begun"] == 0
    assert daemon.stage_stats()["stage_exchanges"] == 0
    with closing(DaemonClient(daemon.address)) as client:
        assert client.fetch_blocks([ShuffleBlockId(77, 0, 0)]) == [None]  # no such shuffle, as ever
        assert not client.stats(0)["exchanged"]


@pytest.mark.parametrize("first", ["run_exchange", "fetch"])
def test_run_exchange_racing_a_first_fetch_gives_one_exchange(daemon, groupbytest, monkeypatch, first):
    """Whichever of an explicit RunExchange and a first fetch claims the
    shuffle, the other waits for it: one exchange, the RunExchange acked ok,
    the fetch served."""
    records = groupbytest.records(3)
    write_maps(daemon, 0, records)
    release = threading.Event()
    calls = counting(daemon, monkeypatch, before=lambda sid: release.wait(TIMEOUT))
    out = {}

    def explicit():
        with closing(DaemonClient(daemon.address)) as client:
            out["explicit"] = client.run_exchange(0)

    def fetch():
        out["fetched"] = fetch_all(daemon, 0, records, connections=1)

    order = [explicit, fetch] if first == "run_exchange" else [fetch, explicit]
    threads = [threading.Thread(target=t, daemon=True) for t in order]
    threads[0].start()
    assert calls["entered"].wait(TIMEOUT)  # the first holds the shuffle's exchange
    threads[1].start()
    time.sleep(0.2)  # the second has arrived and waits
    release.set()
    for t in threads:
        t.join(TIMEOUT)
    assert not any(t.is_alive() for t in threads)
    assert calls["begun"] == calls["returned"] == 1
    assert "explicit" in out and out["fetched"] == written(records)
    # an explicit RunExchange is counted as the client's, a fetch's as the daemon's own
    assert daemon.stage_stats()["stage_exchanges"] == (0 if first == "run_exchange" else 1)
    with closing(DaemonClient(daemon.address)) as client:
        with pytest.raises(RuntimeError, match="already exchanged"):  # a second one, as before the guard
            client.run_exchange(0)


def test_a_failing_exchange_fails_its_waiters_and_hangs_nobody(daemon, groupbytest, monkeypatch):
    records = groupbytest.records(3)
    write_maps(daemon, 0, records)

    def fail(shuffle_id):
        time.sleep(0.2)
        raise TransportError("the chip went away")

    calls = counting(daemon, monkeypatch, before=fail)
    got = fetch_all(daemon, 0, records, connections=4)  # asserts that nobody hangs
    assert set(got.values()) == {None}
    assert calls["begun"] == 1 and calls["returned"] == 0
    stats = daemon.stage_stats()
    assert stats["stage_exchanges"] == 0 and 1 <= stats["stage_waiters"] <= 3
    # later fetches of the shuffle fail too, and start nothing
    assert set(fetch_all(daemon, 0, records, connections=2).values()) == {None} and calls["begun"] == 1
    # the connections and the daemon live on: another shuffle is served, and
    # an explicit RunExchange may still try the failed one again
    monkeypatch.undo()
    write_maps(daemon, 1, records)
    assert fetch_all(daemon, 1, records, connections=2) == written(records)
    with closing(DaemonClient(daemon.address)) as client:
        client.run_exchange(0)
    assert fetch_all(daemon, 0, records, connections=2) == written(records)


def test_a_run_exchange_sent_too_early_can_be_sent_again(daemon, groupbytest):
    """An explicit RunExchange that fails leaves no trace: not for a second
    one, and not for the stage boundary."""
    records = groupbytest.records(2)
    write_maps(daemon, 0, records, commit_last=False)
    with closing(DaemonClient(daemon.address)) as client:
        with pytest.raises(RuntimeError, match="before all maps committed"):
            client.run_exchange(0)
        writer = client.open_map_writer(0, records.num_mappers - 1)
        for r, payload in records.blocks[-1]:
            client.write_partition(writer, r, payload)
        client.commit_map(writer)
    assert fetch_all(daemon, 0, records, connections=2) == written(records)
    assert daemon.stage_stats()["stage_exchanges"] == 1


@pytest.mark.parametrize("daemon", [{"staging_capacity_per_executor": 8 << 20}], indirect=True)
def test_four_connections_write_through_a_round_that_rolls_over(daemon, switchy):
    """Five map tasks of 2.5 MB from four connections, interleaved block by
    block in an 8 MiB staging round that rolls over mid-job: byte-exact, and
    the wait for the store's lock is counted."""
    from benchmark.references import groupby

    records = groupby.make_records({"mappers": 5, "pairs_per_mapper": 100, "value_bytes": 25000,
                                    "reducers": 200, "keys": "uniform-int31"}, 31)
    store = daemon.manager.cluster.transports[0].store
    write_maps(daemon, 0, records, connections=4)
    stats = store.write_stats()
    assert stats["rollovers"] >= 1 and stats["staged_blocks"] == records.num_blocks
    assert stats["staged_bytes"] == records.total_bytes
    assert stats["lock_wait_ns"] > 0
    assert "sparkucx_tpu_store_lock_wait_ns_total" in daemon.manager.cluster.metrics_text()
    assert fetch_all(daemon, 0, records, connections=4) == written(records)
    assert daemon.stage_stats()["stage_exchanges"] == 1


def test_the_connection_gauge_follows_the_connections(daemon):
    clients = [DaemonClient(daemon.address) for _ in range(3)]
    for client in clients:
        client.metrics_text()  # served: the daemon has accepted it
    assert daemon.stage_stats()["connections"] == 3
    for client in clients:
        client.close()
    deadline = time.monotonic() + TIMEOUT
    while daemon.stage_stats()["connections"] and time.monotonic() < deadline:
        time.sleep(0.01)
    stats = daemon.stage_stats()
    assert stats["connections"] == 0 and stats["connections_peak"] == 3

"""Skewed keys on the served path: the gate job with Zipf-popular keys
(``benchmark/references/groupby-zipf.py``) through ``TpuShuffleManager`` on
four executors at 8 MiB staging — unequal blocks, peer regions that roll with
free tails, received shards of any length — is byte-exact against the plain
GroupBy in every host receive mode; what skew does to staging, the exchange
and the read is counted, once a round, a job or a window, and the counts are
what the blocks' sizes say; a block that outgrows a whole peer region is
staged in pieces and read back exact (``tests/test_block_over_region.py`` has
the paths); and a job with a failed map task ends non-zero, soon.

Sizes and counts on the CPU mesh; no rate."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.cells import ROOT, load_benchmark, load_module
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.utils.trace import TRACER

zipf = load_module("references", "groupby-zipf")

N = 4
STAGING = 8 << 20
ROW = 512  # the default block alignment: a staged row
REGION = STAGING // N
#: the gate job's record shapes at test size: 30 MB in 545 blocks of 25 KB to 700 KB
CONFIG = {"mappers": 4, "pairs_per_mapper": 300, "value_bytes": 25000, "reducers": 200,
          "keys": "zipf", "zipf_s": 0.99, "distinct_keys": 200_000}


@pytest.fixture(scope="module")
def records():
    return zipf.make_records(CONFIG, seed=3_000_000_017)


@pytest.fixture
def tracer():
    """The process-wide tracer, cleared; back to what it was afterwards."""
    enabled, recording = TRACER.enabled, TRACER.recording
    TRACER.clear()
    yield TRACER
    TRACER.enabled, TRACER.recording = enabled, recording
    TRACER.clear()


def staged(mgr, shuffle_id, records):
    """What the blocks' sizes say, by the store's rule and nothing of its
    code: a map task's blocks go to the region of the reducer's owner, in
    reducer order, each padded to whole rows; a region that cannot take the
    next block rolls the executor's round and its free tail is lost.  Per
    executor: rollovers, the tails summed, the largest block; and the job's
    (sender, destination) lanes in rows."""
    meta = mgr.cluster.meta(shuffle_id)
    rollovers, tails, largest = [0] * N, [0] * N, [0] * N
    lanes = np.zeros((N, N), dtype=np.int64)
    used = np.zeros((N, N), dtype=np.int64)
    for m, parts in enumerate(records.blocks):
        sender = meta.map_owner[m]
        for r, payload in parts:
            peer = meta.owner_of_reduce(r)
            padded = -(-len(payload) // ROW) * ROW
            if used[sender, peer] + padded > REGION:
                rollovers[sender] += 1
                tails[sender] += REGION - int(used[sender, peer])
                used[sender] = 0
            used[sender, peer] += padded
            lanes[sender, peer] += padded // ROW
            largest[sender] = max(largest[sender], len(payload))
    return rollovers, tails, largest, lanes


def read_and_check(mgr, shuffle_id, records):
    """Every reduce task against the plain GroupBy; returns each task's
    ``window_bytes_max``."""
    checks, window_bytes = [], []
    for r in range(records.reducers):
        check = records.check(r, full=True)
        reader = mgr.get_reader(shuffle_id, r, r + 1)
        for key, value in reader.read():
            check.add(key, value)
        assert check.ok(), f"reduce task {r} differs from the plain GroupBy"
        checks.append(check)
        window_bytes.append(reader.metrics.window_bytes_max)
    assert records.complete(checks)
    return window_bytes


@pytest.mark.parametrize("mode, keep_device", [("array", False), ("memmap", False), ("array", True)],
                         ids=["array", "memmap", "keep-device-recv"])
def test_a_zipf_job_is_exact_and_its_skew_is_counted(records, groupbytest, tracer, tmp_path, mode, keep_device):
    tracer.enabled = True
    conf = TpuShuffleConf(staging_capacity_per_executor=STAGING, host_recv_mode=mode,
                          keep_device_recv=keep_device, spill_dir=str(tmp_path))
    with TpuShuffleManager(conf, num_executors=N) as mgr:
        groupbytest.write_and_exchange(mgr, 0, records)
        rollovers, tails, largest, lanes = staged(mgr, 0, records)
        assert max(rollovers) >= 2 and min(tails) > 0, "the job takes several unequal rounds"
        window_bytes = read_and_check(mgr, 0, records)

        # map-side write: once a rollover, once a map task
        stores = [t.store.write_stats() for t in mgr.cluster.transports]
        assert [s["rollovers"] for s in stores] == rollovers
        assert [s["rollover_tail_bytes"] for s in stores] == tails
        assert [s["largest_block_bytes"] for s in stores] == largest
        assert max(largest) == zipf.geometry(CONFIG, N)["largest_block_bytes"]
        rolled = [e for e in tracer.events if e["ph"] == "X" and e["name"] == "store.rollover"]
        assert len(rolled) == sum(rollovers)
        assert sum(e["args"]["tail_bytes"] for e in rolled) == sum(tails)

        # the exchange: once a job, from the sealed size matrices
        plan = mgr.cluster.stats.counters("exchange.plan")
        assert plan["exchanges"] == 1
        assert [plan[f"recv_rows_e{j}"] for j in range(N)] == lanes.sum(axis=0).tolist()
        assert (plan["lane_rows_max"], plan["lane_rows_mean"]) == (int(lanes.max()), int(lanes.sum()) // N**2)
        [event] = [e for e in tracer.events if e["ph"] == "i" and e["name"] == "exchange.plan"]
        assert json.loads(event["args"]["recv_rows"]) == lanes.sum(axis=0).tolist()
        assert event["args"]["lane_rows_max"] == int(lanes.max())
        assert event["args"]["lane_rows_mean"] == pytest.approx(lanes.mean())
        received = zipf.geometry(CONFIG, N)["chip_received_bytes"]
        assert [int(np.argmax(received)), int(np.argmin(received))] == [
            int(np.argmax(lanes.sum(axis=0))), int(np.argmin(lanes.sum(axis=0)))]

        # the D2H: the prefix buckets move at least the rows used, at most the shards
        d2h = mgr.cluster.stats.counters("exchange.d2h")
        assert d2h["used_bytes"] == int(lanes.sum()) * ROW
        assert records.total_bytes <= d2h["used_bytes"] < d2h["moved_bytes"] < d2h["shard_bytes"]
        assert d2h["moved_bytes"] < 2 * d2h["used_bytes"] + d2h["shard_bytes"] // 16
        assert d2h["sliced_shards"] > 0 and d2h["skipped_shards"] > 0

        # the read: a window is cut by count, its bytes are the reducer's
        width = zipf.record_bytes(CONFIG["value_bytes"])
        assert window_bytes == [n * width for n, _, _ in records.expected]
        windows = [e for e in tracer.events if e["ph"] == "X" and e["name"] == "read.window"]
        assert len(windows) == sum(n > 0 for n, _, _ in records.expected)
        assert sorted(e["args"]["bytes"] for e in windows) == sorted(b for b in window_bytes if b)
        assert max(window_bytes) > 20 * np.median(window_bytes)
        mgr.unregister_shuffle(0)


def test_the_counters_fire_once_a_round_a_job_or_a_window(records, groupbytest, monkeypatch):
    """Never once a block: the sites are a rollover, a commit, an exchange, a
    sub-round's D2H and a window's issue."""
    conf = TpuShuffleConf(staging_capacity_per_executor=STAGING)
    with TpuShuffleManager(conf, num_executors=N) as mgr:
        calls = []
        real = mgr.cluster.stats.record_counters
        monkeypatch.setattr(mgr.cluster.stats, "record_counters",
                            lambda kind, **counters: (calls.append(kind), real(kind, **counters))[1])
        for shuffle_id in range(2):
            groupbytest.write_and_exchange(mgr, shuffle_id, records)
        rounds = len(mgr.cluster.meta(0).recv_sizes)
        assert calls.count("exchange.plan") == 2
        assert calls.count("exchange.d2h") == 2 * rounds and calls.count("exchange.assemble") == 2 * rounds
        assert len(calls) == 2 + 4 * rounds
        plan = mgr.cluster.stats.counters("exchange.plan")
        lanes = staged(mgr, 0, records)[3]
        assert plan["exchanges"] == 2 and plan["lane_rows_max"] == 2 * int(lanes.max())
        for shuffle_id in range(2):
            mgr.unregister_shuffle(shuffle_id)


#: the law raised until one (map, reduce) block outgrows a whole 2 MiB region
STEEP = {**CONFIG, "zipf_s": 3.0}


def test_a_block_over_a_whole_region_is_staged_in_pieces_and_read_back_exact(groupbytest):
    made = zipf.make_records(STEEP, seed=7)
    m, (hot, payload) = max(((m, max(parts, key=lambda p: len(p[1]))) for m, parts in enumerate(made.blocks)),
                            key=lambda found: len(found[1][1]))
    assert len(payload) > REGION
    over = [len(p) for parts in made.blocks for _, p in parts if len(p) > REGION]
    conf = TpuShuffleConf(staging_capacity_per_executor=STAGING)
    with TpuShuffleManager(conf, num_executors=N) as mgr:
        groupbytest.write_and_exchange(mgr, 0, made)  # no limit at ``write``: the close stages the block, a region a round
        store = mgr.cluster.transport(mgr.cluster.meta(0).map_owner[m]).store
        entry = store._state(0).blocks[(m, hot)]
        assert len(entry.pieces) >= -(-len(payload) // REGION) and sum(n for _, _, n in entry.pieces) == len(payload)
        stats = [t.store.write_stats() for t in mgr.cluster.transports]
        assert sum(s["split_blocks"] for s in stats) == len(over) and sum(s["split_bytes"] for s in stats) == sum(over)
        assert store.write_stats()["largest_block_bytes"] == len(payload)
        read_and_check(mgr, 0, made)
        mgr.unregister_shuffle(0)


def test_rehearsal_of_a_job_with_a_failed_map_task_exits_nonzero_and_does_not_hang(tmp_path):
    """``run.py`` on a job one of whose map tasks cannot be written — a region
    of shared-memory staging overflows, and shm staging has no next round
    (a block over a region was the refusal here until it was staged in
    pieces): the map task fails typed, the exchange refuses a job with an
    uncommitted map, and the process ends non-zero within seconds — no
    result line."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmark", "configs", "groupbytest-25k-zipf-4chip.json")) as f:
        config = json.load(f)
    config.update(zipf_s=3.0, rehearse={"mappers": 4, "pairs_per_mapper": 300,
                                        "conf": {"staging_capacity_per_executor": STAGING, "use_shm_staging": True,
                                                 "shm_namespace": f"sparkucx_tpu_test_{os.getpid()}"}})
    (tmp_path / "benchmark" / "configs" / "steep.json").write_text(json.dumps(config))
    bench = load_benchmark()
    bench["configs"].append({"name": "steep", "source": config["source"], "file": "benchmark/configs/steep.json",
                             "reduced": ["mappers"], "why": "x"})
    bench["workloads"].append({"name": "steep-4chip", "config": "steep", "traffic": "manager-jobs",
                               "chips": 4, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    env.pop("XLA_FLAGS", None)
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "steep-4chip",
         "--seed", "3", "--seconds", "0.3", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert out.returncode not in (0, 4), out.stdout[-2000:]
    assert time.monotonic() - t0 < 60
    assert "region overflow with shm staging" in out.stdout and "raise stagingCapacity" in out.stdout
    assert "before all maps committed" in out.stderr
    assert not out.stdout.strip().splitlines()[-1].startswith("{"), "no result line"

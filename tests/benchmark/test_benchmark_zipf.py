"""The Zipf-keyed GroupByTest records (``references/groupby-zipf.py``): the
generator against a plain GroupBy written here, the law it draws from, the
geometry its configuration states, and that the comparison which decides
``correct`` notices a corrupted byte, a duplicated record and a misplaced
record on skewed blocks too."""

import json
import os
import zlib

import numpy as np
import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell, load_module, reader
from benchmark.jobs import JobResult, run_window
from benchmark.measured import Run
from sparkucx_tpu.shuffle.reader import default_deserializer
from test_benchmark_oracle import MemoryEntry  # a plain shuffle in a dict, with a hook to damage a read

zipf = load_module("references", "groupby-zipf")

CELL = "gbt25k-zipf-4chip"
#: ten keys a reducer, and a law steep enough that one reducer's blocks stand out
CONFIG = {"mappers": 3, "pairs_per_mapper": 80, "value_bytes": 64, "reducers": 7,
          "keys": "zipf", "zipf_s": 0.99, "distinct_keys": 70}
WIDTH = zipf.record_bytes(CONFIG["value_bytes"])
SEEDS = (11, 12, 3_000_000_019)  # the driver's seeds pass 2**31


def plain_groupby(blocks, reducers):
    """What any shuffle of ``blocks`` must hand its reduce tasks, by a dict of
    lists over the decoded records: per reducer the records, the value bytes
    and the sum of every value's first eight bytes, per key the crc32 of
    every value; and the records found in a block they do not hash to."""
    per_reducer = [[0, 0, 0] for _ in range(reducers)]
    groups, misplaced = {}, 0
    for parts in blocks:
        for reduce_id, payload in parts:
            for key, value in default_deserializer(payload):
                misplaced += key % reducers != reduce_id
                tally = per_reducer[reduce_id]
                tally[0] += 1
                tally[1] += len(value)
                tally[2] = (tally[2] + int.from_bytes(value[:8], "little")) % 2**64
                groups.setdefault(key, []).append(zlib.crc32(value))
    return [tuple(t) for t in per_reducer], groups, misplaced


def shape(records):
    return [[(r, len(p)) for r, p in parts] for parts in records.blocks]


@pytest.fixture(scope="module")
def records():
    return zipf.make_records(CONFIG, seed=SEEDS[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_generator_agrees_with_a_plain_groupby(seed):
    made = zipf.make_records(CONFIG, seed)
    per_reducer, groups, misplaced = plain_groupby(made.blocks, CONFIG["reducers"])
    assert misplaced == 0, "key mod reducers holds for every record"
    assert made.expected == per_reducer
    assert {k: sorted(v) for k, v in made.groups.items()} == {k: sorted(v) for k, v in groups.items()}
    assert sum(n for n, _, _ in made.expected) == CONFIG["mappers"] * CONFIG["pairs_per_mapper"]
    assert made.total_bytes == CONFIG["mappers"] * CONFIG["pairs_per_mapper"] * WIDTH
    assert all(0 <= key < 2**31 - 1 for key in made.groups)
    # parts in reducer order, no empty block: what a map task's writer is given
    for parts in made.blocks:
        ids = [r for r, _ in parts]
        assert ids == sorted(set(ids)) and all(len(p) % WIDTH == 0 and p for _, p in parts)


def test_every_seed_stages_the_same_blocks_and_keeps_the_groups(records):
    again = zipf.make_records(CONFIG, SEEDS[0])
    assert again.blocks == records.blocks and again.expected == records.expected
    stride = 70  # the least multiple of 7 reducers that holds 70 key ids
    for seed in SEEDS[1:]:
        other = zipf.make_records(CONFIG, seed)
        assert other.blocks != records.blocks and shape(other) == shape(records)
        # other keys and values, the same groups: a key id keeps its records
        sizes = lambda recs: sorted((key % stride, len(crcs)) for key, crcs in recs.groups.items())
        assert sizes(other) == sizes(records)
        assert set(other.groups) != set(records.groups)
    lengths = sorted(len(p) for parts in records.blocks for _, p in parts)
    assert lengths[-1] >= 5 * lengths[0], "one reducer's blocks stand out"


def test_the_law_is_zipf_over_the_universe():
    """Rank 1's share of the cell's own 40,000 draws against 1 / H(N, s); the
    ranks reach far into the universe; one permutation carries them to key
    ids, so the popular keys fall on arbitrary reducers."""
    config = load_cell(CELL).config
    assert (config["keys"], config["zipf_s"], config["distinct_keys"]) == ("zipf", 0.99, 200_000)
    ranks = np.concatenate([zipf.draw_ranks(config, m) for m in range(config["mappers"])])
    assert len(ranks) == 40_000 and ranks.min() == 0 and ranks.max() < 200_000
    top = 1 / zipf.harmonic(200_000, 0.99)
    assert top == pytest.approx(0.0737531, rel=1e-5)
    assert np.mean(ranks == 0) == pytest.approx(top, rel=0.05)
    assert np.mean(ranks == 1) == pytest.approx(top / 2**0.99, rel=0.08)
    assert len(np.unique(ranks)) > 10_000 and np.mean(ranks >= 1_000) > 0.3
    key_ids, bounds = zipf.layout(config, 0)
    assert np.all(np.diff(key_ids % config["reducers"]) >= 0) and bounds[-1] == config["pairs_per_mapper"]
    _, permutation = zipf._law(200_000, 0.99)
    assert sorted(permutation.tolist()) == list(range(200_000))
    hot = [int(permutation[k]) % config["reducers"] for k in range(8)]
    assert len(set(hot)) >= 6 and hot != sorted(hot)


def test_an_unknown_law_is_refused():
    for wrong in ({"keys": "uniform-int31"}, {"zipf_s": 0}, {"distinct_keys": 3}):
        with pytest.raises(ValueError):
            zipf.make_records({**CONFIG, **wrong}, 1)
    with pytest.raises(ValueError, match="unknown key distribution"):
        load_module("references", "groupby").make_records(CONFIG, 1)  # the accepted generator


def test_the_configuration_states_the_generators_geometry():
    """The file's ``geometry`` block is ``geometry(config, chips)``, and the
    job is the sibling's but for the key law."""
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = load_cell(CELL).config
    stated = dict(config["geometry"])
    stated.pop("from")
    region = stated.pop("largest_block_share_of_a_16MiB_region")
    made = zipf.geometry(config, cell["chips"])
    assert stated == made
    assert region == pytest.approx(made["largest_block_bytes"] / (64 << 20) * cell["chips"])
    assert made["hottest_over_median"] > 20 and made["largest_block_bytes"] < (64 << 20) // cell["chips"]
    assert sum(made["chip_received_bytes"]) == made["job_bytes"] == 8 * 5000 * 25019
    assert max(made["chip_received_bytes"]) > 1.25 * min(made["chip_received_bytes"])
    with open(os.path.join(ROOT, "benchmark", "configs", "groupbytest-25k-4chip.json")) as f:
        sibling = json.load(f)
    same = ("mappers", "pairs_per_mapper", "value_bytes", "reducers", "partitioner", "conf", "guarantees",
            "rehearse")
    assert all(config[key] == sibling[key] for key in same)
    assert (cell["traffic"], cell["chips"], list(config["reduced"])) == ("manager-jobs", 4, ["mappers"])


def hottest(shuffle):
    """The reducer that holds the popular key: the damage goes where the
    blocks are longest."""
    return max(shuffle, key=lambda r: sum(len(p) for p in shuffle[r]))


def corrupt_lead_byte(shuffle, reduce_id, payloads):
    if reduce_id == hottest(shuffle):
        damaged = bytearray(payloads[0])
        damaged[zipf.HEADER_BYTES] ^= 0x01  # first byte of the first value
        payloads[0] = bytes(damaged)
    return payloads


def duplicate_record(shuffle, reduce_id, payloads):
    if reduce_id == hottest(shuffle):
        payloads.append(payloads[0][:WIDTH])
    return payloads


def misplace_record(shuffle, reduce_id, payloads):
    # the hot reducer's first record surfaces in the next reducer instead
    hot = hottest(shuffle)
    if reduce_id == hot:
        payloads[0] = payloads[0][WIDTH:]
    elif reduce_id == (hot + 1) % len(shuffle):
        payloads.append(shuffle[hot][0][:WIDTH])
    return payloads


@pytest.mark.parametrize("damage", [corrupt_lead_byte, duplicate_record, misplace_record],
                         ids=["corrupted-byte", "duplicated-record", "misplaced-record"])
def test_damage_to_a_skewed_job_shows_in_the_window(records, damage):
    """What run.py turns into ``correct: false`` and ``failed`` > 0."""
    quiet = lambda event, **fields: {}
    window = run_window(MemoryEntry(damage), records, seconds=0.05, trace=False, control=quiet)
    assert window.warmup.failed >= 1
    assert window.jobs and all(job.failed >= 1 for job in window.jobs)
    assert not window.sound()
    sound = run_window(MemoryEntry(), records, seconds=0.05, trace=False, control=quiet)
    assert sound.sound() and sum(job.failed for job in sound.jobs) == 0


def test_the_three_readers_on_a_run_made_up_by_hand():
    """``read_task_max_ms``, ``read_window_fetch_max_us`` and
    ``d2h_wait_s_per_job``: a job's slowest task, its longest window fetch and
    its seconds of D2H wait, each the median over the jobs; nothing where
    nothing was recorded."""
    ms = 1_000_000
    jobs = [JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001, 0.030, 0.002]),
            JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001, 0.002, 0.040]),
            JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.050, 0.002, 0.001])]
    spans = [("job.exchange", 0, 100 * ms), ("job.read", 100 * ms, 200 * ms),
             ("job.exchange", 200 * ms, 300 * ms), ("job.read", 300 * ms, 400 * ms)]
    program = [
        ("exchange.assemble", 1 * ms, 2 * ms),
        ("exchange.d2h", 10 * ms, 30 * ms), ("exchange.d2h", 40 * ms, 50 * ms),  # 30 ms in the first job
        ("exchange.d2h", 210 * ms, 220 * ms),  # 10 ms in the second
        ("exchange.d2h", 401 * ms, 402 * ms),  # in no job's exchange
        ("read.window.fetch", 110 * ms, 112 * ms), ("read.window.fetch", 120 * ms, 129 * ms),
        ("read.window.fetch", 310 * ms, 315 * ms), ("read.window.fetch", 320 * ms, 321 * ms),
        ("read.window.fetch", 500 * ms, 599 * ms),  # outside every job.read
    ]
    fields = dict(chips=4, device_kind="TPU v5 lite", setup_s=20.0, job_bytes=10**9, jobs=jobs, spans=spans,
                  rounds=[13, 13, 13], stats_before={}, stats_after={}, fetch_faults=0)
    run = Run(program_spans=program, **fields)
    assert reader("layer_metrics", "read_task_max_ms")(run) == pytest.approx(40.0)
    assert reader("layer_metrics", "read_window_fetch_max_us")(run) == pytest.approx((9000 + 5000) / 2)
    assert reader("layer_metrics", "d2h_wait_s_per_job")(run) == pytest.approx((0.030 + 0.010) / 2)
    untraced = Run(**dict(fields, jobs=[]))
    for name in ("read_task_max_ms", "read_window_fetch_max_us", "d2h_wait_s_per_job"):
        assert reader("layer_metrics", name)(untraced) is None, name
    bench = load_benchmark()
    for name in ("read_task_max_ms", "read_window_fetch_max_us", "d2h_wait_s_per_job"):
        [metric] = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(metric["workloads"]) >= {"gbt25k-jobs-4chip", CELL} and metric["moves"] == "shuffle_throughput"

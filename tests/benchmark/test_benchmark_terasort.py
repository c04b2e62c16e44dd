"""The TeraSort records (``references/terasort.py``): the generator against a
plain sort written here, the range rule, the geometry its configuration
states, the three readers of the cell, and that the comparison which decides
``correct`` notices a dropped record, a duplicated record, a neighbour's
record and a flipped byte.

The controls of ``ts10gb-batchjobs-1chip``: the cell's own job with one of its
guarantees broken — a record a timed reduce task never hands on, a record
handed to the task of the neighbouring range, a byte flipped past a record's
first 18 — through ``run.py`` itself in a copy of the benchmark with a
throw-away driver (data and a driver added, nothing edited).  As tests they
run the CPU form; on the chip this file is a program that runs them at the
cell's own size (``python3 tests/benchmark/test_benchmark_terasort.py --seed
<n> --seconds <s>``) and exits 0 only if every one came out as not correct."""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import numpy as np
import pytest

from benchmark.cells import load_benchmark, load_cell, load_module, reader
from benchmark.jobs import JobResult, run_window
from benchmark.measured import Run

terasort = load_module("references", "terasort")

CELL = "ts10gb-batchjobs-1chip"
#: the published shapes (100 B records, 10 B keys, the range rule) at a few records
CONFIG = {"mappers": 3, "records_per_mapper": 700, "record_bytes": 100, "key_bytes": 10, "reducers": 7,
          "keys": "uniform-bytes"}
SEEDS = (11, 12, 3_000_000_019)  # the driver's seeds pass 2**31


def rows_of(payload):
    return np.frombuffer(payload, dtype=np.uint8).reshape(-1, CONFIG["record_bytes"])


def plain_sort(records):
    """Every record of the job as ``bytes``, in Python's own order of byte
    strings (unsigned, most significant first; the key leads the record)."""
    return sorted(bytes(row) for parts in records.blocks for _, payload in parts for row in rows_of(payload))


@pytest.fixture(scope="module")
def records():
    return terasort.make_records(CONFIG, seed=SEEDS[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_partitions_in_order_are_the_plain_sort(seed):
    """The range rule and the sort agree: the reference's slices, one after
    another in reducer order, are all the job's records sorted."""
    made = terasort.make_records(CONFIG, seed)
    slices = [made.sorted_partition(r) for r in range(CONFIG["reducers"])]
    assert b"".join(s.tobytes() for s in slices) == b"".join(plain_sort(made))
    assert [len(s) for s in slices] == [n for n, _, _ in made.expected]
    assert made.total_records == CONFIG["mappers"] * CONFIG["records_per_mapper"]
    assert made.total_bytes == made.total_records * 100
    for r, (n, nbytes, digest) in enumerate(made.expected):
        rows = made.rows_of(r)
        assert (len(rows), rows.size) == (n, nbytes)
        assert digest == sum(int.from_bytes(bytes(row[10:18]), "little") for row in rows) % 2**64
        lo, hi = terasort.range_of(r, CONFIG["reducers"])
        assert all(lo <= int.from_bytes(bytes(row[:7]), "big") < hi for row in rows)
    assert made.checksum == sum(
        int.from_bytes(rec[i : i + 4], "little") for rec in plain_sort(made) for i in range(0, 100, 4)) % 2**64
    # parts in reducer order, no empty block, whole records: what a map task's writer is given
    for parts in made.blocks:
        ids = [r for r, _ in parts]
        assert ids == sorted(set(ids)) and all(p and len(p) % 100 == 0 for _, p in parts)


def test_the_range_rule_is_the_partitioners():
    """prefix / ((2^56 - 1) / reducers), kept below ``reducers``."""
    step = (2**56 - 1) // 75
    assert terasort.range_step(75) == step
    prefix = np.array([0, step - 1, step, 74 * step, 75 * step - 1, 75 * step, 2**56 - 1], dtype=np.uint64)
    assert terasort.partition_of(prefix, 75).tolist() == [0, 0, 1, 74, 74, 74, 74]
    assert terasort.range_of(0, 75) == (0, step) and terasort.range_of(74, 75) == (74 * step, 2**56)
    keys = np.array([[0xFF] * 7 + [0, 1, 2] + [9] * 90, [0] * 6 + [1, 0xFF, 0xFF, 0xFF] + [7] * 90], dtype=np.uint8)
    assert terasort.prefixes(keys).tolist() == [2**56 - 1, 1]


def test_the_comparator_orders_whole_records_as_unsigned_bytes():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, size=(500, 100), dtype=np.uint8)
    rows[:40, :10] = rows[40:80, :10]  # equal keys: the value's bytes decide
    rows[100:120, :8] = rows[120:140, :8]  # equal first eight bytes, other keys
    rows[200] = 0
    rows[201] = 0
    rows[201, 99] = 1  # they differ in the last byte alone
    want = sorted(bytes(row) for row in rows)
    assert [bytes(row) for row in terasort.sort_records(rows)] == want
    plain = rng.integers(0, 256, size=(300, 100), dtype=np.uint8)
    assert [bytes(row) for row in terasort.sort_records(plain)] == sorted(bytes(row) for row in plain)


def test_every_seed_stages_the_same_blocks(records):
    again = terasort.make_records(CONFIG, SEEDS[0])
    assert again.blocks == records.blocks and again.expected == records.expected
    shape = lambda recs: [[(r, len(p)) for r, p in parts] for parts in recs.blocks]
    for seed in SEEDS[1:]:
        other = terasort.make_records(CONFIG, seed)
        assert other.blocks != records.blocks and shape(other) == shape(records)
        # the 7-byte prefixes are the layout's, the rest of a record the seed's
        assert all(np.array_equal(rows_of(a)[:, :7], rows_of(b)[:, :7])
                   for pa, pb in zip(other.blocks, records.blocks) for (_, a), (_, b) in zip(pa, pb))
    keys = np.concatenate([rows_of(p)[:, :10] for parts in records.blocks for _, p in parts])
    assert len(np.unique(keys[:, 7:], axis=0)) > 0.9 * len(keys)


def test_an_unknown_shape_is_refused():
    for wrong in ({"keys": "uniform-int31"}, {"key_bytes": 7}, {"record_bytes": 16}, {"record_bytes": 102}):
        with pytest.raises(ValueError):
            terasort.make_records({**CONFIG, **wrong}, 1)


def test_the_configuration_states_the_source_and_the_generators_geometry():
    """The published shapes uncut, the cut, ``geometry(config, 1)`` and the
    store the geometry is computed for: the program's default conf."""
    from sparkucx_tpu.config import TpuShuffleConf

    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = load_cell(CELL).config
    assert (config["record_bytes"], config["key_bytes"], config["records_per_mapper"], config["reducers"]) == (
        100, 10, (128 << 20) // 100, 75)
    assert (config["mappers"], list(config["reduced"]), config["conf"]) == (-(-75 // 4), ["mappers"], {})
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("terasort-10gb-1of4", "manager-batchjobs", 1)
    stated = dict(config["geometry"])
    stated.pop("from")
    assert stated == terasort.geometry(config, cell["chips"])
    assert stated["job_bytes"] == 19 * 1_342_177 * 100 == 2_550_136_300 and stated["records"] == 25_501_363
    assert (stated["blocks"], stated["rounds"], stated["rollovers_to_disk"]) == (19 * 75, 39, 6)
    default = TpuShuffleConf()
    assert config["store"] == {"staging_bytes": default.staging_capacity_per_executor,
                               "alignment": default.block_alignment,
                               "ram_budget_bytes": default.max_host_pool_bytes}
    # the CPU form keeps the shapes and crosses the disk tier too
    tiny = load_cell(CELL, rehearse=True).config
    assert terasort.geometry({**tiny, "store": {
        "staging_bytes": tiny["conf"]["staging_capacity_per_executor"], "alignment": default.block_alignment,
        "ram_budget_bytes": tiny["conf"]["max_host_pool_bytes"]}}, 1)["rollovers_to_disk"] >= 1


class MemoryEntry:
    """A plain shuffle in a dict whose reduce side hands out batches, with a
    hook to damage what a reducer reads."""

    def __init__(self, damage=None):
        self.shuffles = {}
        self.damage = damage

    def create(self, shuffle_id, mappers, reducers):
        self.shuffles[shuffle_id] = {r: [] for r in range(reducers)}

    def write_map(self, shuffle_id, map_id, parts):
        for reduce_id, payload in parts:
            self.shuffles[shuffle_id][reduce_id].append(payload)

    def exchange(self, shuffle_id):
        pass

    def read(self, shuffle_id, reduce_id, mappers, consume):
        batches = [rows_of(p) for p in self.shuffles[shuffle_id][reduce_id]]
        if self.damage is not None:
            batches = self.damage(self.shuffles[shuffle_id], reduce_id, batches)
        for batch in batches:
            consume(batch)
        return 0

    def remove(self, shuffle_id):
        del self.shuffles[shuffle_id]


def drop_record(shuffle, reduce_id, batches):
    if reduce_id == 2:
        batches[0] = batches[0][:-1]
    return batches


def duplicate_record(shuffle, reduce_id, batches):
    if reduce_id == 2:
        batches.append(batches[0][:1])
    return batches


def neighbours_record(shuffle, reduce_id, batches):
    # reducer 2's first record surfaces in reducer 3 instead
    if reduce_id == 2:
        batches[0] = batches[0][1:]
    elif reduce_id == 3:
        batches.append(rows_of(shuffle[2][0])[:1])
    return batches


def flip_late_byte(shuffle, reduce_id, batches):
    if reduce_id == 2:
        damaged = batches[0].copy()
        damaged[0, 18] ^= 0x01  # the first byte past the key and the eight the timed check sums
        batches[0] = damaged
    return batches


@pytest.mark.parametrize("damage, timed_sees_it", [
    (drop_record, True), (duplicate_record, True), (neighbours_record, True), (flip_late_byte, False)],
    ids=["dropped-record", "duplicated-record", "neighbours-record", "byte-past-the-lead-18"])
def test_damage_shows_in_the_window(records, damage, timed_sees_it):
    """What run.py turns into ``correct: false``.  A byte past a record's
    first 18 is seen by the warm-up job alone (its batches sorted and held
    byte for byte against the plain TeraSort), never by a timed task: the
    timed check is records, bytes, the key's range and the 8 bytes after the
    key."""
    quiet = lambda event, **fields: {}
    window = run_window(MemoryEntry(damage), records, seconds=0.05, trace=False, control=quiet)
    assert window.warmup.failed >= 1 and not window.sound()
    assert window.jobs and all((job.failed >= 1) == timed_sees_it for job in window.jobs)
    sound = run_window(MemoryEntry(), records, seconds=0.05, trace=False, control=quiet)
    assert sound.sound() and sum(job.failed for job in sound.jobs) == 0


def test_teravalidate_sees_what_every_task_alone_would_pass(records):
    """Each task ``ok`` against its own slice and the job still wrong: a
    reference whose count or checksum differs fails ``complete``."""
    checks = [records.check(r, full=True) for r in range(records.reducers)]
    for r, check in enumerate(checks):
        for parts in records.blocks:
            for reduce_id, payload in parts:
                if reduce_id == r:
                    check.add(rows_of(payload))
    assert all(c.ok() for c in checks) and records.complete(checks)
    assert all(a.largest < b.smallest for a, b in zip(checks, checks[1:]))
    assert not records.complete(checks[:-1])  # a partition never read: the count
    checks[1].checksum ^= 1
    assert not records.complete(checks)
    checks[1].checksum ^= 1
    checks[1].smallest, checks[0].largest = checks[0].largest, checks[1].smallest
    assert not records.complete(checks)  # partitions out of order


def test_the_three_readers_on_a_run_made_up_by_hand():
    """``read_batches_task_p50_us``, ``submit_h2d_disk_ms_per_round`` and
    ``spilled_rounds_per_job``: the medians of the two spans and of the
    ``store.spill`` spans that begin in each job's write; nothing where
    nothing was recorded."""
    ms = 1_000_000
    jobs = [JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])] * 3
    spans = [("job.write", 0, 100 * ms), ("job.write", 200 * ms, 300 * ms), ("job.write", 400 * ms, 500 * ms)]
    program = [
        ("exchange.assemble", 1 * ms, 2 * ms),
        ("store.spill", 10 * ms, 30 * ms), ("store.spill", 40 * ms, 50 * ms),  # two in the first job
        ("store.spill", 210 * ms, 220 * ms), ("store.spill", 230 * ms, 240 * ms),
        ("store.spill", 250 * ms, 260 * ms), ("store.spill", 290 * ms, 310 * ms),  # four in the second
        ("store.spill", 350 * ms, 360 * ms),  # in no job's write; none in the third
        ("exchange.h2d.disk", 110 * ms, 112 * ms), ("exchange.h2d.disk", 120 * ms, 126 * ms),
        ("exchange.h2d.disk", 130 * ms, 133 * ms),
        ("read.batches", 150 * ms, 150 * ms + 400_000), ("read.batches", 160 * ms, 160 * ms + 200_000),
    ]
    fields = dict(chips=1, device_kind="TPU v5 lite", setup_s=50.0, job_bytes=10**9, jobs=jobs, spans=spans,
                  rounds=[39, 39, 39], stats_before={}, stats_after={}, fetch_faults=0)
    run = Run(program_spans=program, **fields)
    assert reader("layer_metrics", "spilled_rounds_per_job")(run) == 2
    assert reader("layer_metrics", "submit_h2d_disk_ms_per_round")(run) == pytest.approx(3.0)
    assert reader("layer_metrics", "read_batches_task_p50_us")(run) == pytest.approx(300.0)
    on_ram = Run(program_spans=program[:1], **fields)  # traced, and every round stayed in RAM
    assert reader("layer_metrics", "spilled_rounds_per_job")(on_ram) == 0
    assert reader("layer_metrics", "submit_h2d_disk_ms_per_round")(on_ram) is None
    untraced = Run(**fields)
    names = ("read_batches_task_p50_us", "submit_h2d_disk_ms_per_round", "spilled_rounds_per_job")
    assert all(reader("layer_metrics", name)(untraced) is None for name in names)
    for name in names:
        [metric] = [m for m in load_benchmark()["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"] and metric["moves"] == "shuffle_throughput"


def test_a_program_without_the_batch_read_is_refused(monkeypatch):
    """The parent commit under this benchmark: out at ``start``, before a
    record is made — never a hang, never failing tasks that read as speed."""
    from sparkucx_tpu.shuffle import reader as program

    driver = load_module("traffic", "manager-batchjobs")
    assert driver.require_batch_read() is program.FixedWidthSerializer
    monkeypatch.delattr(program.TpuShuffleReader, "read_batches")
    with pytest.raises(SystemExit, match="needs"):
        driver.Traffic(load_cell(CELL, rehearse=True), None).start(None, {})


# -- the cell through run.py: the rehearsal's disk tier, and the controls ------

DAMAGED = "ts10gb-batchjobs-damaged-1chip"
#: the reduce task whose first batch is damaged (and, for the neighbour's
#: record, the task after it that is handed the record)
DAMAGED_TASK = 3
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-batchjobs`` whose reduce task %d is handed
other records than were written, as the traffic file's ``damage`` says:
``dropped`` (its first batch lacks its last record) and ``neighbour`` (that
record goes to the next task instead) in every timed job — the warm-up job,
shuffle 0, is left whole, so it is the window's comparison that has to notice
— and ``flipped`` (one byte past a record's first 18, in every job: only the
warm-up job's comparison with the plain TeraSort can notice)."""

import numpy as np

from benchmark.cells import load_module
from benchmark.jobs import run_window

shipped = load_module("traffic", "manager-batchjobs")
TASK = %d


class Entry(shipped.Entry):
    def __init__(self, manager, serializer, damage):
        super().__init__(manager, serializer)
        self.damage = damage
        self.taken = None

    def read(self, shuffle_id, reduce_id, mappers, consume):
        first = [True]

        def damaged(batch):
            if first[0]:
                first[0] = False
                if self.damage == "flipped" and reduce_id == TASK:
                    batch = batch.copy()
                    batch[0, 50] ^= 0x01
                elif shuffle_id > 0 and self.damage in ("dropped", "neighbour") and reduce_id == TASK:
                    self.taken, batch = batch[-1:], batch[:-1]
                elif shuffle_id > 0 and self.damage == "neighbour" and reduce_id == TASK + 1:
                    batch = np.concatenate([batch, self.taken])
            consume(batch)

        return super().read(shuffle_id, reduce_id, mappers, damaged)


class Traffic(shipped.Traffic):
    def run(self, control, parts):
        config = self.cell.config
        serializer = self.serializer_class(config["record_bytes"], config["key_bytes"])
        entry = Entry(self.manager, serializer, self.cell.traffic["damage"])
        return run_window(entry, self.records, self.args.seconds, bool(self.args.trace), control)
''' % (DAMAGED_TASK, DAMAGED_TASK)
#: damage -> (failed tasks of the warm-up job, failed tasks a timed job)
CONTROLS = {"dropped": (0, 1), "neighbour": (0, 2), "flipped": (1, 0)}


def run_py(root, cell, seed, seconds, trace, rehearse, **env):
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=900)


def run_a_control(root, damage, seed, seconds, rehearse, **env):
    """``run.py`` on the damaged cell in a copy of the benchmark under
    ``root``; returns the finished process."""
    shutil.rmtree(os.path.join(root, "benchmark"), ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, "manager-batchjobs-damaged.json"), "w") as f:
        json.dump({"driver": "manager-batchjobs-damaged", "damage": damage}, f)
    with open(os.path.join(traffic, "manager-batchjobs-damaged.py"), "w") as f:
        f.write(DAMAGED_DRIVER)
    bench = load_benchmark()
    bench["workloads"].append({"name": DAMAGED, "config": "terasort-10gb-1of4",
                               "traffic": "manager-batchjobs-damaged", "chips": 1, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def lines_of(out):
    lines = out.stdout.strip().splitlines()
    found = lambda label: json.loads(next(l for l in lines if l.startswith(label + ": ")).split(": ", 1)[1])
    return json.loads(lines[-1]), found


def verdict(out, damage):
    """(the control came out as not correct for the reason planted, its last
    line, its ``window:`` line)."""
    last, found = lines_of(out)
    window = found("window")
    in_warmup, in_a_job = CONTROLS[damage]
    caught = (out.returncode == 0 and last["correct"] is False and window["jobs"] >= 1
              and window["warmup_failed_tasks"] == in_warmup and last["failed"] == in_a_job * window["jobs"])
    return caught, last, window


@pytest.mark.parametrize("damage", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_for_damaged_batches_too(tmp_path, damage):
    out = run_a_control(str(tmp_path), damage, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window = verdict(out, damage)
    assert caught, (last, window["warmup_failed_tasks"], window["jobs"])
    assert f"reduce task {DAMAGED_TASK} " not in out.stdout + out.stderr  # no task raised: the comparison found it


def test_rehearsal_of_the_terasort_cell_crosses_the_disk_tier(tmp_path):
    """The traced CPU run: rounds on both tiers in every job, each block read
    as one borrowed batch, the three readers and the accepted spill metric
    report."""
    out = run_py(ROOT, CELL, 3_000_000_019, 0.5, 1, True,
                 JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = lines_of(out)
    assert last["correct"] is True and last["failed"] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    assert metrics["spilled_rounds_per_job"] >= 1 and metrics["write_spill_s_per_job"] > 0
    assert metrics["staging_rounds_per_job"] > metrics["spilled_rounds_per_job"] + 1  # and rounds that stayed in RAM
    assert metrics["read_batches_task_p50_us"] > 0 and metrics["submit_h2d_disk_ms_per_round"] > 0
    read = found("batchread")
    tiny = load_cell(CELL, rehearse=True).config
    assert read["records_a_job"] == tiny["mappers"] * tiny["records_per_mapper"]
    assert read["record_batches"] == read["resident_blocks"] == read["remote_blocks_fetched"] > 0
    assert read["copied_blocks"] == 0
    [store] = read["stores"]
    assert store["ram_rounds"] > 0 and store["recycled_rounds"] == metrics["spilled_rounds_per_job"] * read["jobs_read"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description="the controls of " + CELL + " at the cell's own size")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--damage", choices=sorted(CONTROLS), action="append")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.join(ROOT, ".scratch", "control")  # inside the checkout, listed in .gitignore
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    all_caught = True
    for i, damage in enumerate(args.damage or sorted(CONTROLS)):
        out = run_a_control(root, damage, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        caught, last, window = verdict(out, damage)
        all_caught &= caught
        print(json.dumps({"damage": damage, "control_caught": caught, "jobs": window["jobs"],
                          "warmup_failed_tasks": window["warmup_failed_tasks"], "last": last}), flush=True)
    sys.exit(0 if all_caught else 1)

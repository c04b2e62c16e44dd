"""The ordered TeraSort cell (``ts10gb-sortedjobs-1chip``): its configuration
against its control's, the reference's order check (``references/
terasort-ordered.py``) against orders it must refuse, the four readers, the
driver's refusals, and the cell through ``run.py``.

The controls: the cell's own job with its guarantee broken in one reduce task
of every timed job — two neighbouring records of the task's batch exchanged,
one record dropped, and the task's range ordered by its first EIGHT key bytes
only over records that collide on those eight by design (a throw-away
reference plants them: at the source's key law no two keys of a partition
share eight bytes, so a two-lane sort would pass by luck) — through
``run.py`` itself in a copy of the benchmark with a throw-away driver (data
and a driver added, nothing edited).  The planted records read with all ten
key bytes must come out ``correct``: the third lane orders them.  As tests
they run the CPU form; on the chip this file is a program that runs them at
the cell's own size (``python3 tests/benchmark/test_benchmark_sorted.py
--seed <n> --seconds <s>``) and exits 0 only if every control came out as not
correct and the planted run as correct."""

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

ordered = load_module("references", "terasort-ordered")
terasort = ordered.terasort

CELL = "ts10gb-sortedjobs-1chip"
CONTROL_CONFIG = "terasort-10gb-1of4"
#: what the configuration may state otherwise than its control
OWN_KEYS = {"source", "deployment", "reference", "conf", "guarantees", "assumed", "store", "geometry", "rehearse"}
CONFIG = {"mappers": 3, "records_per_mapper": 700, "record_bytes": 100, "key_bytes": 10, "reducers": 7,
          "keys": "uniform-bytes"}
SEED = 3_000_000_019  # the driver's seeds pass 2**31


@pytest.fixture(scope="module")
def records():
    return ordered.make_records(CONFIG, seed=SEED)


def test_the_configuration_is_the_controls_but_for_where_the_job_lives():
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("terasort-10gb-1of4-hbm", "manager-sortedjobs", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    control_entry = next(c for c in bench["configs"] if c["name"] == CONTROL_CONFIG)
    assert entry["reduced"] == control_entry["reduced"] == ["mappers"] and len(entry["source"]) <= 200
    config = load_cell(CELL).config
    with open(os.path.join(ROOT, control_entry["file"])) as f:
        control = json.load(f)
    assert set(config) == set(control)
    for key in set(control) - OWN_KEYS:
        assert config[key] == control[key], key
    assert config["reference"] == "terasort-ordered" and config["source"] == entry["source"]
    assert config["conf"] == {"keep_device_recv": True, "host_recv_mode": "device",
                              "staging_capacity_per_executor": 1 << 32}
    hbm = next(c for c in bench["configs"] if c["name"] == "groupbytest-25k-hbm")
    with open(os.path.join(ROOT, hbm["file"])) as f:
        assert config["conf"] == json.load(f)["conf"]  # the HBM-held job's conf, nothing else
    assert "non-decreasing order" in config["guarantees"] and "any order among themselves" in config["guarantees"]
    assert "eight bytes" in config["guarantees"]
    assert config["rehearse"]["conf"]["keep_device_recv"] is True


def test_the_configuration_states_the_generators_geometry_and_the_programs_store():
    from sparkucx_tpu.config import TpuShuffleConf

    config = load_cell(CELL).config
    stated = dict(config["geometry"])
    stated.pop("from")
    assert stated == ordered.geometry(config, 1)
    assert (stated["job_bytes"], stated["records"], stated["blocks"]) == (2_550_136_300, 25_501_363, 1_425)
    assert (stated["rounds"], stated["rollovers_to_disk"]) == (1, 0)  # one 4 GiB round
    assert (stated["smallest_reducer_records"], stated["largest_reducer_records"]) == (339_086, 341_485)
    # a task is sorted at its records plus under a slot (128 places) a block, never a power of two of them
    assert stated["slot_records"] == 128
    assert stated["largest_reducer_records"] <= stated["sort_capacity_records"] < 341_485 + 19 * 128
    conf = TpuShuffleConf(**config["conf"])
    assert config["store"] == {"staging_bytes": conf.staging_capacity_per_executor,
                               "alignment": conf.block_alignment,
                               "ram_budget_bytes": conf.max_host_pool_bytes}
    # the job's share of the buffer, as the file's ``assumed`` says it
    used_rows = sum(int((-(-np.diff(terasort.layout(config, m)[1]) * 100 // 512)).sum()) for m in range(19))
    assert used_rows == 4_981_443 and "4,981,443" in " ".join(config["assumed"])


def test_the_records_are_the_controls(records):
    plain = terasort.make_records(CONFIG, SEED)
    assert records.blocks == plain.blocks and records.expected == plain.expected
    assert records.checksum == plain.checksum and isinstance(records, terasort.Records)
    assert type(records.check(0)) is ordered.OrderedTaskCheck
    assert type(records.check(0, full=True)) is ordered.OrderedFullCheck


def hand_out(records, r):
    """A reduce task's records in key order, as a correct program hands them."""
    return records.sorted_partition(r)


def test_a_sorted_batch_passes_both_consumers_and_teravalidate(records):
    checks = []
    for r in range(records.reducers):
        for full in (False, True):
            check = records.check(r, full)
            check.add(hand_out(records, r))
            assert check.ok() and not check.unordered
        checks.append(check)
    assert records.complete(checks)
    # in two batches the order holds from one to the next too
    rows = hand_out(records, 2)
    split = records.check(2)
    split.add(rows[:100])
    split.add(rows[100:])
    assert split.ok()
    swapped = records.check(2)
    swapped.add(rows[100:])
    swapped.add(rows[:100])
    assert swapped.unordered == 1 and not swapped.ok()


def exchanged(rows):
    out = rows.copy()
    out[[40, 41]] = out[[41, 40]]
    return out


def signed_compare(rows):
    """Ordered by the key's lanes as SIGNED little-endian-swapped integers:
    what a sort that forgets the keys are unsigned bytes gives."""
    lanes = np.ascontiguousarray(rows[:, :12]).view(">i4").copy()
    lanes[:, 2] &= np.int32(-65536)  # the two key bytes of the third lane
    return rows[np.lexsort([lanes[:, 2], lanes[:, 1], lanes[:, 0]])]


@pytest.mark.parametrize("damage", [
    exchanged, lambda rows: rows[:-1], lambda rows: np.delete(rows, 17, axis=0), signed_compare],
    ids=["exchanged-neighbours", "dropped-last", "dropped-inside", "signed-compare"])
def test_the_order_check_refuses(records, damage):
    for full in (False, True):
        check = records.check(3, full)
        check.add(damage(hand_out(records, 3)))
        assert check.ok() is False
    unsorted = records.check(3)
    unsorted.add(records.rows_of(3))
    assert unsorted.unordered > 10 and not unsorted.ok()  # the control cell's hand-out is no ordered return


def colliding(rng):
    """A partition's worth of records of which 60 share their first eight key
    bytes, and 30 one whole key."""
    rows = rng.integers(0, 256, size=(500, 100), dtype=np.uint8)
    rows[:60, :8] = rows[0, :8]
    rows[100:130, :10] = rows[100, :10]
    return rows[rng.permutation(len(rows))]


def test_eight_key_bytes_are_another_result_and_equal_keys_a_multiset():
    rng = np.random.default_rng(7)
    rows = colliding(rng)
    want = terasort.sort_records(rows)
    assert ordered.out_of_order(want, 10) == 0
    lead = np.ascontiguousarray(rows[:, :8]).view(">u8").ravel()
    two_lanes = rows[np.argsort(lead, kind="stable")]  # a stable sort on eight bytes
    assert ordered.out_of_order(two_lanes, 8) == 0 and ordered.out_of_order(two_lanes, 10) > 0

    class Reference:
        record_bytes, key_bytes, reducers = 100, 10, 1
        expected = [(len(rows), rows.size, terasort.lead_sum(rows, 10))]
        sorted_partition = staticmethod(lambda r: want)

    def full_check(batch):
        check = ordered.OrderedFullCheck(Reference, 0)
        check.lo, check.hi = np.uint64(0), np.uint64(terasort.PREFIX_END)
        check.add(batch)
        return check.ok()

    assert full_check(want)
    # records of one key in another order among themselves: the same result
    run = np.flatnonzero((want[:, :10] == want[np.flatnonzero((want[1:, :10] == want[:-1, :10]).all(axis=1))[0], :10])
                         .all(axis=1))
    shuffled = want.copy()
    shuffled[run] = want[run[::-1]]
    assert not np.array_equal(shuffled, want) and full_check(shuffled)
    assert not full_check(two_lanes)
    # a record's value changed inside the run: no multiset of the slice's
    forged = shuffled.copy()
    forged[run[0], 50] ^= 1
    assert not full_check(forged)


def test_the_four_readers_on_a_run_made_up_by_hand():
    from benchmark.device_trace import Reduction

    ms = 1_000_000
    jobs = [JobResult(seconds=2.0, tasks=94, failed=0, faults=0, read_task_s=[0.02])] * 2
    spans = [("job.read", 0, 100 * ms)]
    program = [
        ("read.ordered", 1 * ms, 21 * ms), ("read.ordered", 30 * ms, 60 * ms), ("read.ordered", 70 * ms, 80 * ms),
        ("read.ordered.d2h", 5 * ms, 20 * ms), ("read.ordered.d2h", 35 * ms, 60 * ms),
        ("read.ordered.sort", 2 * ms, 3 * ms),
    ]
    fields = dict(chips=1, device_kind="TPU v5 lite", setup_s=50.0, job_bytes=10**9, jobs=jobs, spans=spans,
                  rounds=[1, 1], stats_before={"used_rows": 0}, stats_after={"used_rows": 2 * 4_000_000},
                  fetch_faults=0)
    modules = {"jit_ordered_records(123)": 0.3, "jit_ordered_records(77)": 0.1, "jit_block_gather(5)": 0.01}
    reduction = Reduction(window_s=2.0, busy_s=0.5, idle_share=0.75, device_ops=[], idle_gaps=[],
                          module_s=modules, devices=1, planes=1)
    run = Run(program_spans=program, reduction=reduction, **fields)
    assert reader("layer_metrics", "ordered_read_task_p50_us")(run) == pytest.approx(20_000.0)
    assert reader("layer_metrics", "ordered_sort_wait_p50_us")(run) == pytest.approx(20_000.0)
    assert reader("layer_metrics", "sort_device_ms_per_job")(run) == pytest.approx(400.0)
    least = 2 * 4_000_000 * 512 / 819e9  # a job's used rows read once and written back
    assert reader("layer_metrics", "sort_roofline")(run) == pytest.approx(100 * least / 0.4)
    # a program that orders nothing on the device (the parent): every reader finds nothing
    names = ("ordered_read_task_p50_us", "ordered_sort_wait_p50_us", "sort_device_ms_per_job", "sort_roofline")
    other = Run(program_spans=[("read.batches", 0, ms)], reduction=Reduction(
        window_s=2.0, busy_s=0.5, idle_share=0.75, device_ops=[], idle_gaps=[],
        module_s={"jit_block_gather(5)": 0.01}, devices=1, planes=1), **fields)
    untraced = Run(**fields)
    assert all(reader("layer_metrics", name)(r) is None for name in names for r in (other, untraced))
    for name in names:
        [metric] = [m for m in load_benchmark()["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"] and metric["moves"] == "shuffle_throughput"
        assert metric["layer"] == "reduce-side read"


def test_a_program_whose_read_batches_cannot_order_is_refused(monkeypatch):
    """The parent commit under this benchmark: out at ``start``, before a
    record is made — never a hang, never failing tasks that read as speed."""
    from sparkucx_tpu.shuffle import reader as program
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    driver = load_module("traffic", "manager-sortedjobs")
    assert driver.require_ordered_read() is program.FixedWidthSerializer
    monkeypatch.delattr(TpuShuffleCluster, "ordered_read_stats")
    with pytest.raises(SystemExit, match="needs read_batches\\(\\) under key_ordering"):
        driver.Traffic(load_cell(CELL, rehearse=True), None).start(None, {})


def test_the_driver_holds_every_job_to_the_orderedread_counters(records):
    """One ``sort_dispatches`` a non-empty task, the job's own records, and
    the ordered arrays' bytes across in exactly one D2H each: a job whose
    counters say otherwise makes the run unsound."""
    driver = load_module("traffic", "manager-sortedjobs")
    capacity = 1024

    class Cluster:
        def __init__(self):
            self.row = dict.fromkeys(driver.ORDERED, 0)
            self.mesh = type("Mesh", (), {"devices": np.array([type("D", (), {"memory_stats": lambda self: {}})()])})()

        def ordered_read_stats(self):
            return [{"executor": 0, **self.row}]

        def a_job(self, **wrong):
            rose = {"tasks": records.reducers, "sort_dispatches": records.reducers,
                    "records": records.total_records, "bytes": records.total_bytes,
                    "capacity_records": records.reducers * capacity,
                    "d2h_bytes": records.reducers * capacity * 100, "d2h_ns": 5, **wrong}
            for name, value in rose.items():
                self.row[name] += value

    class Manager:
        cluster = Cluster()

        def unregister_shuffle(self, sid):
            pass

    manager = Manager()
    entry = driver.Entry(manager, None, records)
    manager.cluster.a_job()
    entry.remove(0)
    manager.cluster.a_job(sort_dispatches=records.reducers - 1)  # one task's order came from elsewhere
    entry.remove(1)
    manager.cluster.a_job(d2h_bytes=records.reducers * capacity * 100 * 2)  # a buffer crossed twice
    entry.remove(2)
    manager.cluster.a_job(d2h_bytes=0)  # or not through the one D2H at all
    entry.remove(3)
    assert sorted(entry.miscounted) == [1, 2, 3]
    assert "sort_dispatches rose 6, not 7" in entry.miscounted[1] and "d2h_bytes" in entry.miscounted[2]


# -- the cell through run.py: the rehearsal, the controls, the planted run -----

DAMAGED = "ts10gb-sortedjobs-damaged-1chip"
#: the reduce task whose batch is damaged
DAMAGED_TASK = 3
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-sortedjobs`` whose reduce task %d is handed,
in every timed job (the warm-up job, shuffle 0, is left whole, so it is the
window's comparison that has to notice), what the traffic file's ``damage``
says: ``exchanged`` (two neighbouring records of its batch change places),
``dropped`` (its batch lacks one record), ``eightbytes`` (the program is
asked to order the task by its first eight key bytes only: a reader with
``FixedWidthSerializer(record_bytes, 8)``) — and nothing for ``none``."""

import numpy as np

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-sortedjobs")
TASK = %d


class Entry(shipped.Entry):
    def read(self, shuffle_id, reduce_id, mappers, consume):
        hit = shuffle_id > 0 and reduce_id == TASK
        whole = self.serializer

        def damaged(batch):
            if hit and self.damage == "exchanged":
                batch = batch.copy()
                middle = len(batch) // 2
                batch[[middle, middle + 1]] = batch[[middle + 1, middle]]
            elif hit and self.damage == "dropped":
                batch = np.delete(batch, len(batch) // 2, axis=0)
            consume(batch)

        if hit and self.damage == "eightbytes":
            self.serializer = type(whole)(whole.record_bytes, 8)
        try:
            return super().read(shuffle_id, reduce_id, mappers, damaged)
        finally:
            self.serializer = whole


class Traffic(shipped.Traffic):
    def entry(self):
        config = self.cell.config
        entry = Entry(self.manager, self.serializer_class(config["record_bytes"], config["key_bytes"]), self.records)
        entry.damage = self.cell.traffic["damage"]
        return entry
''' % (DAMAGED_TASK, DAMAGED_TASK)
PLANTED_REFERENCE = '''"""A throw-away reference: ``terasort-ordered``'s records with, at the head of
every block, %d records that share their first eight key bytes (the block's
first record's; the 7-byte prefix with them, so every record stays in its
partition) and differ in key bytes 8-9 as the seed drew them: a range
ordered by eight key bytes only leaves each such run as written."""

import numpy as np

from benchmark.cells import load_module

shipped = load_module("references", "terasort-ordered")
terasort = shipped.terasort
RUN = %d


def make_records(config, seed):
    made = terasort.make_records(config, seed)
    width = made.record_bytes
    blocks, total = [], 0
    for parts in made.blocks:
        planted = []
        for r, payload in parts:
            rows = np.frombuffer(payload, dtype=np.uint8).reshape(-1, width).copy()
            rows[1:RUN, :8] = rows[0, :8]
            total = (total + terasort.checksum(rows)) & terasort._MASK
            planted.append((r, rows.tobytes()))
        blocks.append(planted)
    # bytes 10..17, what the timed check sums, are as they were: ``expected`` holds
    return shipped.Records(config, blocks, made.expected, total)


geometry = shipped.geometry
''' % (24, 24)
#: damage -> (reference, correct, failed tasks of the warm-up job, failed tasks a timed job)
CONTROLS = {
    "exchanged": ("terasort-ordered", False, 0, 1),
    "dropped": ("terasort-ordered", False, 0, 1),
    "eightbytes": ("terasort-ordered-planted", False, 0, 1),
    # the same planted records read with all ten key bytes: the third lane orders them
    "none": ("terasort-ordered-planted", True, 0, 0),
}


def run_py(root, cell, seed, seconds, trace, rehearse, **env):
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1500)


def run_a_control(root, damage, seed, seconds, rehearse, **env):
    """``run.py`` on the damaged cell in a copy of the benchmark under
    ``root``; returns the finished process."""
    shutil.rmtree(os.path.join(root, "benchmark"), ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    made = os.path.join(root, "benchmark")
    with open(os.path.join(made, "traffic", "manager-sortedjobs-damaged.json"), "w") as f:
        json.dump({"driver": "manager-sortedjobs-damaged", "damage": damage}, f)
    with open(os.path.join(made, "traffic", "manager-sortedjobs-damaged.py"), "w") as f:
        f.write(DAMAGED_DRIVER)
    with open(os.path.join(made, "references", "terasort-ordered-planted.py"), "w") as f:
        f.write(PLANTED_REFERENCE)
    bench = load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "terasort-10gb-1of4-hbm")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["reference"] = CONTROLS[damage][0]
    with open(os.path.join(made, "configs", "terasort-10gb-1of4-hbm-control.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({**entry, "name": "terasort-10gb-1of4-hbm-control",
                             "file": "benchmark/configs/terasort-10gb-1of4-hbm-control.json"})
    bench["workloads"].append({"name": DAMAGED, "config": "terasort-10gb-1of4-hbm-control",
                               "traffic": "manager-sortedjobs-damaged", "chips": 1, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def lines_of(out):
    lines = out.stdout.strip().splitlines()
    found = lambda label: json.loads(next(l for l in lines if l.startswith(label + ": ")).split(": ", 1)[1])
    return json.loads(lines[-1]), found


def verdict(out, damage):
    """(the run came out as planted — not correct for the reason planted, or
    correct —, its last line, its ``window:`` line)."""
    last, found = lines_of(out)
    window = found("window")
    _, correct, in_warmup, in_a_job = CONTROLS[damage]
    as_planted = (out.returncode == 0 and last["correct"] is correct and window["jobs"] >= 1
                  and window["warmup_failed_tasks"] == in_warmup and last["failed"] == in_a_job * window["jobs"]
                  and found("sorted")["unsound"] == [])
    return as_planted, last, window


@pytest.mark.parametrize("damage", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_for_an_unordered_batch_too(tmp_path, damage):
    out = run_a_control(str(tmp_path), damage, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    as_planted, last, window = verdict(out, damage)
    assert as_planted, (last, window["warmup_failed_tasks"], window["jobs"], window["compiles_in_window"])
    assert f"reduce task {DAMAGED_TASK} " not in out.stdout + out.stderr  # no task raised: the comparison found it


def test_rehearsal_of_the_sorted_cell_prints_the_sorted_line(tmp_path):
    """The traced CPU run: every task one ordered batch, one sort dispatch and
    one D2H of the ordered array a task, the host readers report."""
    out = run_py(ROOT, CELL, SEED, 0.5, 1, True,
                 JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = lines_of(out)
    assert last["correct"] is True and last["failed"] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    assert metrics["ordered_read_task_p50_us"] > metrics["ordered_sort_wait_p50_us"] > 0
    assert metrics["staging_rounds_per_job"] == 1
    assert "read_batches_task_p50_us" not in metrics and "device_read_task_p50_us" not in metrics
    line = found("sorted")
    tiny = load_cell(CELL, rehearse=True).config
    jobs, tasks = line["jobs_read"], tiny["reducers"]
    assert line["unsound"] == [] and line["gather"] == [line["expected"]] == ["xla"]
    assert line["records_a_job"] == tiny["mappers"] * tiny["records_per_mapper"]
    counted = line["orderedread"]
    assert counted["tasks"] == counted["sort_dispatches"] == line["record_batches"] == jobs * tasks
    assert counted["records"] == line["records_read"] and counted["bytes"] == 100 * counted["records"]
    assert counted["d2h_bytes"] == 100 * counted["capacity_records"]
    assert counted["capacity_records"] % (jobs * tasks) == 0  # one capacity a shuffle, the same every job
    assert len(line["bytes_in_use_after_job"]) == jobs
    assert found("window")["compiles_in_window"]["compiles"] == 0


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
    all_as_planted = True
    for i, damage in enumerate(args.damage or sorted(CONTROLS)):
        out = run_a_control(root, damage, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        as_planted, last, window = verdict(out, damage)
        all_as_planted &= as_planted
        print(json.dumps({"damage": damage, "as_planted": as_planted, "wanted_correct": CONTROLS[damage][1],
                          "jobs": window["jobs"], "warmup_failed_tasks": window["warmup_failed_tasks"],
                          "compiles_in_window": window["compiles_in_window"]["compiles"],
                          "sorted": lines_of(out)[1]("sorted"), "last": last}), flush=True)
    sys.exit(0 if all_as_planted else 1)

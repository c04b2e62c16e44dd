"""The ordered TeraSort cell as its executor runs it
(``ts10gb-sortedjobs-4tasks-1chip``): its configuration against its control's
(``terasort-10gb-1of4-hbm``, the same job one task deep), the three overlap
readers on hand-made spans, the driver's slots (tasks in order to the slot
that frees first, the barrier, the depth rule), and the cell through
``run.py``.

The controls: the cell's own job, four tasks deep, with its guarantee broken
in every timed job — a batch handed to the wrong one of two tasks in flight
(tasks 3 and 4 are each handed the other's batch: every prefix out of range),
and a batch's bytes overwritten after its hand-out while its consumer still
holds it (what a landing block recycled under a live view would do: the sum
and the order) — through ``run.py`` itself in a copy of the benchmark with a
throw-away driver (data and a driver added, nothing edited).  The unbroken job
and PR 48's planted records (24 a block that collide on eight key bytes) read
four deep must come out ``correct``.  As tests they run the CPU form; on the
chip this file is a program that runs them at the cell's own size (``python3
tests/benchmark/test_benchmark_sorted_tasks.py --seed <n> --seconds <s>``) and
exits 0 only if every control came out as planted."""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import pytest

from benchmark.cells import load_benchmark, load_cell, load_module, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run

CELL = "ts10gb-sortedjobs-4tasks-1chip"
CONTROL_CELL = "ts10gb-sortedjobs-1chip"
#: what the configuration may state otherwise than its control (``geometry``
#: adds the measured device peak and is compared apart)
OWN_KEYS = {"source", "deployment", "guarantees", "assumed", "geometry", "rehearse", "task_slots",
            "task_slots_source"}
SEED = 3_000_000_019  # the driver's seeds pass 2**31
NEW_READERS = {"ordered_tasks_in_flight": ("read.ordered", "job.read", "reduce-side read"),
               "ordered_d2h_in_flight": ("read.ordered.d2h", "job.read", "reduce-side read"),
               "map_tasks_in_flight": ("write.task", "job.write", "map-side write")}


def sorted_tests():
    """PR 48's test file as a module: its planted reference, ``run_py`` and
    ``lines_of`` (loaded, not copied)."""
    name = "test_benchmark_sorted"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(os.path.dirname(__file__), name + ".py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def test_the_configuration_is_the_controls_but_for_the_task_slots():
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "terasort-10gb-1of4-hbm-4slots", "manager-sortedtasks", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["mappers"] and len(entry["source"]) <= 200
    assert "buildlib/test.sh:48-50" in entry["source"] and "4 task slots" in entry["source"]
    config, control = load_cell(CELL).config, load_cell(CONTROL_CELL).config
    assert set(config) - set(control) == {"task_slots", "task_slots_source"} and set(control) <= set(config)
    for key in set(control) - OWN_KEYS:
        assert config[key] == control[key], key
    assert config["source"] == entry["source"] != control["source"]
    assert config["task_slots"] == 4 and "test.sh:48-50" in config["task_slots_source"]
    assert "from memory" in config["task_slots_source"]  # the RAPIDS precedent, said so
    assert (config["mappers"], config["reducers"], config["reference"]) == (19, 75, "terasort-ordered")
    assert config["conf"] == {"keep_device_recv": True, "host_recv_mode": "device",
                              "staging_capacity_per_executor": 1 << 32}
    # the control's guarantee, and one sentence more
    assert config["guarantees"].startswith(control["guarantees"])
    added = config["guarantees"][len(control["guarantees"]):]
    assert "never see each other's records" in added and "for as long as its consumer holds it" in added
    assert config["assumed"][: len(control["assumed"])] == control["assumed"]
    more = " ".join(config["assumed"][len(control["assumed"]):])
    assert "a slot is a THREAD" in more and "frees first" in more and "barrier" in more
    # the layout's geometry is the control's; the device peak is this cell's own reading
    own = {k: v for k, v in config["geometry"].items() if not k.startswith("device_memory")}
    assert own == control["geometry"]
    # the fullest device as the chip run read it: 55% of the chip's 16 GB or more, and under the chip
    assert 0.55 * 16e9 <= config["geometry"]["device_memory_peak_bytes"] < 16e9
    assert "measured" in config["geometry"]["device_memory_peak_from"]
    assert config["rehearse"]["mappers"] > config["task_slots"]  # the rehearsal's map stage reaches the slots too


def made_up_run(program, spans):
    jobs = [JobResult(seconds=1.0, tasks=94, failed=0, faults=0, read_task_s=[0.02])]
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=50.0, job_bytes=10**9, jobs=jobs, spans=spans,
               rounds=[1], stats_before={"used_rows": 0}, stats_after={"used_rows": 1}, fetch_faults=0,
               program_spans=program)


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_the_three_overlap_readers_on_spans_made_up_by_hand(name):
    inner, outer, layer = NEW_READERS[name]
    ms = 1_000_000
    read = reader("layer_metrics", name)
    stage = [(outer, 0, 100 * ms), ("job.slot", 0, 100 * ms)]
    end_to_end = [(inner, 0, 25 * ms), (inner, 25 * ms, 60 * ms), (inner, 60 * ms, 100 * ms)]
    assert read(made_up_run(end_to_end, stage)) == pytest.approx(1.0)
    side_by_side = end_to_end + [(inner, 0, 50 * ms), (inner, 50 * ms, 100 * ms)]
    assert read(made_up_run(side_by_side, stage)) == pytest.approx(2.0)
    # a span that reaches over the stage's end counts as far as the stage goes; another job's not at all
    over = [(inner, 80 * ms, 140 * ms), (inner, 200 * ms, 300 * ms)]
    assert read(made_up_run(over, stage)) == pytest.approx(0.2)
    # absent spans: left out of the line, never zero (an untraced run; a program without them)
    assert read(made_up_run([], stage)) is None
    assert read(made_up_run([("read.batches", 0, ms)], stage)) is None
    assert read(made_up_run(end_to_end, [("job.slot", 0, 100 * ms)])) is None
    [metric] = [m for m in load_benchmark()["per_layer"] if m["name"] == name]
    assert CELL in metric["workloads"] and metric["moves"] == "shuffle_throughput"
    assert (metric["layer"], metric["unit"], metric["better"], metric["source"]) == (
        layer, "x", "higher", "program_span")


class FakeRecords:
    """Five map tasks, nine reduce tasks, checks that pass."""

    num_mappers, reducers = 5, 9
    blocks = [[(0, b"x")]] * 5

    class Check:
        def __init__(self):
            self.failed = False

        def add(self, batch):
            pass

        def fail(self):
            self.failed = True

        def ok(self):
            return not self.failed

    def check(self, r, full=False):
        return self.Check()

    def mappers_of(self, r):
        return list(range(self.num_mappers))

    def complete(self, checks):
        return True


class FakeEntry:
    """An entry that notes who ran what, when; tasks take the time given."""

    def __init__(self, log, lock, seconds):
        self.log, self.lock, self.seconds = log, lock, seconds

    def note(self, op, index):
        with self.lock:
            self.log.append((op, index, threading.current_thread().name, time.perf_counter_ns()))

    def create(self, sid, mappers, reducers):
        self.note("create", sid)

    def write_map(self, sid, m, parts):
        self.note("map", m)
        time.sleep(self.seconds("map", m))
        self.note("map-done", m)

    def exchange(self, sid):
        self.note("exchange", sid)

    def read(self, sid, r, mappers, consume):
        self.note("reduce", r)
        time.sleep(self.seconds("reduce", r))
        if r == 7 and sid == 1:
            raise ValueError("planted")
        return 0


def test_the_slots_take_tasks_in_order_each_to_the_slot_that_frees_first_and_the_barrier_holds():
    driver = load_module("traffic", "manager-sortedtasks")
    log, lock = [], threading.Lock()
    # map task 0 and reduce task 1 are long: their slots take no other task meanwhile
    seconds = lambda op, i: 0.25 if (op, i) in (("map", 0), ("reduce", 1)) else 0.02
    records = FakeRecords()
    slots = driver.Slots([FakeEntry(log, lock, seconds) for _ in range(4)], records)
    handed = []  # (op, index, slot) in the order the driver handed them out

    class Noting:
        def __init__(self, k, inbox):
            self.k, self.inbox = k, inbox

        def put(self, task):
            if task is not None:
                handed.append((task[0], task[2], self.k))
            self.inbox.put(task)

    slots.inboxes = [Noting(k, inbox) for k, inbox in enumerate(slots.inboxes)]
    try:
        executor = driver.Executor(FakeEntry(log, lock, seconds), slots, records, task_slots=4)
        job = executor.run_job(0, full=True)
        assert (job.tasks, job.failed, len(job.read_task_s)) == (14, 0, 9)
        assert executor.depths == [(4, 4)] and not executor.shallow
        names = [name for name, _, _ in executor.spans]
        assert names.count("job.slot") == 4 and names.count("task.map") == 5 and names.count("task.reduce") == 9
        by_name = {name: (lo, hi) for name, lo, hi in executor.spans if name.startswith("job.")}
        assert by_name["job.write"][1] == by_name["job.exchange"][0] <= by_name["job.exchange"][1]
        assert by_name["job.exchange"][1] == by_name["job.read"][0] and by_name["job.slot"] == (
            by_name["job.write"][0], by_name["job.read"][1])
        ops = [(op, i) for op, i, _, _ in log]
        # tasks are handed out in index order, a stage at a time, between the driver's own three calls
        assert [(op, i) for op, i, _ in handed] == [("map", m) for m in range(5)] + [("reduce", r) for r in range(9)]
        assert [k for _, _, k in handed[:4]] == [0, 1, 2, 3] and sorted(k for _, _, k in handed[5:9]) == [0, 1, 2, 3]
        assert ops[0] == ("create", 0) and ops.index(("exchange", 0)) > max(
            k for k, (op, _) in enumerate(ops) if op == "map-done")
        assert ops.index(("exchange", 0)) < ops.index(("reduce", 0))
        thread_of = {(op, i): thread for op, i, thread, _ in log}
        assert thread_of[("create", 0)] == thread_of[("exchange", 0)] == threading.current_thread().name
        on_slots = {thread_of[("map", m)] for m in range(4)}
        assert on_slots == {f"task-slot-{k}" for k in range(4)}  # the first four, a slot each
        # the slot that frees first: the fifth map task goes to a slot whose task was short, never to task 0's
        assert thread_of[("map", 4)] != thread_of[("map", 0)]
        long_slot = thread_of[("reduce", 1)]
        assert [r for r in range(9) if thread_of[("reduce", r)] == long_slot] == [1]
        # a task that raises is one failed task; the job goes on
        job = executor.run_job(1)
        assert job.failed == 1 and job.tasks == 14 and executor.depths[-1] == (4, 4)
    finally:
        slots.close()
    assert not any(t.is_alive() for t in slots.threads)


def test_a_job_that_never_reached_the_slots_is_not_this_traffic():
    driver = load_module("traffic", "manager-sortedtasks")
    log, lock = [], threading.Lock()
    records = FakeRecords()
    slots = driver.Slots([FakeEntry(log, lock, lambda op, i: 0.0) for _ in range(2)], records)  # two of four came up
    try:
        executor = driver.Executor(FakeEntry(log, lock, None), slots, records, task_slots=4)
        executor.run_job(3)
        assert executor.depths == [(2, 2)]
        assert executor.shallow == {3: "tasks in flight reached 2 / 2, not 4 / 4"}
    finally:
        slots.close()


# -- the cell through run.py: the rehearsal and the controls -------------------

DAMAGED = "ts10gb-sortedjobs-4tasks-damaged-1chip"
#: the two reduce tasks, in flight together, whose batches are damaged
DAMAGED_TASKS = (3, 4)
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-sortedtasks`` whose slots, in every timed
job (the warm-up job, shuffle 0, is left whole, so it is the window's
comparison that has to notice), do what the traffic file's ``damage`` says:
``wrongtask`` (reduce tasks %d and %d, in flight side by side, are each handed
the other's batch), ``overwritten`` (task %d's batch is written over after its
hand-out, while its consumer still holds it and before it has looked: the
second half's bytes land on the first half, as a landing block recycled under
a live view would have it) — and nothing for ``none``."""

import ctypes

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-sortedtasks")
PAIR = {%d: %d, %d: %d}


class Entry(shipped.sortedjobs.Entry):
    def read(self, shuffle_id, reduce_id, mappers, consume):
        hit = shuffle_id > 0 and reduce_id in PAIR
        if hit and self.damage == "wrongtask":
            return super().read(shuffle_id, PAIR[reduce_id], mappers, consume)
        if hit and self.damage == "overwritten" and reduce_id == min(PAIR):
            held = []
            faults = super().read(shuffle_id, reduce_id, mappers, held.append)
            for batch in held:  # handed out; the consumer has not looked yet
                half = len(batch) // 2 * batch.shape[1]  # bytes; a writer that asks no view's leave, as a DMA
                ctypes.memmove(batch.ctypes.data, batch.ctypes.data + batch.size - half, half)
            for batch in held:
                consume(batch)
            return faults
        return super().read(shuffle_id, reduce_id, mappers, consume)


class Traffic(shipped.Traffic):
    def entry(self):
        config = self.cell.config
        entry = Entry(self.manager, self.serializer_class(config["record_bytes"], config["key_bytes"]), self.records)
        entry.damage = self.cell.traffic["damage"]
        return entry
''' % (DAMAGED_TASKS + (DAMAGED_TASKS[0],) + (DAMAGED_TASKS[0], DAMAGED_TASKS[1], DAMAGED_TASKS[1], DAMAGED_TASKS[0]))
#: control -> (damage, reference, correct, failed tasks of the warm-up job, failed tasks a timed job)
CONTROLS = {
    "wrongtask": ("wrongtask", "terasort-ordered", False, 0, 2),
    "overwritten": ("overwritten", "terasort-ordered", False, 0, 1),
    "none": ("none", "terasort-ordered", True, 0, 0),
    # PR 48's planted collisions, four deep: the third key lane orders them whoever is in flight beside
    "planted": ("none", "terasort-ordered-planted", True, 0, 0),
}


def run_a_control(root, control, seed, seconds, rehearse, **env):
    """``run.py`` on the damaged cell in a copy of the benchmark under
    ``root``; returns the finished process."""
    damage, reference = CONTROLS[control][:2]
    shutil.rmtree(os.path.join(root, "benchmark"), ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    made = os.path.join(root, "benchmark")
    with open(os.path.join(made, "traffic", "manager-sortedtasks-damaged.json"), "w") as f:
        json.dump({"driver": "manager-sortedtasks-damaged", "damage": damage}, f)
    with open(os.path.join(made, "traffic", "manager-sortedtasks-damaged.py"), "w") as f:
        f.write(DAMAGED_DRIVER)
    with open(os.path.join(made, "references", "terasort-ordered-planted.py"), "w") as f:
        f.write(sorted_tests().PLANTED_REFERENCE)
    bench = load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "terasort-10gb-1of4-hbm-4slots")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["reference"] = reference
    with open(os.path.join(made, "configs", "terasort-10gb-1of4-hbm-4slots-control.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({**entry, "name": "terasort-10gb-1of4-hbm-4slots-control",
                             "file": "benchmark/configs/terasort-10gb-1of4-hbm-4slots-control.json"})
    bench["workloads"].append({"name": DAMAGED, "config": "terasort-10gb-1of4-hbm-4slots-control",
                               "traffic": "manager-sortedtasks-damaged", "chips": 1, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return sorted_tests().run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def verdict(out, control):
    """(the run came out as planted — not correct for the reason planted, or
    correct —, its last line, its ``window:`` line, its ``tasks:`` line)."""
    last, found = sorted_tests().lines_of(out)
    window, tasks = found("window"), found("tasks")
    _, _, correct, in_warmup, in_a_job = CONTROLS[control]
    as_planted = (out.returncode == 0 and last["correct"] is correct and window["jobs"] >= 1
                  and window["warmup_failed_tasks"] == in_warmup and last["failed"] == in_a_job * window["jobs"]
                  and found("sorted")["unsound"] == [] and tasks["depth_reached"] == {"map": [4], "reduce": [4]})
    return as_planted, last, window, tasks


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_for_a_batch_of_the_wrong_or_a_recycled_task_too(tmp_path, control):
    out = run_a_control(str(tmp_path), control, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    as_planted, last, window, tasks = verdict(out, control)
    assert as_planted, (last, window["warmup_failed_tasks"], window["jobs"], tasks)
    assert "reduce task " not in out.stdout + out.stderr  # no task raised: the comparison found it


def test_rehearsal_of_the_four_slot_sorted_cell_prints_the_tasks_line(tmp_path):
    """The traced CPU run: both stages four deep in every job, the warm-up
    job too, the ``orderedread`` counters exact, the gauges back to 0, every
    host reader and the three new ones reporting."""
    out = sorted_tests().run_py(ROOT, CELL, SEED, 0.5, 1, True,
                                JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = sorted_tests().lines_of(out)
    assert last["correct"] is True and last["failed"] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    for name in NEW_READERS:
        assert 0 < metrics[name] <= 4.0, (name, metrics[name])
    assert metrics["staging_rounds_per_job"] == 1 and metrics["write_s_per_job"] > 0 < metrics["read_s_per_job"]
    assert "ordered_read_task_p50_us" not in metrics and "slot_idle_share" not in metrics  # other cells' lists
    tiny = load_cell(CELL, rehearse=True).config
    line, tasks = found("sorted"), found("tasks")
    jobs, reducers = line["jobs_read"], tiny["reducers"]
    assert line["unsound"] == [] and line["gather"] == [line["expected"]] == ["xla"]
    assert line["records_a_job"] == tiny["mappers"] * tiny["records_per_mapper"]
    counted = line["orderedread"]
    assert counted["tasks"] == counted["sort_dispatches"] == line["record_batches"] == jobs * reducers
    assert counted["d2h_bytes"] == 100 * counted["capacity_records"]
    assert tasks["task_slots"] == 4 and tasks["depth_reached"] == {"map": [4], "reduce": [4]}
    assert tasks["jobs"] == jobs - 1 == found("window")["jobs"]
    in_flight = tasks["in_flight"]
    assert in_flight["in_flight"] == 0 and tasks["in_flight_after_job"] == [0]
    assert 1 <= in_flight["in_flight_peak"] <= 4
    capacity = counted["capacity_records"] // (jobs * reducers)
    held = 2 * capacity * 100  # a task's gathered segment and its sorted array
    assert in_flight["in_flight_device_bytes_peak"] == in_flight["in_flight_peak"] * held
    assert found("window")["compiles_in_window"]["compiles"] == 0
    assert last["attempted"] == found("window")["jobs"] * (tiny["mappers"] + reducers)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description="the controls of " + CELL + " at the cell's own size")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS), action="append")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.join(ROOT, ".scratch", "control")  # inside the checkout, listed in .gitignore
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    all_as_planted = True
    for i, control in enumerate(args.control or sorted(CONTROLS)):
        out = run_a_control(root, control, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        as_planted, last, window, tasks = verdict(out, control)
        all_as_planted &= as_planted
        print(json.dumps({"control": control, "as_planted": as_planted, "wanted_correct": CONTROLS[control][2],
                          "jobs": window["jobs"], "warmup_failed_tasks": window["warmup_failed_tasks"],
                          "compiles_in_window": window["compiles_in_window"]["compiles"], "tasks": tasks,
                          "sorted": sorted_tests().lines_of(out)[1]("sorted"), "last": last}), flush=True)
    sys.exit(0 if all_as_planted else 1)

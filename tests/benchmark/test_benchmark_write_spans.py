"""The readers of the spans PR 50 opened inside the map-side write — a map
task's ``write.task`` with its copy, lock wait and commit, one block in 199 by
phase, a round buffer of fresh pages by name — on a run made up by hand; the
ten declarations, found by name with their cells; and a CPU rehearsal of one
host-write manager cell and of the loss cell whose traced line carries every
one of its new metrics."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run
from benchmark.spans import program_spans

US = 1_000
MS = 1_000_000

ALL_CELLS = [
    "gbt25k-jobs-1chip", "gbt25k-jobs-4chip", "gbt1k-jobs-1chip", "gbt1k-daemon-1chip", "gbt25k-devfetch-1chip",
    "gbt25k-devproducer-1chip", "gbt25k-daemon-1chip", "gbt25k-daemon-4tasks-1chip", "gbt25k-zipf-4chip",
    "ts10gb-batchjobs-1chip", "gbt25k-execloss-4chip", "ts10gb-sortedjobs-1chip",
]
#: the manager cells whose map tasks copy their blocks into host staging
HOST_WRITE_CELLS = [
    "gbt25k-jobs-1chip", "gbt25k-jobs-4chip", "gbt1k-jobs-1chip", "gbt25k-zipf-4chip", "gbt25k-devfetch-1chip",
    "ts10gb-batchjobs-1chip", "ts10gb-sortedjobs-1chip", "gbt25k-execloss-4chip",
]
BLOCK_CELLS = ["gbt1k-jobs-1chip", "gbt25k-jobs-1chip", "gbt25k-jobs-4chip"]
#: metric -> (the program's span it reads, its unit, the cells that list it)
DECLARED = {
    "write_task_p50_ms": ("write.task", "ms", ALL_CELLS),
    "write_task_max_ms": ("write.task", "ms", ALL_CELLS),
    "write_copy_s_per_job": ("write.task.copy", "s", HOST_WRITE_CELLS),
    "write_lock_wait_s_per_job": ("write.task.lock_wait", "s",
                                  ["gbt25k-daemon-4tasks-1chip", "gbt25k-daemon-1chip", "gbt25k-jobs-1chip"]),
    "write_commit_s_per_job": ("write.task.commit", "s", BLOCK_CELLS),
    "fresh_round_buffers_per_job": ("store.round_buffer.fresh", "buffers", HOST_WRITE_CELLS),
    "write_block_p50_us": ("write.block", "us", BLOCK_CELLS),
    "write_block_admit_p50_us": ("write.block.admit", "us", BLOCK_CELLS),
    "write_block_copy_p50_us": ("write.block.copy", "us", BLOCK_CELLS),
    "write_block_record_p50_us": ("write.block.record", "us", BLOCK_CELLS),
}
NAMES = list(DECLARED)
MEDIANS = [n for n in NAMES if n.endswith("_p50_us")]
PER_JOB = [n for n in NAMES if n.endswith("_s_per_job")]
#: recorded once a round and chunk by every traced program (``inner_spans.MARKER``)
MARKER = ("exchange.assemble", 401 * MS, 402 * MS)


def spans_of(name, durations_us, start=5 * MS, every=MS):
    """Spans of that name, one every millisecond, of those durations."""
    return [(name, start + i * every, start + i * every + int(d * US)) for i, d in enumerate(durations_us)]


def a_run(spans, jobs=1):
    """``jobs`` jobs a second apart, each a 400 ms ``job.write`` from its start."""
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    own = []
    for j in range(jobs):
        own += [("job.write", j * 1000 * MS, j * 1000 * MS + 400 * MS),
                ("job.read", j * 1000 * MS + 500 * MS, j * 1000 * MS + 900 * MS)]
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job] * jobs,
               spans=own, rounds=[1] * jobs, stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(spans))


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_untraced_or_on_the_parent(name):
    read = reader("layer_metrics", name)
    assert read(a_run([])) is None  # an untraced run
    # the parent, traced: its ring holds the rounds and the windows, no span of the write's inside
    older = [MARKER] + spans_of("store.rollover", [300, 310]) + spans_of("read.window", [2500]) \
        + spans_of("exchange.superstep", [11000])
    assert read(a_run(older)) is None  # left out of the line, never a zero


@pytest.mark.parametrize("name", MEDIANS)
def test_a_block_reader_is_the_median_of_its_span_and_of_no_other(name):
    # all four block spans in the window, each with its own median: a reader
    # that took a prefix of its name for the name would read another
    spans = [MARKER]
    for i, metric in enumerate(MEDIANS):
        spans += spans_of(DECLARED[metric][0], [10 + i, 30 + i, 20 + i, 1000])
    assert reader("layer_metrics", name)(a_run(spans)) == pytest.approx(25 + MEDIANS.index(name))


def test_the_task_readers_take_the_median_and_each_jobs_longest():
    # three jobs of three tasks: 10, 12 and 14+j ms, and one task of 90 ms outside every job.write
    spans = [MARKER]
    for j in range(3):
        spans += spans_of("write.task", [10_000, 12_000, 14_000 + 1000 * j], start=j * 1000 * MS + 5 * MS,
                          every=20 * MS)
    spans += spans_of("write.task", [90_000], start=450 * MS)
    run = a_run(spans, jobs=3)
    assert reader("layer_metrics", "write_task_p50_ms")(run) == pytest.approx(12.0)  # of all ten
    assert reader("layer_metrics", "write_task_max_ms")(run) == pytest.approx(15.0)  # 14, 15, 16
    # children and blocks are other names: neither reader takes them
    spans += spans_of("write.task.copy", [500_000]) + spans_of("write.block", [600_000])
    assert reader("layer_metrics", "write_task_max_ms")(a_run(spans, jobs=3)) == pytest.approx(15.0)


@pytest.mark.parametrize("name", PER_JOB)
def test_a_per_job_reader_sums_its_span_inside_each_job_write(name):
    span = DECLARED[name][0]
    spans = [MARKER]
    for j, tasks_us in enumerate(([100, 200, 300], [150, 250, 350], [50, 50, 50])):
        spans += spans_of(span, tasks_us, start=j * 1000 * MS + 5 * MS)
    spans += spans_of(span, [70_000], start=450 * MS)  # between the jobs' writes: nobody's
    for other in (n for n in PER_JOB if n != name):
        spans += spans_of(DECLARED[other][0], [9_000], start=10 * MS)
    got = reader("layer_metrics", name)(a_run(spans, jobs=3))
    assert got == pytest.approx(600e-6)  # the median of 600, 750 and 150 us


def test_fresh_round_buffers_are_counted_by_name_in_each_job_write():
    read = reader("layer_metrics", "fresh_round_buffers_per_job")
    tasks = [s for j in range(3) for s in spans_of("write.task", [10_000], start=j * 1000 * MS + 5 * MS)]
    # a program that records the tasks and had no fresh buffer: a count of 0, not nothing
    assert read(a_run([MARKER] + tasks, jobs=3)) == 0.0
    fresh = spans_of("store.round_buffer.fresh", [30] * 9, start=6 * MS) \
        + spans_of("store.round_buffer.fresh", [30] * 9, start=1006 * MS) \
        + spans_of("store.round_buffer.fresh", [30], start=2006 * MS) \
        + spans_of("store.round_buffer.fresh", [30] * 4, start=460 * MS)  # at a register, outside the writes
    assert read(a_run([MARKER] + tasks + fresh, jobs=3)) == 9.0  # 9, 9 and 1
    assert read(a_run([MARKER] + fresh, jobs=3)) is None  # the span alone names no program of this PR


def test_the_readers_take_what_the_tracers_bulk_path_wrote():
    """From the program's own events: a task with its children and a sampled
    block in ONE ``record_spans`` call, through ``program_spans`` to the readers."""
    from sparkucx_tpu.utils.trace import Tracer

    t = Tracer(enabled=True)
    t0 = 5 * MS
    cuts = (t0 + 100 * US, t0 + 103 * US, t0 + 110 * US, t0 + 112 * US)
    block = ("write.block", t0 + 90 * US, t0 + 112 * US, {"reduce_id": 3, "bytes": 1600},
             list(zip(("write.block.admit", "write.block.copy", "write.block.record"), cuts, cuts[1:])))
    children = [("write.task.copy", t0, t0 + 300 * US, {"turns": 63}),
                ("write.task.lock_wait", t0 + 300 * US, t0 + 340 * US, {"turns": 63}),
                block, ("write.task.commit", t0 + 800 * US, t0 + 900 * US)]
    t.record_spans(None, [("write.task", t0, t0 + 900 * US, {"map_id": 0, "blocks": 63}, children)])
    events = t.events
    task = events[0]
    assert [e["name"] for e in events if e["parent_id"] == task["span_id"]] == [
        "write.task.copy", "write.task.lock_wait", "write.block", "write.task.commit"]
    assert len({e["trace_id"] for e in events}) == 1 and task["args"] == {"map_id": 0, "blocks": 63}
    run = a_run([MARKER] + program_spans(events))
    got = {n: reader("layer_metrics", n)(run) for n in NAMES}
    assert got == pytest.approx({
        "write_task_p50_ms": 0.9, "write_task_max_ms": 0.9, "write_copy_s_per_job": 300e-6,
        "write_lock_wait_s_per_job": 40e-6, "write_commit_s_per_job": 100e-6, "fresh_round_buffers_per_job": 0.0,
        "write_block_p50_us": 22, "write_block_admit_p50_us": 3, "write_block_copy_p50_us": 7,
        "write_block_record_p50_us": 2}, abs=1e-6)


def test_the_ten_are_declared_by_name_in_their_cells():
    bench = load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert [n for n in order if n in DECLARED] == NAMES  # in this order among themselves
    assert order.index("sort_roofline") < order.index(NAMES[0])  # after PR 48's, wherever the list ends
    cells = {w["name"] for w in bench["workloads"]}
    for name, (_, unit, listed) in DECLARED.items():
        entry = declared[name]
        assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            "unit": unit, "better": "lower", "source": "program_span", "layer": "map-side write",
            "moves": "shuffle_throughput"}
        # its own cells, all of them cells of the benchmark; a later PR may append more
        assert set(listed) <= set(entry["workloads"]) <= cells
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    # the layer is one the benchmark already names, and the outside view stays
    assert declared["write_s_per_job"]["layer"] == "map-side write" and "workloads" not in declared["write_s_per_job"]


@pytest.mark.parametrize("name", NAMES)
def test_a_cell_outside_its_workloads_is_never_asked(name):
    bench = load_benchmark()
    listed = set(next(m for m in bench["per_layer"] if m["name"] == name)["workloads"])
    for cell in ALL_CELLS:
        asked = name in {m["name"] for m in load_cell(cell).per_layer}
        assert asked == (cell in listed) == (cell in DECLARED[name][2])


def rehearse(tmp_path, cell):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "compile_cache"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the test session's eight devices are not the cell's
    run_py = os.path.join(ROOT, load_benchmark()["command"][-1])
    out = subprocess.run(
        [sys.executable, run_py, "--workload", cell, "--seed", "2147483777", "--seconds", "0.5", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    trace = json.loads(next(line for line in lines if line.startswith("trace: ")).split(": ", 1)[1])
    return json.loads(lines[-1]), trace


# named for ``test_rehearsal``: the guard of test_benchmark_contract.py leaves
# out, by that name, the tests that run a job
@pytest.mark.parametrize("cell", ["gbt1k-jobs-1chip", "gbt25k-execloss-4chip"])
def test_rehearsal_carries_every_new_metric_of_the_cell(tmp_path, cell):
    last, trace = rehearse(tmp_path, cell)
    assert last["correct"] is True and last["failed"] == 0 and trace["program_spans_dropped"] == 0
    mine = [n for n in NAMES if cell in DECLARED[n][2]]
    values = {n: last["metrics"][n]["value"] for n in mine}  # every one reports
    assert not [n for n in NAMES if n not in mine and n in last["metrics"]]  # and no other cell's
    assert all(last["metrics"][n]["unit"] == DECLARED[n][1] and v >= 0 for n, v in values.items())
    write = last["metrics"]["write_s_per_job"]["value"]
    # a job's tasks are inside its job.write, and the longest is no shorter than the median
    assert 0 < values["write_task_p50_ms"] <= values["write_task_max_ms"] <= write * 1e3
    assert 0 < values["write_copy_s_per_job"] < write
    rows = {name for name, _ in last["breakdown"]["idle_gaps"]}
    if cell == "gbt1k-jobs-1chip":
        assert 0 < values["write_commit_s_per_job"] < write
        phases = [values[f"write_block_{p}_p50_us"] for p in ("admit", "copy", "record")]
        assert all(p > 0 for p in phases) and values["write_block_p50_us"] > max(phases)
        assert values["fresh_round_buffers_per_job"] == 0  # the free list has the one round's buffer
        assert any(name.startswith("write.task") for name in rows)  # the idle time names the task
    else:
        # every job kills and rejoins an executor, whose store starts without a free list
        assert values["fresh_round_buffers_per_job"] > 0

"""The cell that loses an executor AFTER every job's exchange
(``gbt25k-readloss-4chip``): its configuration against the control's kept
sizes, what ``references/groupby-readloss.py`` says the loss must cost the
reduce stage against the configuration's and the traffic's files, the three
readers of the cell, the refusal of a program that cannot place a reader of
a lost partition, the rehearsal's ``readloss:`` line, and the controls.

The controls: the cell's own job with its guarantee broken — ``flipped`` (one
byte of one block's replica on executor 3 flipped between the exchange and
the kill: a re-placed task reads another byte than was written), ``both``
(executors 2 AND 3 lost: the only replica went with its holder) and
``unreplicated`` (the same traffic on the cell's configuration with
``replication_factor`` 0) — through ``run.py`` itself in a copy of the
benchmark with a throw-away driver (data and a driver added, nothing edited).
As tests they run the CPU form; on the chip this file is a program that runs
them at the cell's own size (``python3
tests/benchmark/test_benchmark_readloss.py --seed <n> --seconds <s>``) and
exits 0 only if every one came out as not correct, for the reason planted."""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import pytest

from benchmark.cells import load_benchmark, load_cell, load_module, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run

readloss = load_module("references", "groupby-readloss")

CELL = "gbt25k-readloss-4chip"
CONTROL = "gbt25k-execloss-4chip"
METRICS = ("refetch_s_per_job", "refetch_block_p50_us", "replica_serve_s_per_job")


# -- the configuration, the traffic and the geometry of the loss ---------------


def test_the_configuration_is_the_controls_but_for_the_moment_of_the_loss():
    """Every shape and size of ``groupbytest-25k-repl-4chip``: the same
    records, cut, conf (factor 1, elastic; nothing else), store and
    rehearsal; what differs is when the executor dies, so the guarantee's
    wording, the reference's wrapper, what had to be assumed and the block
    that states the geometry."""
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "groupbytest-25k-repl-readloss-4chip", "manager-readlossjobs", 4)
    config, control = load_cell(CELL).config, load_cell(CONTROL).config
    assert {key for key in control if key in config and config[key] != control[key]} == {
        "source", "reference", "guarantees", "assumed", "store"}
    assert set(control) - set(config) == {"loss"} and set(config) - set(control) == {"read_loss"}
    for key in ("mappers", "pairs_per_mapper", "value_bytes", "reducers", "keys", "partitioner", "kept",
                "reduced", "conf", "rehearse", "block_layout", "deployment"):
        assert config[key] == control[key], key
    assert {k: v for k, v in config["store"].items() if k != "what"} == {
        k: v for k, v in control["store"].items() if k != "what"}
    assert config["conf"] == {"replication_factor": 1, "elastic": True}
    assert config["reference"] == "groupby-readloss" and list(config["reduced"]) == ["mappers"]
    assert config["assumed"][:4] == control["assumed"][:4]
    for word in ("died after the exchange", "re-placed on live executors", "nothing the dead executor held is ever served",
                 "ring successor", "BlockNotFoundError"):
        assert word in config["guarantees"], word
    assumed = " ".join(config["assumed"])
    for word in ("Celeborn", "executor 2", "EVERY job", "after run_exchange has returned", "survivors[r mod 3]",
                 "rejoin after the shuffle's removal"):
        assert word in assumed, word
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "buildlib/test.sh:169-173 run_big_test: GroupByTest 200 5000 25000 200" in entry["source"]
    assert entry["reduced"] == ["mappers"]


def test_the_traffic_names_the_event():
    traffic = load_cell(CELL).traffic
    assert traffic["driver"] == "manager-readlossjobs"
    assert (traffic["lost_executor"], traffic["lost_after"], traffic["every_job"], traffic["placement"],
            traffic["rejoin"]) == (2, "exchange", True, "survivors[r % 3]", "after remove")


def test_the_store_of_the_geometry_is_the_programs_default():
    from sparkucx_tpu.config import TpuShuffleConf

    store, conf = load_cell(CELL).config["store"], TpuShuffleConf()
    assert store["staging_bytes"] == conf.staging_capacity_per_executor
    assert store["alignment"] == conf.block_alignment


def test_the_configuration_states_the_geometry_of_the_loss():
    """The file's ``read_loss`` block is ``read_loss_geometry(config, traffic,
    chips)``: 50 tasks re-placed over executors 0, 1 and 3, 400 blocks pulled
    — 300 from the staging of map owners 0, 1, 3 and 100 (map tasks 2 and 6)
    from executor 3's replica tier — a quarter of the job's bytes, 150 tasks
    untouched, nothing run again."""
    cell = load_cell(CELL)
    stated = dict(cell.config["read_loss"])
    stated.pop("from")
    made = readloss.read_loss_geometry(cell.config, cell.traffic, cell.chips)
    assert stated == made
    assert (made["lost_executor"], made["replica_holder"], made["survivors"]) == (2, 3, [0, 1, 3])
    assert made["lost_map_tasks"] == [2, 6] and made["replaced_partitions"] == [100, 149]
    assert (made["replaced_tasks"], made["undisturbed_tasks"]) == (50, 150)
    assert made["tasks_placed_on"] == {"0": 16, "1": 17, "3": 17}
    assert (made["pulled_blocks"], made["staging_blocks"], made["replica_blocks"]) == (400, 300, 100)
    assert made["pulled_from_staging"] == {"0": 100, "1": 100, "3": 100}
    assert made["pulled_from_replicas"] == {"0": 0, "1": 0, "3": 100}
    assert made["job_bytes"] == made["replicated_bytes"] == 8 * 5000 * 25019 == 1_000_760_000
    assert 0.24 < made["pulled_bytes"] / made["job_bytes"] < 0.26 and made["pulled_bytes"] % 25019 == 0
    assert 0.24 < made["replica_bytes"] / made["pulled_bytes"] < 0.26
    assert (made["unserved_blocks"], made["rounds_rerun"], made["recoveries"]) == (0, 0, 0)
    # task by task it adds up, and only the lost executor's partitions are touched
    tasks = readloss.replaced_tasks(cell.config, cell.traffic, 4)
    assert tasks[100] == readloss.replaced_task(cell.config, cell.traffic, 4, 100) and len(tasks) == 200
    assert [r for r, t in enumerate(tasks) if t is not None] == list(range(100, 150))
    assert all(t["executor"] == [0, 1, 3][r % 3] for r, t in enumerate(tasks) if t is not None)
    assert sum(t["pulled_bytes"] for t in tasks if t) == made["pulled_bytes"]
    assert all((t["pulled_blocks"], t["replica_blocks"]) == (8, 2) for t in tasks if t)
    # the guarantee's edge, by the layout too: with the replica's holder gone, or no replica, blocks are unserved
    both = readloss.read_loss_geometry(cell.config, {"lost_executor": [2, 3]}, 4)
    assert (both["replaced_tasks"], both["unserved_blocks"], both["survivors"]) == (100, 200, [0, 1])
    bare = dict(cell.config, conf={"replication_factor": 0})
    assert readloss.read_loss_geometry(bare, cell.traffic, 4)["unserved_blocks"] == 100


def test_the_records_are_the_plain_groupbys():
    groupby = load_module("references", "groupby")
    config = {"mappers": 3, "pairs_per_mapper": 30, "value_bytes": 64, "reducers": 7, "keys": "uniform-int31"}
    ours, theirs = readloss.make_records(config, 3_000_000_019), groupby.make_records(config, 3_000_000_019)
    assert ours.blocks == theirs.blocks and ours.expected == theirs.expected and ours.groups == theirs.groups
    assert type(ours.check(0)) is groupby.TaskCheck and type(ours.check(0, full=True)) is groupby.FullCheck
    source = open(os.path.join(ROOT, "benchmark", "references", "groupby-readloss.py")).read()
    assert "sparkucx_tpu" not in source.split('"""', 2)[2]  # nothing of the code under test


@pytest.mark.parametrize("missing", ["reader", "cluster"])
def test_a_program_that_cannot_place_a_reader_of_a_lost_partition_is_refused(monkeypatch, missing):
    """The parent commit under this benchmark: out at ``start``, before a
    record is made — never 51 s of failing tasks and backoff sleeps."""
    from sparkucx_tpu.shuffle import reader as reader_module
    from sparkucx_tpu.transport import tpu as program

    driver = load_module("traffic", "manager-readlossjobs")
    driver.require_replaceable_reader()
    if missing == "cluster":
        monkeypatch.delattr(program.TpuShuffleCluster, "drop_received_of")
    else:
        init = reader_module.TpuShuffleReader.__init__

        def older(self, transport, executor_id, shuffle_id, start_partition, end_partition, num_mappers,
                  block_sizes, **kw):
            init(self, transport, executor_id, shuffle_id, start_partition, end_partition, num_mappers,
                 block_sizes, **kw)

        monkeypatch.setattr(reader_module.TpuShuffleReader, "__init__", older)
    with pytest.raises(SystemExit, match="needs a manager that places a reduce task of a lost partition"):
        driver.Traffic(load_cell(CELL, rehearse=True), None).start(None, {})


def _manager_whose_readers_report(owner_of, by_executor):
    """A stand-in manager: ``get_reader`` hands out a reader with the metrics
    planted for the executor it was placed on (None: the default placement)."""
    from types import SimpleNamespace

    zero = dict.fromkeys(("refetched_blocks", "refetched_bytes", "replica_blocks", "replica_bytes", "failovers",
                          "blocks_retried", "fetch_timeouts", "resident_blocks", "copied_blocks"), 0)

    def get_reader(sid, lo, hi, executor_id=None):
        metrics = SimpleNamespace(**{**zero, **by_executor[executor_id]})
        placed = owner_of(lo) if executor_id is None else executor_id
        return SimpleNamespace(metrics=metrics, executor_id=placed, read=lambda: iter([(7, b"v")]))

    cluster = SimpleNamespace(
        num_executors=4, elastic_stats={"recoveries": 0},
        meta=lambda sid: SimpleNamespace(owner_of_reduce=owner_of),
    )
    return SimpleNamespace(cluster=cluster, get_reader=get_reader)


def test_a_task_whose_counters_are_not_the_layouts_is_a_failed_task():
    """An undisturbed task that saw anything of the loss, a re-placed task
    that pulled other blocks than the layout says (or retried one, or was
    placed elsewhere), recoveries that rose: each raises, and ``run_job``
    counts the task failed by name."""
    driver = load_module("traffic", "manager-readlossjobs")
    owner_of = lambda r: 2 if r >= 100 else 0
    want = {"executor": 1, "pulled_blocks": 8, "pulled_bytes": 800, "replica_blocks": 2, "replica_bytes": 200,
            "unserved_blocks": 0}
    sound = {"refetched_blocks": 8, "refetched_bytes": 800, "replica_blocks": 2, "replica_bytes": 200,
             "failovers": 2, "copied_blocks": 8}
    table = lambda r: want if r >= 100 else None
    consume = lambda key, value: None

    def entry_of(default, placed):
        return driver.Entry(_manager_whose_readers_report(owner_of, {None: default, 1: placed}), [2], table)

    borrowed = {"resident_blocks": 3}
    e = entry_of(borrowed, sound)
    assert e.read(0, 5, [0, 1, 2], consume) == 0  # undisturbed: no fault
    assert e.read(0, 100, list(range(8)), consume) == 2  # re-placed: its failovers are the cell's fetch_faults
    assert e.replaced == 1 and e.summed["refetched_blocks"] == 8 and e.summed["resident_blocks"] == 3
    for planted in ({"resident_blocks": 3, "failovers": 1}, {"resident_blocks": 2, "copied_blocks": 1},
                    {"resident_blocks": 3, "fetch_timeouts": 1}):
        with pytest.raises(AssertionError, match="an undisturbed task saw the loss"):
            entry_of(planted, sound).read(0, 5, [0, 1, 2], consume)
    for planted in ({**sound, "refetched_blocks": 7}, {**sound, "replica_blocks": 1, "failovers": 1},
                    {**sound, "blocks_retried": 1}, {**sound, "fetch_timeouts": 1}, {**sound, "resident_blocks": 1},
                    {**sound, "replica_bytes": 199}):
        with pytest.raises(AssertionError, match="a re-placed task on executor 1 pulled"):
            entry_of(borrowed, planted).read(0, 100, list(range(8)), consume)
    e = entry_of(borrowed, sound)
    e.cluster.elastic_stats["recoveries"] = 1
    with pytest.raises(AssertionError, match="recoveries rose by 1"):
        e.read(0, 5, [0, 1, 2], consume)
    e = entry_of(borrowed, sound)
    e.unsound = "alive [0, 1] under the reads of shuffle 0, not [0, 1, 3]"
    with pytest.raises(AssertionError, match="under the reads"):
        e.read(0, 5, [0, 1, 2], consume)
    assert e.read(0, 5, [0, 1, 2], consume) == 0  # said once, by the task that found it


# -- the three readers ----------------------------------------------------------


def test_the_three_readers_on_a_run_made_up_by_hand():
    """Seconds of ``read.refetch`` and of ``store.read.replica`` inside each
    job's ``job.read``, each the median over the jobs, and the median
    ``read.refetch.block``; nothing where nothing was recorded."""
    ms, us = 1_000_000, 1_000
    jobs = [JobResult(seconds=1.0, tasks=208, failed=0, faults=100, read_task_s=[0.001])] * 3
    spans = [("job.exchange", 0, 100 * ms), ("job.read", 100 * ms, 400 * ms),
             ("job.read", 1100 * ms, 1400 * ms), ("job.read", 2100 * ms, 2400 * ms)]
    program = [
        ("exchange.assemble", 10 * ms, 11 * ms),
        ("read.refetch", 110 * ms, 150 * ms), ("read.refetch", 160 * ms, 180 * ms),  # 0.06 s in the first job
        ("store.read.replica", 112 * ms, 114 * ms), ("store.read.replica", 161 * ms, 162 * ms),  # 0.003 s
        ("read.refetch.block", 110 * ms, 110 * ms + 300 * us), ("read.refetch.block", 111 * ms, 111 * ms + 500 * us),
        ("read.refetch", 1110 * ms, 1190 * ms),  # 0.08 s in the second
        ("store.read.replica", 1111 * ms, 1116 * ms),  # 0.005 s
        ("read.refetch.block", 1110 * ms, 1110 * ms + 400 * us),
        ("read.refetch", 2110 * ms, 2150 * ms),  # 0.04 s in the third
        ("store.read.replica", 2111 * ms, 2112 * ms),  # 0.001 s
        ("read.refetch", 3000 * ms, 3100 * ms), ("store.read.replica", 3000 * ms, 3001 * ms),  # in no job's read
    ]
    fields = dict(chips=4, device_kind="TPU v5 lite", setup_s=60.0, job_bytes=10**9, jobs=jobs, spans=spans,
                  rounds=[9, 9, 9], stats_before={}, stats_after={}, fetch_faults=300)
    run = Run(program_spans=program, **fields)
    assert reader("layer_metrics", "refetch_s_per_job")(run) == pytest.approx(0.06)
    assert reader("layer_metrics", "replica_serve_s_per_job")(run) == pytest.approx(0.003)
    assert reader("layer_metrics", "refetch_block_p50_us")(run) == pytest.approx(400.0)
    # a job that lost nothing, an untraced run, the parent's program: left out
    whole = Run(program_spans=[("exchange.assemble", 1 * ms, 2 * ms)], **fields)
    untraced = Run(**dict(fields, jobs=[]))
    for name in METRICS:
        assert reader("layer_metrics", name)(whole) is None, name
        assert reader("layer_metrics", name)(untraced) is None, name
    # a job whose re-placed tasks needed no replica: the refetch reads, the replica's share is left out
    staged = Run(program_spans=[s for s in program if s[0] != "store.read.replica"], **fields)
    assert reader("layer_metrics", "replica_serve_s_per_job")(staged) is None
    assert reader("layer_metrics", "refetch_s_per_job")(staged) == pytest.approx(0.06)
    declared = {m["name"]: m for m in load_benchmark()["per_layer"]}
    cells = {w["name"] for w in load_benchmark()["workloads"]}
    for name in METRICS:
        metric = declared[name]
        assert CELL in metric["workloads"] and set(metric["workloads"]) <= cells
        assert (metric["moves"], metric["source"], metric["layer"], metric["better"]) == (
            "shuffle_throughput", "program_span", "reduce-side read", "lower")
    assert {m["name"] for m in load_cell(CELL).per_layer} >= set(METRICS) | {"fetch_faults", "read_task_p95_ms"}
    assert not {m["name"] for m in load_cell(CONTROL).per_layer} & set(METRICS)


# -- the cell through run.py: the rehearsal's readloss: line, and the controls --

DAMAGED = "gbt25k-readloss-damaged-4chip"
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-readlossjobs`` with its guarantee broken, as
the traffic file's ``control`` says: ``flipped`` (in every job, between the
exchange's return and the kill, one byte of one block's replica on the ring
successor is flipped where it lies: the first byte of the first value of the
lost executor's first block for one of its own partitions), ``both`` (the
lost executor's ring successor dies with it) and ``unreplicated`` (the
traffic as it is, on a configuration with replication off)."""

import ctypes

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-readlossjobs")
HEADER_BYTES = 19


class Entry(shipped.Entry):
    flip_map = None  # a map task of the lost executor, or None

    def after_exchange(self, shuffle_id):
        if self.flip_map is None:
            return
        meta = self.cluster.meta(shuffle_id)
        reduce_id = next(r for r in range(meta.num_reducers) if meta.owner_of_reduce(r) in self.lost
                         and meta.mapper_infos[self.flip_map].partitions[r][1] > HEADER_BYTES)
        holder = self.cluster.transport((self.lost[0] + 1) % self.cluster.num_executors)
        body, offset, length = holder.store.replica_view(shuffle_id, self.flip_map, reduce_id)
        byte = ctypes.c_ubyte.from_address(body.ctypes.data + offset + HEADER_BYTES)
        byte.value ^= 0x01


class Traffic(shipped.Traffic):
    def lost(self):
        lost = super().lost()
        if self.cell.traffic["control"] == "both":
            lost = lost + [(lost[0] + 1) % self.cell.chips]
        return lost

    def entry(self):
        made = super().entry()
        entry = Entry(self.manager, made.lost, made.replaced_task)
        if self.cell.traffic["control"] == "flipped":
            entry.flip_map = made.lost[0]  # map task m is executor m mod chips's
        return entry
'''
#: control -> the typed error a failed reduce task's line names, or None
#: where the bytes are read and the comparison finds them
CONTROLS = {"flipped": None, "both": "BlockNotFoundError", "unreplicated": "ExecutorLostError"}
CONFIG = "groupbytest-25k-repl-readloss-4chip"
UNREPLICATED = "groupbytest-25k-norepl-readloss-4chip"


def run_py(root, cell, seed, seconds, trace, rehearse, **env):
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1500)


def run_a_control(root, control, seed, seconds, rehearse, **env):
    """``run.py`` on the damaged cell in a copy of the benchmark under
    ``root``; returns the finished process."""
    shutil.rmtree(os.path.join(root, "benchmark"), ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, "manager-readlossjobs.json")) as f:
        shipped = json.load(f)
    with open(os.path.join(traffic, "manager-readlossjobs-damaged.json"), "w") as f:
        json.dump({**shipped, "driver": "manager-readlossjobs-damaged", "control": control}, f)
    with open(os.path.join(traffic, "manager-readlossjobs-damaged.py"), "w") as f:
        f.write(DAMAGED_DRIVER)
    bench = load_benchmark()
    config = CONFIG
    if control == "unreplicated":
        # the cell's configuration with the one key changed: its file, its
        # rehearsal and an entry of its own, beside the copy
        config = UNREPLICATED
        entry = dict(next(c for c in bench["configs"] if c["name"] == CONFIG))
        with open(os.path.join(ROOT, entry["file"])) as f:
            stated = json.load(f)
        stated["conf"]["replication_factor"] = stated["rehearse"]["conf"]["replication_factor"] = 0
        entry.update(name=config, file=f"benchmark/configs/{config}.json")
        with open(os.path.join(root, entry["file"]), "w") as f:
            json.dump(stated, f)
        bench["configs"].append(entry)
    bench["workloads"].append({"name": DAMAGED, "config": config,
                               "traffic": "manager-readlossjobs-damaged", "chips": 4, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def lines_of(out):
    lines = out.stdout.strip().splitlines()
    found = lambda label: json.loads(next(l for l in lines if l.startswith(label + ": ")).split(": ", 1)[1])
    return json.loads(lines[-1]), found


def failing_tasks(control, rehearse):
    """By the layout alone: the reduce tasks of a job that the planted fault
    must fail, in task order."""
    cell = load_cell(CELL, rehearse=rehearse)
    config, lost = cell.config, [cell.traffic["lost_executor"]]
    if control == "flipped":
        return [None]  # one, found by the comparison; which one is the driver's choice
    if control == "both":
        lost = lost + [(lost[0] + 1) % cell.chips]
    else:
        config = dict(config, conf=dict(config["conf"], replication_factor=0))
    tasks = readloss.replaced_tasks(config, {"lost_executor": lost}, cell.chips)
    return [r for r, task in enumerate(tasks) if task is not None and task["unserved_blocks"]]


def verdict(out, control, rehearse):
    """(the control came out as not correct for the reason planted, its last
    line, its ``window:`` line, its ``readloss:`` line)."""
    last, found = lines_of(out)
    window, lost = found("window"), found("readloss")
    error, failing = CONTROLS[control], failing_tasks(control, rehearse)
    caught = (out.returncode == 0 and last["correct"] is False and window["jobs"] >= 1 and len(failing) >= 1
              and window["warmup_failed_tasks"] == len(failing) and last["failed"] == len(failing) * window["jobs"]
              and lost["recoveries"] == 0 and lost["dead_recv_bytes_after_kill_max"] == 0)
    if error is None:  # every block was served, one of them with another byte: the comparison found it, no task raised
        caught &= "reduce task" not in out.stdout and lost["replica_blocks"] > 0
    else:  # typed, on the line of the first task that needed a block nobody holds; the others read on
        caught &= f"reduce task {failing[0]} of shuffle 0: {error}: " in out.stdout
        caught &= out.stdout.count(f": {error}: ") == len(failing) * (window["jobs"] + 1)
    return caught, last, window, lost


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_under_a_loss_after_the_exchange_too(tmp_path, control):
    out = run_a_control(str(tmp_path), control, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window, lost = verdict(out, control, rehearse=True)
    assert caught, (last, window["warmup_failed_tasks"], window["jobs"], lost, out.stdout[-1500:])
    assert lost["alive_at_end"] == [0, 1, 2, 3]  # whoever died came back


def test_rehearsal_of_the_readloss_cell_prints_the_readloss_line(tmp_path):
    """The traced CPU run: an executor lost after the exchange and regained in
    every job, the warm-up job too, the counters of the ``readloss:`` line
    what the reference's ``read_loss_geometry`` says of the rehearsal's own
    layout, ``fetch_faults`` the blocks replicas served, the three readers
    report, and nothing of a job is left after its removal."""
    out = run_py(ROOT, CELL, 3_000_000_019, 0.5, 1, True,
                 JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = lines_of(out)
    assert last["correct"] is True and last["failed"] == 0
    window, lost = found("window"), found("readloss")
    jobs = window["jobs"] + 1  # and the warm-up job
    cell = load_cell(CELL, rehearse=True)
    made = readloss.read_loss_geometry(cell.config, cell.traffic, cell.chips)
    assert made["replica_blocks"] > 0 and made["unserved_blocks"] == 0
    assert lost["jobs"] == jobs and lost["lost_executors"] == [made["lost_executor"]]
    assert lost["survivors"] == made["survivors"] and lost["replaced_tasks"] == made["replaced_tasks"] * jobs
    assert lost["refetched_blocks"] == made["pulled_blocks"] * jobs
    assert lost["refetched_bytes"] == made["pulled_bytes"] * jobs
    assert lost["replica_blocks"] == lost["failovers"] == made["replica_blocks"] * jobs
    assert lost["replica_bytes"] == made["replica_bytes"] * jobs
    assert lost["copied_blocks"] == lost["refetched_blocks"]
    assert lost["resident_blocks"] + lost["copied_blocks"] == window["job_blocks"] * jobs
    assert (lost["blocks_retried"], lost["fetch_timeouts"], lost["recoveries"]) == (0, 0, 0)
    assert lost["replicated_bytes"] == made["replicated_bytes"] * jobs == window["job_bytes"] * jobs
    assert lost["lost_recv_bytes"] > 0 and lost["dead_recv_bytes_after_kill_max"] == 0
    assert window["fetch_faults"] == made["replica_blocks"] * window["jobs"] and window["warmup_failed_tasks"] == 0
    assert window["compiles_in_window"]["compiles"] == 0
    assert lost["alive_at_end"] == [0, 1, 2, 3] and lost["epoch"] == 2 * jobs
    assert lost["replica_bytes_after_remove_max"] == 0
    first, _, end = lost["pool_held_bytes_after_remove"]
    assert first == end > 0 and lost["pool_held_bytes"][made["lost_executor"]] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    assert metrics["fetch_faults"] == window["fetch_faults"]
    assert metrics["read_s_per_job"] > metrics["refetch_s_per_job"] > metrics["replica_serve_s_per_job"] > 0
    assert metrics["refetch_block_p50_us"] > 0
    assert found("trace")["program_spans_dropped"] == 0


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
    all_caught = True
    for i, control in enumerate(args.control or sorted(CONTROLS)):
        out = run_a_control(root, control, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        caught, last, window, lost = verdict(out, control, args.rehearse)
        all_caught &= caught
        print(json.dumps({"control": control, "control_caught": caught, "jobs": window["jobs"],
                          "warmup_failed_tasks": window["warmup_failed_tasks"], "job_s": window["job_s"],
                          "readloss": lost, "last": last}), flush=True)
    sys.exit(0 if all_caught else 1)

"""The cell that loses an executor in every job (``gbt25k-execloss-4chip``):
what ``references/groupby-loss.py`` says the loss must cost against the
configuration's and the traffic's files, the four readers of the cell, the
refusal of a program that cannot lose the same executor twice, the
rehearsal's ``loss:`` line, and the controls.

The controls: the cell's own job with its guarantee broken — ``flipped`` (one
byte of one block's replica on executor 3 flipped after the replication and
before the kill: the restaged block is not the block that was written),
``both`` (executors 2 AND 3 lost: the only replica went with its holder) and
``unreplicated`` (the same traffic on the cell's configuration with
``replication_factor`` 0) — through ``run.py`` itself in a copy of the
benchmark with a throw-away driver (data and a driver added, nothing edited).
As tests they run the CPU form; on the chip this file is a program that runs
them at the cell's own size (``python3 tests/benchmark/test_benchmark_loss.py
--seed <n> --seconds <s>``) and exits 0 only if every one came out as not
correct, for the reason planted."""

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

loss = load_module("references", "groupby-loss")

CELL = "gbt25k-execloss-4chip"
SIBLING = "gbt25k-jobs-4chip"
METRICS = ("replicate_s_per_job", "recover_s_per_job", "recover_restage_s_per_job",
           "degraded_subexchanges_per_job")


def traffic_of(cell, rehearse=False):
    """The traffic file's parameters as the driver takes them."""
    traffic = dict(cell.traffic)
    return {**traffic, **traffic["rehearse"]} if rehearse else traffic


# -- the configuration, the traffic and the geometry of the loss ---------------


def test_the_configuration_is_the_controls_but_for_the_guarantee():
    """Every shape of the source job as ``groupbytest-25k-4chip`` has it; what
    differs is the conf (factor 1, elastic; nothing else), the guarantee, the
    reference's wrapper and what had to be assumed."""
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "groupbytest-25k-repl-4chip", "manager-lossjobs", 4)
    config, sibling = load_cell(CELL).config, load_cell(SIBLING).config
    differs = {"source", "reference", "conf", "guarantees", "assumed", "rehearse"}
    assert {key for key in sibling if config[key] != sibling[key]} == differs
    assert set(config) - set(sibling) == {"store", "loss"}
    assert config["conf"] == {"replication_factor": 1, "elastic": True}
    assert config["reference"] == "groupby-loss" and list(config["reduced"]) == ["mappers"]
    assert config["assumed"][: len(sibling["assumed"])] == sibling["assumed"]
    for word in ("although the executor that held it died", "ring successor", "BlockNotFoundError"):
        assert word in config["guarantees"], word
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["mappers"]
    # the rehearsal keeps the conf's two keys and stages several rounds
    tiny = load_cell(CELL, rehearse=True).config
    assert {k: tiny["conf"][k] for k in config["conf"]} == config["conf"]
    assert tiny["store"]["staging_bytes"] == tiny["conf"]["staging_capacity_per_executor"]
    assert (tiny["mappers"], tiny["pairs_per_mapper"]) == (
        sibling["rehearse"]["mappers"], sibling["rehearse"]["pairs_per_mapper"])


def test_the_traffic_names_the_event():
    traffic = load_cell(CELL).traffic
    assert traffic["driver"] == "manager-lossjobs"
    assert (traffic["lost_executor"], traffic["lost_at_round"], traffic["every_job"], traffic["rejoin"]) == (
        2, 4, True, "after remove")
    assert 0 < traffic["rehearse"]["lost_at_round"]  # some rounds have gone through there too


def test_the_store_of_the_geometry_is_the_programs_default():
    from sparkucx_tpu.config import TpuShuffleConf

    store, conf = load_cell(CELL).config["store"], TpuShuffleConf()
    assert store["staging_bytes"] == conf.staging_capacity_per_executor
    assert store["alignment"] == conf.block_alignment


def test_the_configuration_states_the_geometry_of_the_loss():
    """The file's ``loss`` block is ``loss_geometry(config, traffic, chips)``:
    the dead executor's two map tasks, their 400 blocks and 250,190,000 bytes
    from executor 3's replicas, 4 rounds aborted and 9 run again."""
    cell = load_cell(CELL)
    stated = dict(cell.config["loss"])
    stated.pop("from")
    made = loss.loss_geometry(cell.config, traffic_of(cell), cell.chips)
    assert stated == made
    assert made["lost_map_tasks"] == [2, 6] and made["replica_holder"] == 3
    assert made["survivors"] == [0, 1, 3] and made["shrunk_mesh"] == [0, 1] and made["waves"] == 2
    assert (made["restaged_blocks"], made["restaged_bytes"]) == (400, 250_190_000)
    assert made["replicated_bytes"] == 8 * 5000 * 25019 == 1_000_760_000
    assert (made["rounds"], made["rounds_aborted"], made["rounds_rerun"]) == (9, 4, 9)
    assert made["subexchanges"] == sum(made["subexchanges_per_round"]) <= 4 * 9
    assert all(1 <= n <= 4 for n in made["subexchanges_per_round"])
    with pytest.raises(ValueError, match="outside the job's 9"):
        loss.loss_geometry(cell.config, {"lost_executor": 2, "lost_at_round": 9}, 4)


def test_the_records_are_the_plain_groupbys():
    """The wrapper loads the control's generator and check: the same seed
    gives the same job, so a job that recovers is held to the job that lost
    nothing."""
    groupby = load_module("references", "groupby")
    config = {"mappers": 3, "pairs_per_mapper": 30, "value_bytes": 64, "reducers": 7, "keys": "uniform-int31"}
    ours, theirs = loss.make_records(config, 3_000_000_019), groupby.make_records(config, 3_000_000_019)
    assert ours.blocks == theirs.blocks and ours.expected == theirs.expected and ours.groups == theirs.groups
    assert type(ours.check(0)) is groupby.TaskCheck and type(ours.check(0, full=True)) is groupby.FullCheck
    sizes = loss.block_bytes(config)
    assert [[(r, len(p)) for r, p in parts] for parts in ours.blocks] == [
        [(r, int(n)) for r, n in enumerate(row) if n] for row in sizes]


def test_a_program_that_cannot_lose_an_executor_twice_is_refused(monkeypatch):
    """The parent commit under this benchmark: out at ``start``, before a
    record is made — never a hang, never failing tasks that read as speed."""
    from sparkucx_tpu.transport import tpu as program

    driver = load_module("traffic", "manager-lossjobs")
    driver.require_repeatable_loss()
    monkeypatch.delattr(program.TpuShuffleTransport, "restart")
    with pytest.raises(SystemExit, match="needs"):
        driver.Traffic(load_cell(CELL, rehearse=True), None).start(None, {})


def test_a_reduce_task_that_saw_the_loss_is_a_failed_task():
    """The recovery is the exchange's: a reader that retried, failed over or
    timed out a fetch was not served by a recovered shuffle, and its task
    raises (``run_job`` counts it failed and names it) where ``manager-jobs``
    only counts the fault."""
    from types import SimpleNamespace

    driver = load_module("traffic", "manager-lossjobs")

    def manager_whose_reader_reports(**counted):
        metrics = SimpleNamespace(**{"blocks_retried": 0, "failovers": 0, "fetch_timeouts": 0, **counted})
        reader = SimpleNamespace(metrics=metrics, read=lambda: iter([(7, b"v")]))
        return SimpleNamespace(cluster=None, get_reader=lambda sid, lo, hi: reader)

    seen = []
    entry = driver.Entry(manager_whose_reader_reports(), [2], 4)
    assert entry.read(0, 5, [0], lambda key, value: seen.append((key, value))) == 0
    assert seen == [(7, b"v")]
    for name in driver.shipped.FAULT_COUNTERS:
        entry = driver.Entry(manager_whose_reader_reports(**{name: 1}), [2], 4)
        with pytest.raises(AssertionError, match="1 fetch.* a reduce task never sees it"):
            entry.read(0, 5, [0], lambda key, value: None)


# -- the four readers ----------------------------------------------------------


def test_the_four_readers_on_a_run_made_up_by_hand():
    """Seconds of ``exchange.replicate``, ``exchange.recover`` and
    ``exchange.recover.restage`` inside each job's ``job.exchange`` and the
    ``exchange.collective.degraded`` spans that begin there, each the median
    over the jobs; nothing where nothing was recorded."""
    ms = 1_000_000
    jobs = [JobResult(seconds=3.0, tasks=208, failed=0, faults=0, read_task_s=[0.001])] * 3
    spans = [("job.exchange", 0, 1000 * ms), ("job.read", 1000 * ms, 1100 * ms),
             ("job.exchange", 2000 * ms, 3000 * ms), ("job.exchange", 4000 * ms, 5000 * ms)]
    program = [
        ("exchange.assemble", 310 * ms, 311 * ms),
        ("exchange.replicate", 10 * ms, 310 * ms),  # 0.3 s in the first job
        ("exchange.recover", 400 * ms, 900 * ms),  # 0.5 s
        ("exchange.recover.restage", 400 * ms, 450 * ms),
        ("exchange.collective.degraded", 500 * ms, 501 * ms), ("exchange.collective.degraded", 600 * ms, 601 * ms),
        ("exchange.replicate", 2010 * ms, 2210 * ms),  # 0.2 s in the second
        ("exchange.recover", 2300 * ms, 2990 * ms),  # 0.69 s
        ("exchange.recover.restage", 2300 * ms, 2370 * ms),
        *[("exchange.collective.degraded", (2400 + 10 * i) * ms, (2401 + 10 * i) * ms) for i in range(4)],
        ("exchange.replicate", 4010 * ms, 4410 * ms),  # 0.4 s in the third
        ("exchange.recover", 4500 * ms, 4900 * ms),  # 0.4 s
        ("exchange.recover.restage", 4500 * ms, 4560 * ms),
        *[("exchange.collective.degraded", (4600 + 10 * i) * ms, (4601 + 10 * i) * ms) for i in range(3)],
        ("exchange.collective.degraded", 5500 * ms, 5501 * ms),  # in no job's exchange
        ("exchange.recover", 6000 * ms, 6500 * ms),
    ]
    fields = dict(chips=4, device_kind="TPU v5 lite", setup_s=60.0, job_bytes=10**9, jobs=jobs, spans=spans,
                  rounds=[9, 9, 9], stats_before={}, stats_after={}, fetch_faults=0)
    run = Run(program_spans=program, **fields)
    assert reader("layer_metrics", "replicate_s_per_job")(run) == pytest.approx(0.3)
    assert reader("layer_metrics", "recover_s_per_job")(run) == pytest.approx(0.5)
    assert reader("layer_metrics", "recover_restage_s_per_job")(run) == pytest.approx(0.06)
    assert reader("layer_metrics", "degraded_subexchanges_per_job")(run) == 3
    # a job that lost nothing, an untraced run, the parent's program: left out
    whole = Run(program_spans=[("exchange.assemble", 1 * ms, 2 * ms)], **fields)
    untraced = Run(**dict(fields, jobs=[]))
    for name in METRICS:
        assert reader("layer_metrics", name)(whole) is None, name
        assert reader("layer_metrics", name)(untraced) is None, name
    # a program before the restage span: the three older spans still read
    older = Run(program_spans=[s for s in program if s[0] != "exchange.recover.restage"], **fields)
    assert reader("layer_metrics", "recover_restage_s_per_job")(older) is None
    assert reader("layer_metrics", "recover_s_per_job")(older) == pytest.approx(0.5)
    declared = {m["name"]: m for m in load_benchmark()["per_layer"]}
    cells = {w["name"] for w in load_benchmark()["workloads"]}
    for name in METRICS:
        metric = declared[name]
        assert CELL in metric["workloads"] and set(metric["workloads"]) <= cells
        assert (metric["moves"], metric["source"], metric["layer"]) == (
            "shuffle_throughput", "program_span", "plan executor")
    assert {m["name"] for m in load_cell(CELL).per_layer} >= set(METRICS)
    assert not {m["name"] for m in load_cell(SIBLING).per_layer} & set(METRICS)


# -- the cell through run.py: the rehearsal's loss: line, and the controls -----

DAMAGED = "gbt25k-execloss-damaged-4chip"
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-lossjobs`` with its guarantee broken, as the
traffic file's ``control`` says: ``flipped`` (in every job, after the
replication and before the kill — at the submit of staging round 0 — one byte
of one block's replica on the ring successor is flipped where it lies: the
first byte of the first value of the lost executor's first block), ``both``
(the lost executor's ring successor dies with it) and ``unreplicated`` (the
traffic as it is, on a configuration with replication off)."""

import ctypes

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-lossjobs")
HEADER_BYTES = 19


class Entry(shipped.Entry):
    def __init__(self, manager, lost, at_round, block):
        super().__init__(manager, lost, at_round)
        self.block = block  # (map task of the lost executor, reducer) or None

    def exchange(self, shuffle_id):
        from sparkucx_tpu.testing import faults

        def flip(**_ctx):
            holder = self.cluster.transport((self.lost[0] + 1) % self.cluster.num_executors)
            body, offset, length = holder.store.replica_view(shuffle_id, *self.block)
            assert length > HEADER_BYTES
            byte = ctypes.c_ubyte.from_address(body.ctypes.data + offset + HEADER_BYTES)
            byte.value ^= 0x01

        armed = None
        if self.block is not None:
            armed = faults.arm("exchange.submit", flip, times=1, match={"shuffle_id": shuffle_id, "round": 0})
        try:
            super().exchange(shuffle_id)
        finally:
            if armed is not None:
                faults.disarm(armed)


class Traffic(shipped.Traffic):
    def entry(self):
        shipped_entry = super().entry()
        control = self.cell.traffic["control"]
        lost, block = shipped_entry.lost, None
        if control == "both":
            lost = lost + [(lost[0] + 1) % self.cell.chips]
        elif control == "flipped":
            map_id = lost[0]  # map task m is executor m mod chips's
            block = (map_id, self.records.blocks[map_id][0][0])
        return Entry(self.manager, lost, shipped_entry.at_round, block)
'''
#: control -> (the typed error a reduce task's line names or None, whether
#: every reduce task of a job fails or one)
CONTROLS = {
    "flipped": (None, False),
    "both": ("BlockNotFoundError", True),
    "unreplicated": ("ExecutorLostError", True),
}
CONFIG = "groupbytest-25k-repl-4chip"
UNREPLICATED = "groupbytest-25k-norepl-4chip"


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
    with open(os.path.join(traffic, "manager-lossjobs.json")) as f:
        shipped = json.load(f)
    with open(os.path.join(traffic, "manager-lossjobs-damaged.json"), "w") as f:
        json.dump({**shipped, "driver": "manager-lossjobs-damaged", "control": control}, f)
    with open(os.path.join(traffic, "manager-lossjobs-damaged.py"), "w") as f:
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
                               "traffic": "manager-lossjobs-damaged", "chips": 4, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def lines_of(out):
    lines = out.stdout.strip().splitlines()
    found = lambda label: json.loads(next(l for l in lines if l.startswith(label + ": ")).split(": ", 1)[1])
    return json.loads(lines[-1]), found


def verdict(out, control):
    """(the control came out as not correct for the reason planted, its last
    line, its ``window:`` line, its ``loss:`` line)."""
    last, found = lines_of(out)
    window, lost = found("window"), found("loss")
    error, every_task = CONTROLS[control]
    reducers = load_cell(CELL).config["reducers"]
    a_job = reducers if every_task else 1
    caught = (out.returncode == 0 and last["correct"] is False and window["jobs"] >= 1
              and window["warmup_failed_tasks"] == a_job and last["failed"] == a_job * window["jobs"])
    if error is None:  # the recovery ran, on other bytes: the comparison found it, no task raised
        caught &= lost["recoveries"] == window["jobs"] + 1 and "reduce task" not in out.stdout
    else:  # no recovery: the typed error, on a reduce task's line
        caught &= lost["recoveries"] == 0 and f"reduce task 0 of shuffle 0: {error}: " in out.stdout
    return caught, last, window, lost


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_under_a_lost_executor_too(tmp_path, control):
    out = run_a_control(str(tmp_path), control, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window, lost = verdict(out, control)
    assert caught, (last, window["warmup_failed_tasks"], window["jobs"], lost)
    assert lost["alive_at_end"] == [0, 1, 2, 3]  # whoever died came back


def test_rehearsal_of_the_loss_cell_prints_the_loss_line(tmp_path):
    """The traced CPU run: an executor lost and regained in every job, the
    warm-up job too, the counters of the ``loss:`` line what the reference's
    ``loss_geometry`` says of the rehearsal's own layout, the four readers
    report, and nothing of a job is left after its removal."""
    out = run_py(ROOT, CELL, 3_000_000_019, 0.5, 1, True,
                 JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = lines_of(out)
    assert last["correct"] is True and last["failed"] == 0
    window, lost = found("window"), found("loss")
    jobs = window["jobs"] + 1  # and the warm-up job
    assert lost["jobs"] == jobs and lost["recoveries"] == window["jobs"] + 1
    assert window["fetch_faults"] == 0 and window["warmup_failed_tasks"] == 0
    assert window["compiles_in_window"]["compiles"] == 0
    cell = load_cell(CELL, rehearse=True)
    made = loss.loss_geometry(cell.config, traffic_of(cell, rehearse=True), cell.chips)
    assert (lost["lost_executors"], lost["lost_at_round"]) == ([made["lost_executor"]], made["rounds_aborted"])
    assert lost["restaged_blocks"] == made["restaged_blocks"] * jobs
    assert lost["restaged_bytes"] == made["restaged_bytes"] * jobs
    assert lost["replicated_bytes"] == made["replicated_bytes"] * jobs == window["job_bytes"] * jobs
    assert lost["degraded_subexchanges"] == made["subexchanges"] * jobs
    assert set(window["rounds_per_job"]) == {made["rounds"]}
    assert lost["alive_at_end"] == [0, 1, 2, 3] and lost["epoch"] == 2 * jobs
    assert lost["replica_bytes_after_remove_max"] == 0
    first, _, end = lost["pool_held_bytes_after_remove"]
    assert first == end > 0 and lost["pool_held_bytes"][made["lost_executor"]] == 0
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    assert metrics["degraded_subexchanges_per_job"] == made["subexchanges"]
    assert metrics["staging_rounds_per_job"] == made["rounds"]
    assert metrics["recover_s_per_job"] > metrics["recover_restage_s_per_job"] > 0
    assert metrics["replicate_s_per_job"] > 0
    assert metrics["exchange_s_per_job"] > metrics["replicate_s_per_job"] + metrics["recover_s_per_job"]
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
        caught, last, window, lost = verdict(out, control)
        all_caught &= caught
        print(json.dumps({"control": control, "control_caught": caught, "jobs": window["jobs"],
                          "warmup_failed_tasks": window["warmup_failed_tasks"], "job_s": window["job_s"],
                          "loss": lost, "last": last}), flush=True)
    sys.exit(0 if all_caught else 1)

"""The Zipf job at the peer region a v5e-16 run as one mesh gives a block
(``gbt25k-zipf-mesh16-4chip``): the configuration's file against its control's
and against ``references/groupby-zipf-regions.py``, the three readers on a run
made up by hand, and the controls — the job through ``run.py`` in a copy of the
benchmark with a throw-away driver that breaks how a block staged in pieces is
put together for a timed job's reader (a piece dropped; two pieces exchanged):
each has to come out as not ``correct``, the unbroken job as ``correct``.

As a test it runs the CPU form; on the chip the same file is a program that
runs the three at the cell's own size (``python3
tests/benchmark/test_benchmark_regions.py --seed <n> --seconds <s>``) and
exits 0 only if every one came out as it must."""

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

CELL = "gbt25k-zipf-mesh16-4chip"
CONTROL_OF = "gbt25k-zipf-4chip"
MODES = ("dropped", "exchanged", "unbroken")
BROKEN_DRIVER = '''"""A throw-away control: ``manager-jobs`` on a program whose cluster puts a
block staged in pieces together wrongly for the readers of every timed job
(the warm-up job, shuffle 0, is left whole, so it is the window's comparison
that has to notice): ``dropped`` leaves the block's second piece out,
``exchanged`` hands its first two pieces out in each other's place,
``unbroken`` changes nothing.  The blocks broken are map task 0's."""

import numpy as np

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-jobs")


class Traffic(shipped.Traffic):
    def start(self, conf, parts):
        manager = super().start(conf, parts)
        cluster, mode = manager.cluster, self.cell.traffic["mode"]
        whole = cluster._received_block
        self.broken = 0

        def received_block(meta, consumer, map_id, reduce_id, starts=None, assembled=None):
            view, length = whole(meta, consumer, map_id, reduce_id, starts, assembled)
            splits = meta.mapper_infos[map_id].splits
            if mode == "unbroken" or meta.shuffle_id == 0 or map_id != 0 or not splits or reduce_id not in splits:
                return view, length
            first, second = (nbytes for _, _, nbytes in splits[reduce_id][:2])
            rest = view[first + second:]
            if mode == "dropped":
                view = np.concatenate([view[:first], rest])
            else:
                view = np.concatenate([view[first : first + second], view[:first], rest])
            self.broken += 1
            return view, len(view)

        cluster._received_block = received_block
        return manager

    def close(self):
        print("control: " + str(self.broken) + " blocks handed out broken", flush=True)
        super().close()
'''


def geometry_of(config, chips):
    return load_module("references", config["reference"]).geometry(config, chips)


# -- the configuration ---------------------------------------------------------------


def test_the_configuration_is_the_controls_but_for_the_deployment():
    from sparkucx_tpu.config import TpuShuffleConf

    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    control = load_cell(CONTROL_OF).config
    config = load_cell(CELL).config
    differ = {"source", "deployment", "reference", "conf", "assumed", "geometry", "rehearse", "reduced", "guarantees",
              "deployment_executors", "region_bytes", "row_bytes"}
    assert set(config) == set(control) | differ
    assert all(config[key] == control[key] for key in set(control) - differ)
    assert config["reduced"]["mappers"] == control["reduced"]["mappers"] and set(config["reduced"]) == {"mappers", "executors"}
    assert sorted(entry["reduced"]) == ["executors", "mappers"] and entry["source"] == config["source"]
    assert config["assumed"][: len(control["assumed"])] == control["assumed"] and len(config["assumed"]) > len(control["assumed"])
    assert config["guarantees"].startswith(control["guarantees"].split(";")[0])
    assert "never split across receivers" in config["guarantees"] and "default conf but the staging" in config["guarantees"]
    for needle in ("buildlib/test.sh:169-173", "Zipf 0.99", "200000", "4 of a v5e-16 mesh's 16 executors", "4 MiB regions"):
        assert needle in config["source"], needle
    assert len(config["source"]) <= 200 and config["source"] != control["source"]
    # the deployment's region, from the default conf and nothing else
    default = TpuShuffleConf()
    assert config["deployment_executors"] == 16 and config["region_bytes"] == 4 << 20
    assert config["region_bytes"] == default.staging_capacity_per_executor // config["deployment_executors"]
    assert config["row_bytes"] == default.block_alignment
    assert config["conf"] == {"staging_capacity_per_executor": config["region_bytes"] * cell["chips"]}
    conf = TpuShuffleConf(**config["conf"])
    changed = {name: getattr(conf, name) for name in vars(default) if getattr(conf, name) != getattr(default, name)}
    assert changed == config["conf"]
    assert (cell["traffic"], cell["chips"]) == ("manager-jobs", 4)


def test_the_configuration_states_the_references_geometry():
    cell = load_cell(CELL)
    stated = dict(cell.config["geometry"])
    stated.pop("from")
    made = geometry_of(cell.config, cell.chips)
    assert stated == made
    control = load_cell(CONTROL_OF)
    theirs = load_module("references", control.config["reference"]).geometry(control.config, control.chips)
    assert {key: made[key] for key in theirs} == theirs  # the same job
    assert made["blocks_over_a_region"] == made["blocks_over_4MiB"] == 15
    assert made["bytes_in_blocks_over_a_region"] == 110_934_246
    assert made["share_of_the_job_in_blocks_over_a_region"] == pytest.approx(0.1108, abs=1e-4)
    assert made["hottest_reducer_blocks_over_a_region"] == made["hottest_reducer_blocks"] == 8
    assert made["least_pieces"] == 38 and 2.4 < made["largest_block_in_regions"] < 2.5
    assert made["map_tasks_with_a_block_over_a_region"] == cell.config["mappers"]


def test_the_geometry_counts_what_no_placement_can_avoid():
    """Blocks by hand: which are over a region once padded to rows, their
    bytes, the fewest pieces, the hottest reducer's."""
    regions = load_module("references", "groupby-zipf-regions")
    config = {"mappers": 3, "pairs_per_mapper": 80, "value_bytes": 64, "reducers": 7, "keys": "zipf",
              "zipf_s": 0.99, "distinct_keys": 70, "region_bytes": 1024, "row_bytes": 512}
    made = regions.geometry(config, 2)
    blocks = regions.block_bytes(config)
    over = [int(b) for b in blocks.reshape(-1) if -(-int(b) // 512) * 512 > 1024]
    assert over and made["blocks_over_a_region"] == len(over) and made["bytes_in_blocks_over_a_region"] == sum(over)
    assert made["least_pieces"] == sum(-(-(-(-b // 512) * 512) // 1024) for b in over)
    hottest = int(blocks.sum(axis=0).argmax())
    assert made["hottest_reducer_blocks_over_a_region"] == sum(-(-int(b) // 512) * 512 > 1024 for b in blocks[:, hottest])
    assert regions.make_records is load_module("references", "groupby-zipf").make_records  # loaded, not copied


def test_the_rehearsal_has_a_block_over_a_region_too():
    cell = load_cell(CELL, rehearse=True)
    assert cell.config["region_bytes"] * cell.chips == cell.config["conf"]["staging_capacity_per_executor"]
    made = geometry_of(cell.config, cell.chips)
    assert made["blocks_over_a_region"] >= 1 and made["least_pieces"] > made["blocks_over_a_region"]


def test_the_three_metrics_list_the_cell_alone_and_the_unlisted_ones_report():
    bench = load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {"split_blocks_per_job": "map-side write", "split_write_s_per_job": "map-side write",
              "block_assemble_s_per_job": "reduce-side read"}
    for name, layer in layers.items():
        metric = declared[name]
        assert CELL in metric["workloads"] and CONTROL_OF not in metric["workloads"]
        assert (metric["layer"], metric["moves"], metric["source"]) == (layer, "shuffle_throughput", "program_span")
    mine = {m["name"] for m in load_cell(CELL).per_layer}
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine >= unlisted | set(layers)
    assert {"staging_rounds_per_job", "padding_share", "exchange_roofline", "read_task_p95_ms"} <= unlisted


# -- the readers ------------------------------------------------------------------------

MS = 1_000_000


def a_run(program, jobs=3):
    """``jobs`` jobs a second apart: ``job.write`` its first 400 ms,
    ``job.read`` from 500 to 900 ms."""
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    own = []
    for j in range(jobs):
        own += [("job.write", j * 1000 * MS, j * 1000 * MS + 400 * MS),
                ("job.read", j * 1000 * MS + 500 * MS, j * 1000 * MS + 900 * MS)]
    return Run(chips=4, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job] * jobs,
               spans=own, rounds=[1] * jobs, stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(program))


def test_the_three_readers_on_a_run_made_up_by_hand():
    tasks = [("write.task", j * 1000 * MS + 1 * MS, j * 1000 * MS + 399 * MS) for j in range(3)]
    windows = [("read.window", j * 1000 * MS + 501 * MS, j * 1000 * MS + 899 * MS) for j in range(3)]
    windows.append(("exchange.assemble", 401 * MS, 402 * MS))  # once a round in every program that records any span
    program = tasks + windows + [
        # job 0: two split blocks of 10 and 30 ms; job 1: one of 20 ms; job 2: three, one of them
        # begun in the write and ended after it (counted where it begins, timed where it lies)
        ("store.block_split", 10 * MS, 20 * MS), ("store.block_split", 100 * MS, 130 * MS),
        ("store.block_split", 1050 * MS, 1070 * MS),
        ("store.block_split", 2010 * MS, 2015 * MS), ("store.block_split", 2020 * MS, 2025 * MS),
        ("store.block_split", 2390 * MS, 2420 * MS),
        ("store.block_split", 450 * MS, 460 * MS),  # in no job's write
        ("store.rollover", 12 * MS, 13 * MS),  # another span's time is not this one's
        ("read.block_assemble", 510 * MS, 514 * MS), ("read.block_assemble", 600 * MS, 602 * MS),
        ("read.block_assemble", 1600 * MS, 1601 * MS),
        ("read.block_assemble", 2950 * MS, 2960 * MS),  # after the job's read
    ]
    run = a_run(program)
    assert reader("layer_metrics", "split_blocks_per_job")(run) == 2.0  # median of 2, 1, 3
    assert reader("layer_metrics", "split_write_s_per_job")(run) == pytest.approx(0.020)  # of 0.040, 0.020, 0.020
    assert reader("layer_metrics", "block_assemble_s_per_job")(run) == pytest.approx(0.001)  # of 0.006, 0.001, 0
    # a window with tasks and windows and no split: zero, not nothing
    quiet = a_run(tasks + windows)
    assert [reader("layer_metrics", name)(quiet) for name in
            ("split_blocks_per_job", "split_write_s_per_job", "block_assemble_s_per_job")] == [0.0, 0.0, 0.0]
    # an untraced run, and a program that records neither marker: left out of the line
    for empty in (a_run([]), a_run([("store.rollover", 12 * MS, 13 * MS), ("exchange.assemble", 1 * MS, 2 * MS)])):
        assert [reader("layer_metrics", name)(empty) for name in
                ("split_blocks_per_job", "split_write_s_per_job", "block_assemble_s_per_job")] == [None, None, None]


# -- the controls ----------------------------------------------------------------------


def run_a_control(root, mode, seed, seconds, rehearse, **env):
    """``run.py`` on the cell's job with the broken driver in a copy of the
    benchmark under ``root``; returns the finished process."""
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, "manager-brokenpieces.json"), "w") as f:
        json.dump({"driver": "manager-brokenpieces", "mode": mode}, f)
    with open(os.path.join(traffic, "manager-brokenpieces.py"), "w") as f:
        f.write(BROKEN_DRIVER)
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    name = f"{CELL}-{mode}"
    bench["workloads"].append({**cell, "name": name, "traffic": "manager-brokenpieces", "why": f"a control of {CELL}"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", name, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0"] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1500)


def verdict(out, mode):
    """(the control came out as it must, its last line, its ``window:``
    line, the blocks the driver broke)."""
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    window = json.loads(next(line for line in lines if line.startswith("window: ")).split(": ", 1)[1])
    broken = int(next(line for line in lines if line.startswith("control: ")).split()[1])
    sound_warmup = out.returncode == 0 and window["warmup_failed_tasks"] == 0 and window["jobs"] >= 1
    if mode == "unbroken":
        caught = sound_warmup and last["correct"] is True and last["failed"] == 0 and broken == 0
    else:  # at least the hottest reduce task of every timed job, and only tasks that read a broken block
        caught = (sound_warmup and last["correct"] is False and window["jobs"] <= last["failed"] <= broken
                  and broken >= window["jobs"])
    return caught, last, window, broken


@pytest.mark.parametrize("mode", MODES)
def test_a_lost_block_comes_out_as_not_correct_where_a_split_blocks_pieces_are(tmp_path, mode):
    out = run_a_control(str(tmp_path / "copy"), mode, seed=2147483693, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window, broken = verdict(out, mode)
    assert caught, (mode, last, window["warmup_failed_tasks"], window["jobs"], broken)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--modes", default=",".join(MODES))
    args = ap.parse_args()
    results = []
    for i, mode in enumerate(args.modes.split(",")):
        root = os.path.join(ROOT, ".scratch", "control-regions")  # inside the checkout, listed in .gitignore
        out = run_a_control(root, mode, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        caught, last, window, broken = verdict(out, mode)
        results.append(caught)
        print(json.dumps({"control": mode, "came_out_as_it_must": caught, "jobs": window["jobs"], "broken_blocks": broken,
                          "warmup_failed_tasks": window["warmup_failed_tasks"], "last": last}), flush=True)
    sys.exit(0 if all(results) else 1)

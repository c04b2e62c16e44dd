"""The device-producer cell's own files: the reference's packed map output and
its inverse on seeded records, the driver's refusal of a program without the
packed device write, its conditions for an unsound run on doctored reports,
what ``BENCHMARK.json`` declares for the cell, and the cell's CPU form end to
end.

CPU backend: counts and bytes, no rate."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell, load_module

devmap = load_module("references", "groupby-devmap")
driver = load_module("traffic", "manager-devproduce")

CELL = "gbt25k-devproducer-1chip"
ROW = 512
CONFIG = {"mappers": 5, "pairs_per_mapper": 60, "value_bytes": 700, "reducers": 7, "keys": "uniform-int31"}


@pytest.fixture(scope="module")
def records():
    return devmap.make_records(CONFIG, seed=3_000_000_019)


# -- the reference ------------------------------------------------------------


@pytest.mark.parametrize("m", range(CONFIG["mappers"]))
def test_unpack_gives_the_mappers_blocks_back(records, m):
    packed, reduce_ids, lengths = devmap.map_output(records, m, ROW)
    assert packed.dtype == np.int32 and packed.shape[1] == ROW // 4
    assert packed.shape[0] == sum(-(-n // ROW) for n in lengths)  # each block from a fresh row
    assert reduce_ids == sorted(set(reduce_ids)) and len(set(lengths)) > 1  # reducer order, ragged
    assert devmap.unpack(packed, reduce_ids, lengths) == records.blocks[m]
    # the rest of a block's last row is zeros
    flat, at = packed.reshape(-1).view(np.uint8), 0
    for n in lengths:
        rows = -(-n // ROW)
        assert not flat[at + n : at + rows * ROW].any()
        at += rows * ROW


def test_the_records_and_the_consumers_are_the_hbm_references(records):
    hbm = load_module("references", "groupby-hbm")
    assert devmap.make_records is hbm.make_records and isinstance(records, hbm.Records)
    assert type(records.check(0)) is hbm.TaskCheck and type(records.check(0, full=True)) is hbm.FullCheck


@pytest.mark.parametrize("pack_rows", [1, 64, 4096])
def test_on_device_rounds_the_capacity_up_with_a_zero_tail(records, pack_rows):
    import jax

    packed, reduce_ids, lengths = devmap.map_output(records, 1, ROW)
    put = devmap.on_device(packed, jax.devices()[0], pack_rows)
    assert put.devices() == {jax.devices()[0]} and put.shape[0] % pack_rows == 0
    assert 0 <= put.shape[0] - packed.shape[0] < pack_rows
    host = np.asarray(put)
    assert (host[: len(packed)] == packed).all() and not host[len(packed):].any()
    assert devmap.unpack(host, reduce_ids, lengths) == records.blocks[1]


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    assert "sparkucx_tpu" not in inspect.getsource(devmap)


# -- the driver ---------------------------------------------------------------


def test_the_driver_refuses_a_writer_without_the_packed_device_write():
    from sparkucx_tpu.shuffle.writer import TpuShuffleMapOutputWriter

    driver.require_device_write(TpuShuffleMapOutputWriter)  # this program: accepted

    class ParentWriter:  # the parent commit's: a device array a block
        def write_partition_device(self, reduce_id, rows, length=None):
            pass

    with pytest.raises(SystemExit, match="write_partitions_device"):
        driver.require_device_write(ParentWriter)


@pytest.mark.parametrize("missing", ["writer", "reader"])
def test_the_driver_fails_before_any_record_is_made(monkeypatch, missing):
    """``start`` on the parent's program: no records, no manager, no array."""
    import sparkucx_tpu.shuffle.reader as reader_module
    import sparkucx_tpu.shuffle.writer as writer_module

    if missing == "writer":
        monkeypatch.delattr(writer_module.TpuShuffleMapOutputWriter, "write_partitions_device")
    else:
        monkeypatch.delattr(reader_module.TpuShuffleReader, "read_device")
    traffic = driver.Traffic(cell=None, args=None)  # nothing of either is touched before the refusal
    with pytest.raises(SystemExit):
        traffic.start(conf=None, parts={})
    assert traffic.manager is None and not hasattr(traffic, "records") and not hasattr(traffic, "outputs")
    traffic.close()


JOB_BYTES = 1000


def a_report(**doctored):
    """The ``devproduce:`` line of a sound run of three jobs on the chip."""
    store = {"executor": 0, "staged_blocks": 30, "staged_bytes": 3 * JOB_BYTES,
             "device_staged_blocks": 30, "device_staged_bytes": 3 * JOB_BYTES, "scatter_dispatches": 6,
             "device_stage_ns": 5, "copy_ns": 0, "rollovers": 0, "spilled_bytes": 0,
             "released_device_bytes": 9000}
    report = {"scatter": ["dma"], "gather": ["dma"], "expected": "dma", "stores": [store],
              "producer_bytes": 1 << 30, "bytes_in_use_after_job": [(1 << 30) + (2 << 20)] * 4,
              "host_rounds": []}
    store.update({k: v for k, v in doctored.items() if k in store})
    report.update({k: v for k, v in doctored.items() if k not in store})
    return report


def test_a_sound_report():
    assert driver.unsound(a_report(), 3, JOB_BYTES) == []


@pytest.mark.parametrize("doctored, why", [
    ({"staged_bytes": 3 * JOB_BYTES + 10}, "device_staged_bytes"),    # a host write: commit counted more
    ({"device_staged_bytes": 2 * JOB_BYTES}, "device_staged_bytes"),  # a job's bytes not staged on the device
    ({"copy_ns": 7}, "copy_ns"),                                      # a host copy was timed
    ({"bytes_in_use_after_job": [(1 << 30) + (2 << 20), (1 << 30) + (4 << 30)]}, "bytes_in_use"),  # staging kept
    ({"scatter": ["xla"]}, "scatter lowering"),                       # a foreign lowering
    ({"scatter": []}, "scatter lowering"),                            # no scatter ran at all
    ({"gather": ["dma", "xla"]}, "gather lowering"),
    ({"host_rounds": [4]}, "host staging"),
], ids=["host-write", "bytes-missing", "host-copy", "staging-kept", "foreign-scatter", "no-scatter",
        "foreign-gather", "host-round"])
def test_each_condition_trips_on_a_doctored_report(doctored, why):
    found = driver.unsound(a_report(**doctored), 3, JOB_BYTES)
    assert len(found) == 1 and why in found[0]


def test_another_number_of_jobs_is_unsound():
    assert driver.unsound(a_report(), 4, JOB_BYTES)


def test_the_entry_writes_through_get_writer_only():
    """The cell's write is ``manager.get_writer(...).write_partitions_device``
    and its read ``get_reader(...).read_device()``: the driver names no store
    method on the job's clock."""
    import inspect

    source = inspect.getsource(driver.Entry.write_map)
    assert "get_writer(" in source and ".write_partitions_device(" in source and "commit_all_partitions()" in source
    assert "store" not in source and "map_writer" not in source


# -- what BENCHMARK.json declares ------------------------------------------------


#: the per-layer metrics of the device write and of the device read (PR 33)
DEVICE_WRITE = {"device_stage_s_per_job", "scatter_roofline"}
DEVICE_READ = {"device_read_task_p50_us", "device_read_locate_p50_us", "gather_roofline"}


def test_the_cell_adds_no_per_layer_entry_and_reports_the_unrestricted_ones():
    """The configuration and the cell are declared and name each other.  The
    cell reports every metric kept to no cell, as the devfetch cell does, and
    the metrics that list it: those of the device write and of the device
    read (PR 33; withheld until then), never the seal's put, which a
    device-staged shuffle does not fire.  By name, not by place: a later PR
    appends configurations, cells and metrics, some of which may list this cell."""
    bench = load_benchmark()
    assert "groupbytest-25k-devmap" in {c["name"] for c in bench["configs"]}
    assert {w["name"]: w["config"] for w in bench["workloads"]}[CELL] == "groupbytest-25k-devmap"
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed >= DEVICE_WRITE | DEVICE_READ and "seal_put_s_per_job" not in listed
    unrestricted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    mine = {m["name"] for m in load_cell(CELL).per_layer}
    assert mine == unrestricted | listed
    theirs = {m["name"] for m in load_cell("gbt25k-devfetch-1chip").per_layer}
    assert unrestricted <= theirs and DEVICE_READ <= theirs and not DEVICE_WRITE & theirs


def test_the_configuration_keeps_the_hbm_shapes_and_differs_in_the_producer():
    with open(os.path.join(ROOT, "benchmark/configs/groupbytest-25k-hbm.json")) as f:
        hbm = json.load(f)
    mine = load_cell(CELL).config
    for key in ("mappers", "pairs_per_mapper", "value_bytes", "reducers", "keys", "block_layout",
                "partitioner", "kept", "reduced"):
        assert mine[key] == hbm[key], key
    assert mine["conf"] == {**hbm["conf"], "device_staging": True}
    assert mine["rehearse"]["conf"] == {**hbm["rehearse"]["conf"], "device_staging": True}
    assert mine["reference"] == "groupby-devmap" and mine["source"] != hbm["source"]


# -- the cell, end to end in its CPU form --------------------------------------


def test_the_rehearsal_prints_the_devproduce_line_and_the_write_metrics(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "compile_cache"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "0.5", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    report = json.loads(next(line for line in lines if line.startswith("devproduce: ")).split(": ", 1)[1])
    window = json.loads(next(line for line in lines if line.startswith("window: ")).split(": ", 1)[1])
    assert report["unsound"] == [] and report["scatter"] == report["gather"] == ["xla"]
    (store,) = report["stores"]
    jobs, mappers = window["jobs"], load_cell(CELL, rehearse=True).config["mappers"]
    assert store["device_staged_bytes"] == store["staged_bytes"] == jobs * window["job_bytes"]
    assert store["scatter_dispatches"] == jobs * mappers and store["copy_ns"] == 0
    assert store["device_staged_blocks"] == jobs * window["job_blocks"] and store["spilled_bytes"] == 0
    assert len(report["bytes_in_use_after_job"]) == jobs + 1  # the warm-up job's too
    assert window["compiles_in_window"]["compiles"] == 0 and set(window["rounds_per_job"]) == {1}
    # a single round born on the device: no rollover, no spill, nothing put on the chip at seal
    assert last["metrics"]["write_s_per_job"]["value"] > 0
    assert last["metrics"]["staging_rounds_per_job"]["value"] == 1
    assert last["metrics"]["write_rollover_s_per_job"]["value"] == 0 == last["metrics"]["write_spill_s_per_job"]["value"]
    setup = json.loads(next(line for line in lines if line.startswith("setup: ")).split(": ", 1)[1])
    assert "device_output" in setup

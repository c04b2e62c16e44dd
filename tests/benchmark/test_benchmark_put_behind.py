"""The reader of the span PR 51 opened where a single staging round goes to
the device behind its writers — ``early_put_pieces_per_job``, a count of
``store.piece_put`` by name inside each job's ``job.write`` — on a run made
up by hand and on the program's own events; its declaration, found by name
with its two cells."""

import os

import jax
import numpy as np
import pytest

import sparkucx_tpu.store.hbm_store as hbm_store
from benchmark.cells import ROOT, load_benchmark, load_cell, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run
from benchmark.spans import SpanLog, program_spans
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.utils.trace import TRACER

MS = 1_000_000
NAME = "early_put_pieces_per_job"
CELLS = ["gbt25k-devfetch-1chip", "ts10gb-sortedjobs-1chip"]


def a_run(spans, own=None, jobs=3):
    """``jobs`` jobs a second apart, each a 400 ms ``job.write`` from its start."""
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    if own is None:
        own = []
        for j in range(jobs):
            own += [("job.write", j * 1000 * MS, j * 1000 * MS + 400 * MS),
                    ("job.exchange", j * 1000 * MS + 400 * MS, j * 1000 * MS + 450 * MS)]
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job] * jobs,
               spans=own, rounds=[1] * jobs, stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(spans))


def pieces(count, start):
    """``count`` piece puts of 1.5 ms, one every 7 ms from ``start``."""
    return [("store.piece_put", start + i * 7 * MS, start + i * 7 * MS + 1500_000) for i in range(count)]


def test_pieces_are_counted_by_name_where_they_begin_in_each_job_write():
    read = reader("layer_metrics", NAME)
    seals = [("store.seal_put", j * 1000 * MS + 400 * MS, j * 1000 * MS + 402 * MS) for j in range(3)]
    spans = seals + pieces(46, 5 * MS) + pieces(45, 1005 * MS) + pieces(46, 2005 * MS)
    spans += pieces(3, 420 * MS)  # inside a job.exchange: nobody's
    spans += [("write.task", 5 * MS, 20 * MS)]  # the task spans are another name
    assert read(a_run(spans)) == 46  # the median of 46, 45 and 46
    assert read(a_run(seals + pieces(46, 5 * MS))) == 0  # a job in three put pieces: the median job put none


def test_a_seal_that_put_the_whole_round_reads_zero_and_no_seal_reads_nothing():
    read = reader("layer_metrics", NAME)
    assert read(a_run([])) is None  # an untraced run
    # a multi-round or device-staged job: rounds and tasks, no single round sealed onto a device
    other = [("exchange.assemble", 401 * MS, 402 * MS), ("store.rollover", 6 * MS, 7 * MS),
             ("write.task", 5 * MS, 20 * MS), ("store.device_stage", 6 * MS, 7 * MS)]
    assert read(a_run(other)) is None  # left out of the line
    # the parent, and a staging round of one piece: the seal put all of it
    sealed_whole = other + [("store.seal_put", 400 * MS, 430 * MS)]
    assert read(a_run(sealed_whole)) == 0
    assert read(a_run(sealed_whole + pieces(4, 5 * MS), own=[])) is None  # no job to count them in


def test_the_reader_takes_what_the_store_records(monkeypatch):
    """From the program's own events: a store that puts behind its writer,
    through ``program_spans``, reads the store's ``early_put_pieces``."""
    monkeypatch.setattr(hbm_store, "SEAL_PUT_PIECE_BYTES", 1 << 13)
    store = hbm_store.HbmBlockStore(
        TpuShuffleConf(block_alignment=128, staging_capacity_per_executor=1 << 17), device=jax.devices()[0])
    before = TRACER.recording
    TRACER.recording = True
    TRACER.clear()
    log = SpanLog()
    try:
        rng = np.random.default_rng(51)
        store.create_shuffle(99, 1, 1)  # the job before: its buffer is the next jobs' (held pages)
        store.map_writer(99, 0).write_partition(0, b"the job before")
        store.remove_shuffle(99)
        counted = []
        for sid in range(3):
            store.create_shuffle(sid, 1, 32)
            with log.span("job.write"):
                writer = store.map_writer(sid, 0)
                for r in range(32):
                    writer.write_partition(r, rng.integers(0, 256, size=2000 + 300 * sid, dtype=np.uint8).tobytes())
                writer.commit()
            counted.append(store.write_stats()["early_put_pieces"] - sum(counted))
            with log.span("job.exchange"):
                store.seal(sid)
            store.remove_shuffle(sid)
        # ``exchange.assemble``: recorded once a round by every traced program (``inner_spans.MARKER``)
        run = a_run([("exchange.assemble", 0, 1)] + program_spans(TRACER.events), own=log.spans)
    finally:
        TRACER.recording = before
        TRACER.clear()
        store.close()
    assert min(counted) > 0 and len(set(counted)) == 3
    assert reader("layer_metrics", NAME)(run) == sorted(counted)[1]
    assert reader("layer_metrics", "seal_put_s_per_job")(run) > 0  # the seal's span keeps its place


def test_it_is_declared_by_name_in_its_two_cells_and_asked_in_no_other():
    bench = load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        "unit": "pieces", "better": "higher", "source": "program_span", "layer": "seal and plan",
        "moves": "shuffle_throughput"}
    # a layer the benchmark already names; a later PR may append a cell to the list
    assert entry["layer"] == next(m for m in bench["per_layer"] if m["name"] == "seal_put_s_per_job")["layer"]
    assert set(CELLS) <= set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"))
    for cell in (w["name"] for w in bench["workloads"]):
        assert (NAME in {m["name"] for m in load_cell(cell).per_layer}) == (cell in entry["workloads"])

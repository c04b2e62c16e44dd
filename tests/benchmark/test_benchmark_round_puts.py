"""The reader of the span PR 57 opened where a completed round of a
multi-round job goes to its store's device before the seal —
``early_round_puts_per_job``, a count of ``store.round_put`` by name inside
each job's ``job.write`` — on a run made up by hand and on the program's own
events; its declaration, found by name with its cells."""

import os

import jax
import numpy as np

import sparkucx_tpu.store.hbm_store as hbm_store
from benchmark.cells import ROOT, load_benchmark, load_cell, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run
from benchmark.spans import SpanLog, program_spans
from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.utils.trace import TRACER

MS = 1_000_000
NAME = "early_round_puts_per_job"
CELLS = ["gbt25k-jobs-1chip", "gbt25k-jobs-4chip", "gbt25k-zipf-4chip", "ts10gb-batchjobs-1chip",
         "gbt25k-execloss-4chip", "gbt25k-readloss-4chip", "gbt25k-daemon-1chip", "gbt25k-daemon-4tasks-1chip"]
#: recorded once a round by every traced program (``inner_spans.MARKER``)
TRACED = [("exchange.assemble", 401 * MS, 402 * MS)]


def a_run(spans, own=None, jobs=3):
    """``jobs`` jobs a second apart, each a 400 ms ``job.write`` from its start."""
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    if own is None:
        own = []
        for j in range(jobs):
            own += [("job.write", j * 1000 * MS, j * 1000 * MS + 400 * MS),
                    ("job.exchange", j * 1000 * MS + 400 * MS, j * 1000 * MS + 450 * MS)]
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job] * jobs,
               spans=own, rounds=[25] * jobs, stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(spans))


def puts(count, start):
    """``count`` round puts of 1.8 ms, one every 7 ms from ``start``."""
    return [("store.round_put", start + i * 7 * MS, start + i * 7 * MS + 1800_000) for i in range(count)]


def test_rounds_are_counted_by_name_where_they_begin_in_each_job_write():
    read = reader("layer_metrics", NAME)
    spans = TRACED + puts(24, 5 * MS) + puts(23, 1005 * MS) + puts(24, 2005 * MS)
    spans += puts(3, 420 * MS)  # inside a job.exchange: nobody's
    spans += [("store.piece_put", 6 * MS, 7 * MS), ("store.rollover", 6 * MS, 7 * MS)]  # other names
    assert read(a_run(spans)) == 24  # the median of 24, 23 and 24
    assert read(a_run(TRACED + puts(24, 5 * MS))) == 0  # a job in three put rounds: the median job put none


def test_a_traced_run_that_put_no_round_early_reads_zero_and_an_untraced_one_nothing():
    read = reader("layer_metrics", NAME)
    assert read(a_run([])) is None  # an untraced run: left out of the line
    assert read(a_run(puts(24, 5 * MS))) is None  # spans of a program that records no submit lane
    # the parent, a store's first job, a single-round job: the exchange put every round
    assert read(a_run(TRACED + [("store.rollover", 6 * MS, 7 * MS), ("write.task", 5 * MS, 20 * MS)])) == 0
    assert read(a_run(TRACED + puts(4, 5 * MS), own=[])) == 0  # no job to count them in


def test_the_reader_takes_what_the_store_records():
    """From the program's own events: a store whose free list holds its round
    buffers, through ``program_spans``, reads the store's ``early_round_puts``."""
    store = hbm_store.HbmBlockStore(
        TpuShuffleConf(block_alignment=128, staging_capacity_per_executor=1 << 13), device=jax.devices()[0])
    before = TRACER.recording
    TRACER.recording = True
    TRACER.clear()
    log = SpanLog()
    try:
        rng = np.random.default_rng(57)
        counted = []
        for sid in range(4):  # the first writes into fresh pages and puts nothing early
            store.create_shuffle(sid, 1, 32)
            with log.span("job.write"):
                writer = store.map_writer(sid, 0)
                for r in range(32):
                    writer.write_partition(r, rng.integers(0, 256, size=1500 + 400 * sid, dtype=np.uint8).tobytes())
                writer.commit()
            counted.append(store.write_stats()["early_round_puts"] - sum(counted))
            with log.span("job.exchange"):
                store.seal(sid)
            store.remove_shuffle(sid)
        run = a_run(TRACED + program_spans(TRACER.events), own=log.spans)
    finally:
        TRACER.recording = before
        TRACER.clear()
        store.close()
    assert counted[0] == 0 and min(counted[1:]) > 2 and len(set(counted)) == 4
    assert reader("layer_metrics", NAME)(run) == (sorted(counted)[1] + sorted(counted)[2]) / 2
    assert reader("layer_metrics", "staging_rounds_per_job")(run) == 25  # the harness's own count keeps its place


def test_it_is_declared_by_name_in_its_cells_and_asked_in_no_other():
    bench = load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        "unit": "rounds", "better": "higher", "source": "program_span", "layer": "seal and plan",
        "moves": "shuffle_throughput"}
    assert entry["layer"] == next(m for m in bench["per_layer"] if m["name"] == "early_put_pieces_per_job")["layer"]
    assert set(CELLS) == set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"))
    for cell in (w["name"] for w in bench["workloads"]):
        assert (NAME in {m["name"] for m in load_cell(cell).per_layer}) == (cell in entry["workloads"])

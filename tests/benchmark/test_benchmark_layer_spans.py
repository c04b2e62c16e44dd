"""The readers of the program's inner spans (``benchmark/inner_spans.py`` and
the five metrics on it; ``benchmark/device_path.py`` and the six metrics of
the device write, the seal's put and the device read on it), on a run made up
by hand: two jobs, one of them without a rollover."""

import pytest

from benchmark.cells import load_benchmark, load_cell, reader
from benchmark.device_trace import Reduction
from benchmark.inner_spans import recorded, seconds_inside_per_job
from benchmark.jobs import JobResult
from benchmark.measured import Run

S = 1_000_000_000
MS = 1_000_000
US = 1_000

#: job 0 writes over [0, 4 s) and rolls over twice (0.5 s and 0.3 s, of which
#: the disk tier 0.4 s and 0.2 s); job 1 writes over [10 s, 12 s) in one round
JOB_SPANS = [("job.write", 0, 4 * S), ("job.exchange", 4 * S, 6 * S), ("job.read", 6 * S, 7 * S),
             ("job.write", 10 * S, 12 * S), ("job.exchange", 12 * S, 13 * S), ("job.read", 13 * S, 14 * S)]
PROGRAM_SPANS = [
    ("store.rollover", 1 * S, 1 * S + 500 * MS), ("store.spill", 1 * S, 1 * S + 400 * MS),
    ("store.rollover", 3 * S, 3 * S + 300 * MS), ("store.spill", 3 * S, 3 * S + 200 * MS),
    # three rounds in job 0, one in job 1
    ("exchange.pipeline.submit", 4 * S, 4 * S + 130 * MS),
    ("exchange.assemble", 4 * S, 4 * S + 60 * MS), ("exchange.h2d", 4 * S + 60 * MS, 4 * S + 100 * MS),
    ("exchange.collective", 4 * S + 100 * MS, 4 * S + 101 * MS),
    ("exchange.assemble", 5 * S, 5 * S + 80 * MS), ("exchange.h2d", 5 * S + 80 * MS, 5 * S + 110 * MS),
    ("exchange.assemble", 5 * S + 500 * MS, 5 * S + 570 * MS),
    ("exchange.h2d", 5 * S + 570 * MS, 5 * S + 620 * MS),
    ("exchange.assemble", 12 * S, 12 * S + 10 * MS), ("exchange.h2d", 12 * S + 10 * MS, 12 * S + 12 * MS),
    ("daemon.write_partition", 100 * MS, 100 * MS + 90 * US),
    ("daemon.write_partition", 200 * MS, 200 * MS + 110 * US),
    ("daemon.write_partition", 10 * S + MS, 10 * S + MS + 100 * US),
    ("daemon.commit_map", 3 * S + 900 * MS, 3 * S + 901 * MS),
]


def a_run(program_spans=PROGRAM_SPANS, spans=JOB_SPANS, **fields):
    jobs = [JobResult(seconds=7.0, tasks=4, failed=0, faults=0, read_task_s=[0.001]),
            JobResult(seconds=4.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])]
    fields = {"chips": 1, "jobs": jobs, "stats_before": {}, "stats_after": {}, **fields}
    return Run(device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, spans=list(spans),
               rounds=[3, 1], fetch_faults=0, program_spans=list(program_spans), **fields)


@pytest.mark.parametrize("name, want", [
    ("write_rollover_s_per_job", (0.8 + 0.0) / 2),  # 0.5 + 0.3 s in job 0, none in job 1
    ("write_spill_s_per_job", (0.6 + 0.0) / 2),
    ("submit_assemble_ms_per_round", 65.0),  # median of 60, 80, 70, 10 ms
    ("submit_h2d_ms_per_round", 35.0),  # median of 40, 30, 50, 2 ms
    ("daemon_serve_p50_us", 100.0),  # median of 90, 110, 100 us
])
def test_readers_on_two_jobs(name, want):
    assert reader("layer_metrics", name)(a_run()) == pytest.approx(want)


NEW = ["write_rollover_s_per_job", "write_spill_s_per_job", "submit_assemble_ms_per_round",
       "submit_h2d_ms_per_round", "daemon_serve_p50_us"]


@pytest.mark.parametrize("name", NEW)
def test_an_untraced_run_has_nothing_to_read(name):
    assert reader("layer_metrics", name)(a_run(program_spans=[])) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_has_nothing_to_read(name):
    """The parent commit of the PR that added them: traced, its ring holds
    the older spans only.  The metric is left out, not reported as zero."""
    older = [s for s in PROGRAM_SPANS if s[0] in ("exchange.pipeline.submit", "exchange.collective")]
    run = a_run(program_spans=older)
    assert not recorded(run) and reader("layer_metrics", name)(run) is None


@pytest.mark.parametrize("name", ["write_rollover_s_per_job", "write_spill_s_per_job", "daemon_serve_p50_us"])
def test_a_window_without_such_a_span_reads_zero(name):
    """One-round jobs roll nothing over; the manager entry serves no frame."""
    one_round = [s for s in PROGRAM_SPANS if s[0].startswith("exchange.")]
    assert reader("layer_metrics", name)(a_run(program_spans=one_round)) == 0.0


def test_a_single_job_with_rollovers_is_its_own_median():
    run = a_run(spans=JOB_SPANS[:3])
    assert reader("layer_metrics", "write_rollover_s_per_job")(run) == pytest.approx(0.8)
    assert reader("layer_metrics", "write_spill_s_per_job")(run) == pytest.approx(0.6)


def test_only_what_falls_inside_the_outer_span_counts():
    # a demotion by the eviction manager during the read is no part of the write
    spans = PROGRAM_SPANS + [("store.spill", 6 * S, 6 * S + 900 * MS)]
    assert seconds_inside_per_job(a_run(program_spans=spans), "store.spill") == pytest.approx(0.3)
    # a span that straddles the end of the write is clipped to it
    spans = PROGRAM_SPANS + [("store.rollover", 11 * S + 900 * MS, 12 * S + 400 * MS)]
    assert seconds_inside_per_job(a_run(program_spans=spans), "store.rollover") == pytest.approx((0.8 + 0.1) / 2)
    assert seconds_inside_per_job(a_run(program_spans=spans), "store.rollover", outer="job.read") == 0.0


#: the per-layer metrics PR 23 declared, before PR 24's five
FIRST = ["write_s_per_job", "staging_rounds_per_job", "padding_share", "exchange_s_per_job",
         "pipeline_submit_p50_ms", "pipeline_drain_p50_ms", "exchange_roofline", "read_s_per_job",
         "read_task_p95_ms", "fetch_faults", "wire_write_frame_p50_us", "device_idle_share",
         "device_busy_ms_per_job"]


def test_the_new_metrics_are_declared_where_they_are_read():
    """By name and by order among themselves, never by their place in the
    list: a later PR appends metrics, and cells to a metric's ``workloads``."""
    bench = load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert [name for name in order if name in NEW] == NEW  # in this order among themselves
    assert max(order.index(name) for name in FIRST) < order.index(NEW[0])  # after PR 23's
    for name in NEW:
        assert declared[name]["source"] == "program_span" and declared[name]["moves"] == "shuffle_throughput"
    # the two metrics of a frame: the first daemon cell and only cells a daemon serves
    served = {w["name"] for w in bench["workloads"] if load_cell(w["name"]).traffic["driver"] == "daemon-jobs"}
    for name in ("daemon_serve_p50_us", "wire_write_frame_p50_us"):
        assert "gbt1k-daemon-1chip" in declared[name]["workloads"]
        assert set(declared[name]["workloads"]) <= served
    assert all("workloads" not in declared[name] for name in NEW[:4])
    layers = {declared[name]["layer"] for name in FIRST}
    assert {declared[name]["layer"] for name in NEW} <= layers  # layers the benchmark already names


# -- the device write, the seal's put, the device read (benchmark/device_path.py) --

#: job 0 stages two map tasks on the device (4 + 6 ms) and puts its round at
#: seal in 0.2 s; job 1 stages 2 + 4 ms and puts in 0.1 s; three device reads
DEVICE_SPANS = [
    ("store.device_stage", 1 * S, 1 * S + 4 * MS), ("store.device_stage", 2 * S, 2 * S + 6 * MS),
    ("store.device_stage", 10 * S + MS, 10 * S + 3 * MS), ("store.device_stage", 11 * S, 11 * S + 4 * MS),
    ("store.device_stage", 8 * S, 8 * S + 50 * MS),  # between the jobs: part of no job's write
    ("store.seal_put", 4 * S, 4 * S + 200 * MS), ("store.seal_put", 12 * S, 12 * S + 100 * MS),
    ("store.seal_put", 3 * S, 3 * S + 70 * MS),  # inside job 0's write, not its exchange
    ("read.device", 6 * S, 6 * S + 700 * US), ("read.device.locate", 6 * S, 6 * S + 200 * US),
    ("read.device", 6 * S + MS, 6 * S + MS + 900 * US), ("read.device.locate", 6 * S + MS, 6 * S + MS + 300 * US),
    ("read.device", 13 * S, 13 * S + 800 * US), ("read.device.locate", 13 * S, 13 * S + 250 * US),
]
#: the traced job's executables: two gather shapes, one scatter, and two of
#: other names (the exchange's copy runs the gather's kernel; the consumer's check)
MODULE_S = {"jit_block_gather(111)": 0.010, "jit_block_gather(222)": 0.002, "jit_block_scatter(5)": 0.008,
            "jit_local_fn(1)": 0.003, "jit_device_numbers(3)": 1.0, "jit_block_gather_plan(4)": 1.0}
#: 1,000,000 used rows a job x 512 B, read and written, over 819 GB/s = 1.2503 ms
LEAST = 1.024e9 / 819e9
SIX = ["device_read_task_p50_us", "device_read_locate_p50_us", "gather_roofline", "seal_put_s_per_job",
       "device_stage_s_per_job", "scatter_roofline"]


def a_device_run(program_spans=PROGRAM_SPANS + DEVICE_SPANS, module_s=MODULE_S, chips=1, **fields):
    reduction = None if module_s is None else Reduction(
        window_s=2.0, busy_s=0.02, idle_share=0.99, device_ops=[], idle_gaps=[], module_s=module_s,
        devices=chips, planes=chips)
    return a_run(program_spans=program_spans, chips=chips, reduction=reduction, stats_before={"used_rows": 100},
                 stats_after={"used_rows": 100 + 2 * 1_000_000 * chips}, **fields)


@pytest.mark.parametrize("name, run, want", [
    ("device_read_task_p50_us", {}, 800.0),  # median of 700, 900, 800 us
    ("device_read_locate_p50_us", {}, 250.0),  # median of 200, 300, 250 us
    ("seal_put_s_per_job", {}, (0.2 + 0.1) / 2),  # what falls inside each job's exchange
    ("device_stage_s_per_job", {}, (0.010 + 0.006) / 2),  # what falls inside each job's write
    ("gather_roofline", {}, 100 * LEAST / 0.012),  # both gather shapes, no module of another name
    ("scatter_roofline", {}, 100 * LEAST / 0.008),
    # four chips: the rows are all chips', a module's seconds their mean; a block never leaves its chip
    ("gather_roofline", {"chips": 4}, 100 * LEAST / 0.012),
    ("scatter_roofline", {"chips": 4}, 100 * LEAST / 0.008),
    # a host-staged, host-read window (every older cell): the program's older spans only
    *[(name, {"program_spans": PROGRAM_SPANS, "module_s": {"jit_local_fn(1)": 0.003}}, None) for name in SIX],
    # no program span at all (an untraced run), no trace of the device
    *[(name, {"program_spans": [], "module_s": None}, None) for name in SIX],
    # the spans without a reduction: the host readers read, the rooflines have no device time
    ("gather_roofline", {"module_s": None}, None),
    ("scatter_roofline", {"module_s": None}, None),
    ("device_read_task_p50_us", {"module_s": None}, 800.0),
    ("seal_put_s_per_job", {"module_s": None}, 0.15),
    # a window without a job cannot say what a job staged
    ("gather_roofline", {"jobs": []}, None),
])
def test_the_readers_of_the_device_path(name, run, want):
    got = reader("layer_metrics", name)(a_device_run(**run))
    assert got is None if want is None else got == pytest.approx(want)

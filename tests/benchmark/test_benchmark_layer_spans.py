"""The readers of the program's inner spans (``benchmark/inner_spans.py`` and
the five metrics on it), on a run made up by hand: two jobs, one of them
without a rollover."""

import pytest

from benchmark.cells import load_benchmark, reader
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


def a_run(program_spans=PROGRAM_SPANS, spans=JOB_SPANS):
    jobs = [JobResult(seconds=7.0, tasks=4, failed=0, faults=0, read_task_s=[0.001]),
            JobResult(seconds=4.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])]
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=jobs, spans=list(spans),
               rounds=[3, 1], stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(program_spans))


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


def test_the_new_metrics_are_declared_where_they_are_read():
    bench = load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW  # appended, in this order
    for name in NEW:
        assert declared[name]["source"] == "program_span" and declared[name]["moves"] == "shuffle_throughput"
    assert declared["daemon_serve_p50_us"]["workloads"] == ["gbt1k-daemon-1chip"]
    assert all("workloads" not in declared[name] for name in NEW[:4])
    layers = {m["layer"] for m in bench["per_layer"][: -len(NEW)]}
    assert {declared[name]["layer"] for name in NEW} <= layers  # layers the benchmark already names

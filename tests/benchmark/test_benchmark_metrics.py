"""The arithmetic of the metric readers and of the peaks table, on a run made
up by hand."""

import pytest

from benchmark.cells import reader
from benchmark.device_trace import Reduction
from benchmark.jobs import JobResult
from benchmark.measured import Run, median, percentile
from benchmark.peaks import exchange_min_seconds, peaks_for


def a_run(**changes):
    jobs = [
        JobResult(seconds=2.0, tasks=12, failed=0, faults=0, read_task_s=[0.001 * i for i in range(1, 11)]),
        JobResult(seconds=3.0, tasks=12, failed=0, faults=1, read_task_s=[0.001 * i for i in range(11, 21)]),
    ]
    spans = [("job.write", 0, 1_000_000_000), ("job.write", 0, 3_000_000_000),
             ("job.exchange", 0, 500_000_000), ("job.read", 0, 250_000_000)]
    fields = dict(
        chips=1, device_kind="TPU v5 lite", setup_s=12.5, job_bytes=100_000_000, jobs=jobs, spans=spans,
        rounds=[3, 5], stats_before={"used_rows": 100, "padded_rows": 100, "submit_p50_ns": 1, "drain_p50_ns": 1},
        stats_after={"used_rows": 2_000_100, "padded_rows": 500_100, "submit_p50_ns": 2_000_000,
                     "drain_p50_ns": 500_000},
        fetch_faults=1, frame_ns=[100_000, 300_000, 200_000],
    )
    fields.update(changes)
    return Run(**fields)


def a_reduction(module_s, devices=1):
    return Reduction(window_s=2.0, busy_s=0.004, idle_share=0.998, device_ops=[], idle_gaps=[],
                     module_s=module_s, devices=devices, planes=devices)


def test_median_and_percentile():
    assert median([3, 1, 2]) == 2 and median([4, 1, 3, 2]) == 2.5 and median([]) is None
    assert percentile(range(1, 101), 0.95) == 95
    assert percentile(range(1, 21), 0.95) == 19
    assert percentile([7], 0.95) == 7 and percentile([], 0.95) is None


@pytest.mark.parametrize("kind, name, want", [
    ("end_to_end", "shuffle_throughput", 2 * 100.0 / 5.0),  # MB of both jobs over the sum of their seconds
    ("end_to_end", "setup_s", 12.5),
    ("layer_metrics", "write_s_per_job", 2.0),
    ("layer_metrics", "exchange_s_per_job", 0.5),
    ("layer_metrics", "read_s_per_job", 0.25),
    ("layer_metrics", "read_task_p95_ms", 19.0),  # nearest rank of 20 samples, ms
    ("layer_metrics", "staging_rounds_per_job", 4.0),
    ("layer_metrics", "padding_share", 20.0),
    ("layer_metrics", "pipeline_submit_p50_ms", 2.0),
    ("layer_metrics", "pipeline_drain_p50_ms", 0.5),
    ("layer_metrics", "fetch_faults", 1),
    ("layer_metrics", "wire_write_frame_p50_us", 200.0),
])
def test_host_readers(kind, name, want):
    assert reader(kind, name)(a_run()) == pytest.approx(want)


def test_readers_that_find_nothing_return_nothing():
    run = a_run(frame_ns=[], stats_after={"used_rows": 100, "padded_rows": 100,
                                          "submit_p50_ns": None, "drain_p50_ns": None})
    for name in ("wire_write_frame_p50_us", "padding_share", "pipeline_submit_p50_ms",
                 "pipeline_drain_p50_ms", "device_idle_share", "device_busy_ms_per_job", "exchange_roofline"):
        assert reader("layer_metrics", name)(run) is None, name
    # a trace that saw no device is no device number either
    blind = Reduction(2.0, 0.0, 1.0, [], [], {}, devices=1, planes=0)
    assert reader("layer_metrics", "device_idle_share")(a_run(reduction=blind)) is None


def test_device_readers():
    run = a_run(reduction=a_reduction({"jit_local_fn(123)": 0.004, "jit_other(9)": 1.0}))
    assert reader("layer_metrics", "device_idle_share")(run) == pytest.approx(99.8)
    assert reader("layer_metrics", "device_busy_ms_per_job")(run) == pytest.approx(4.0)
    # 1,000,000 used rows a job x 512 B, read and written, over 819 GB/s = 1.2503 ms of 4 ms
    assert reader("layer_metrics", "exchange_roofline")(run) == pytest.approx(100 * 1.024e9 / 819e9 / 0.004)


def test_exchange_roofline_on_four_chips_is_bound_by_the_interconnect():
    run = a_run(chips=4, reduction=a_reduction({"jit__exchange_shard_ragged(5)": 0.010}, devices=4))
    # a chip holds a quarter of the 512 MB and sends three quarters of that at 200 GB/s
    least = 512e6 / 4 * 3 / 4 / 200e9
    assert exchange_min_seconds("TPU v5 lite", 4, 1_000_000, 512) == pytest.approx(least)
    assert reader("layer_metrics", "exchange_roofline")(run) == pytest.approx(100 * least / 0.010)


def test_an_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in the peaks table"):
        peaks_for("TPU v9")

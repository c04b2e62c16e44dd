"""The reduction from a profiler trace and host spans to device numbers, on a
recorded trace small enough to reduce by hand (fixtures/make_tiny_xplane.py
draws it)."""

import os

import pytest

from benchmark.device_trace import complement, load_xplane, reduce_trace, short_op_name, union
from benchmark.spans import SpanLog, attribute, durations, innermost, program_spans

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "tiny.xplane.pb")
#: one job of gbt1k-jobs-1chip as the profiler recorded it on a TPU v5 lite
#: (kept from a chip run of PR 23): one staging round, so one exchange executable
RECORDED = os.path.join(os.path.dirname(FIXTURE), "gbt1k-job-v5e.xplane.pb")
#: host spans on the perf_counter clock = the trace's clock + 5,000,000 ns
SPANS = [
    ("job.write", 5_000_000, 5_030_000),
    ("job.exchange", 5_030_000, 5_060_000),
    ("exchange.pipeline.drain", 5_035_000, 5_042_000),
]


@pytest.fixture(scope="module")
def trace():
    return load_xplane(FIXTURE)


def test_trace_lands_on_the_host_clock(trace):
    assert trace.ops["/device:TPU:0"][0] == ("fusion.1", 5_010_000, 5_014_000)
    assert trace.ops["/device:TPU:1"] == [("fusion.1", 5_030_000, 5_050_000)]
    assert trace.modules["/device:TPU:0"][1] == ("jit_local_fn", 5_040_000, 5_045_000)
    assert trace.layout["/device:TPU:0"] == {"XLA Ops": 3, "XLA Modules": 2}


def test_busy_union_and_idle_share(trace):
    r = reduce_trace(trace, 5_005_000, 5_055_000, SPANS, devices=2)
    # chip 0: [10,20) u [40,45) = 15 us; chip 1: 20 us; mean 17.5 of 50 us
    assert r.window_s == pytest.approx(50e-6)
    assert r.busy_s == pytest.approx(17.5e-6)
    assert r.idle_share == pytest.approx(0.65)
    assert r.devices == 2


def test_top_operations(trace):
    r = reduce_trace(trace, 5_005_000, 5_055_000, SPANS, devices=2)
    # fusion.1: 4 + 5 + 20 us, copy.2: 7 us, each a mean over the two chips
    assert [name for name, _ in r.device_ops] == ["fusion.1", "copy.2"]
    assert dict(r.device_ops) == pytest.approx({"fusion.1": 14.5e-6, "copy.2": 3.5e-6})
    assert r.module_s == pytest.approx({"jit_local_fn": 7.5e-6})


def test_gap_attribution(trace):
    r = reduce_trace(trace, 5_005_000, 5_055_000, SPANS, devices=2)
    # chip 0 idle [5,10) [20,40) [45,55), chip 1 idle [5,30) [50,55):
    # write 5+10+25, exchange 5+10+5, drain (innermost in [35,42)) 5; halved
    assert dict(r.idle_gaps) == pytest.approx(
        {"job.write": 20e-6, "job.exchange": 10e-6, "exchange.pipeline.drain": 2.5e-6}
    )
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s)


def test_a_chip_without_a_plane_is_idle_throughout(trace):
    r = reduce_trace(trace, 5_005_000, 5_055_000, SPANS, devices=4)
    assert r.busy_s == pytest.approx(35e-6 / 4)
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s)


def test_clipping_to_the_traced_job(trace):
    r = reduce_trace(trace, 5_012_000, 5_018_000, SPANS, devices=2)
    assert r.busy_s == pytest.approx(3e-6)  # chip 0 busy throughout, chip 1 not at all
    assert dict(r.idle_gaps) == pytest.approx({"job.write": 3e-6})


def test_a_trace_recorded_on_the_chip():
    trace = load_xplane(RECORDED)
    assert list(trace.ops) == ["/device:TPU:0"]
    (name, t0, t1), = trace.ops["/device:TPU:0"]
    assert name == "%_unknown_.1 custom-call tpu_custom_call"  # the Pallas DMA copy of the local exchange
    assert t1 - t0 == 34_005
    (module, m0, m1), = trace.modules["/device:TPU:0"]
    assert module.startswith("jit_local_fn(") and m0 <= t0 and t1 <= m1
    # the job ran 0.36-0.43 s on the host's clock; the chip was busy 34 us of it
    r = reduce_trace(trace, t0 - 200_000_000, t1 + 200_000_000, [], devices=1)
    assert r.busy_s == pytest.approx(34.005e-6) and r.idle_share > 0.9999
    assert dict(r.idle_gaps) == pytest.approx({"(no span)": r.window_s - r.busy_s})


def test_op_names_are_shortened():
    long = ('%_unknown_.1 = s32[131072,128]{1,0:T(8,128)} custom-call(s32[1]{0:T(128)} %constant.1), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}}')
    assert short_op_name(long) == "%_unknown_.1 custom-call tpu_custom_call"
    assert short_op_name("%fusion.3 = (s32[4]{0}, u8[2]) fusion(s32[1] %p), kind=kLoop") == "%fusion.3 fusion"
    assert short_op_name("plain name") == "plain name"


def test_no_clock_sync_is_an_error(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(ValueError, match="clock_sync"):
        load_xplane(str(empty))


def test_union_and_complement():
    busy = union([(0, 10), (5, 20), (30, 40), (40, 45), (90, 200)], 2, 100)
    assert busy == [(2, 20), (30, 45), (90, 100)]
    assert complement(busy, 2, 100) == [(20, 30), (45, 90)]
    assert complement([], 0, 7) == [(0, 7)]


def test_innermost_is_the_span_opened_last():
    spans = [("a", 0, 100), ("b", 10, 30), ("c", 20, 25), ("d", 200, 300)]
    assert innermost(spans) == [
        ("a", 0, 10), ("b", 10, 20), ("c", 20, 25), ("b", 25, 30), ("a", 30, 100), ("d", 200, 300),
    ]
    got = attribute([(0, 50), (90, 250)], innermost(spans))
    assert got == pytest.approx({"a": 40e-9, "b": 15e-9, "c": 5e-9, "d": 50e-9, "(no span)": 100e-9})


def test_span_log_and_program_spans():
    log = SpanLog()
    with log.span("job.write"):
        pass
    assert len(durations(log.spans, "job.write")) == 1
    assert durations([("job.read", 1_000, 3_000)], "job.read") == [2e-6]
    events = [
        {"name": "exchange.seal", "ph": "X", "ts": 2.0, "dur": 1.5},
        {"name": "exchange.plan", "ph": "i", "ts": 3.0},
    ]
    assert program_spans(events) == [("exchange.seal", 2000, 3500)]

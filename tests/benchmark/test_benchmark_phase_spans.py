"""The readers of the spans PR 36 opened — a daemon ``write_partition`` and
``fetch_block`` frame by phase, the connection's wait for its client, a reduce
task's ``read.window`` and its three children — on a run made up by hand; the
thirteen declarations, found by name; and a CPU rehearsal of one daemon and one
manager cell whose traced line carries every one of its new metrics."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell, reader
from benchmark.jobs import JobResult
from benchmark.measured import Run
from benchmark.spans import program_spans

US = 1_000
MS = 1_000_000

#: metric -> the program's span it is the median of
SPAN_OF = {
    "daemon_write_meta_p50_us": "daemon.write_partition.meta",
    "daemon_write_admit_p50_us": "daemon.write_partition.admit",
    "daemon_write_body_p50_us": "daemon.write_partition.body",
    "daemon_write_record_p50_us": "daemon.write_partition.record",
    "daemon_write_ack_p50_us": "daemon.write_partition.ack",
    "daemon_write_client_turn_p50_us": "daemon.client_turn.write_partition",
    "daemon_fetch_locate_p50_us": "daemon.fetch_block.locate",
    "daemon_fetch_send_p50_us": "daemon.fetch_block.send",
    "daemon_fetch_client_turn_p50_us": "daemon.client_turn.fetch_block",
    "read_window_p50_us": "read.window",
    "read_window_fetch_p50_us": "read.window.fetch",
    "read_window_decode_p50_us": "read.window.decode",
    "read_window_consumer_p50_us": "read.window.consumer",
}
NAMES = list(SPAN_OF)
DAEMON_CELLS = ["gbt1k-daemon-1chip", "gbt25k-daemon-1chip", "gbt25k-daemon-4tasks-1chip"]
MANAGER_CELLS = ["gbt25k-jobs-1chip", "gbt25k-jobs-4chip", "gbt1k-jobs-1chip"]


def cells_of(name):
    return MANAGER_CELLS if name.startswith("read_window") else DAEMON_CELLS


def spans_of(name, durations_us, start=5 * MS):
    """Spans of that name, one every millisecond, of those durations."""
    return [(name, start + i * MS, start + i * MS + int(d * US)) for i, d in enumerate(durations_us)]


def a_run(spans):
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job],
               spans=[("job.write", 0, 400 * MS), ("job.read", 500 * MS, 900 * MS)], rounds=[1],
               stats_before={}, stats_after={}, fetch_faults=0, program_spans=list(spans))


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_is_the_median_of_its_span_and_of_no_other(name):
    # every span of the thirteen in the window, each with its own median:
    # a reader that took a prefix of its name for the name would read another
    spans = []
    for i, (metric, span) in enumerate(SPAN_OF.items()):
        spans += spans_of(span, [10 + i, 30 + i, 20 + i, 1000])  # median (20 + i) + (30 + i) over 2
    got = reader("layer_metrics", name)(a_run(spans))
    assert got == pytest.approx(25 + NAMES.index(name))
    # one sample is its own median: a summed span is one event a sampled window
    assert reader("layer_metrics", name)(a_run(spans_of(SPAN_OF[name], [417.5]))) == pytest.approx(417.5)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_untraced_or_on_a_program_without_the_span(name):
    assert reader("layer_metrics", name)(a_run([])) is None  # an untraced run
    # the parent of PR 36, traced: its ring holds the frames and the windows whole
    older = spans_of("daemon.write_partition", [140, 150]) + spans_of("daemon.fetch_block", [900]) \
        + spans_of("read.window", [2500, 2600]) + spans_of("exchange.superstep", [11000])
    got = reader("layer_metrics", name)(a_run(older))
    if name == "read_window_p50_us":  # the one span of the thirteen the parent records
        assert got == pytest.approx(2550)
    else:
        assert got is None  # left out of the line, never a zero


def test_the_readers_take_what_the_tracers_bulk_path_wrote():
    """From the program's own events: a parent by ``span()``, its phases by
    ``record_spans``, through ``benchmark.spans.program_spans`` to a reader."""
    from sparkucx_tpu.utils.trace import Tracer

    t = Tracer(enabled=True)
    with t.span("daemon.write_partition") as ctx:
        pass
    cuts = [ctx.t0, ctx.t0 + 7 * US, ctx.t0 + 19 * US, ctx.t0 + 20 * US, ctx.t0 + 26 * US, ctx.t0 + 50 * US]
    phases = [SPAN_OF[n] for n in NAMES[:5]]
    t.record_spans(ctx, zip(phases, cuts, cuts[1:]))
    t.record_spans(None, [(SPAN_OF["daemon_write_client_turn_p50_us"], ctx.t0 - 270 * US, ctx.t0)])
    run = a_run(program_spans(t.events))
    got = [reader("layer_metrics", n)(run) for n in NAMES[:6]]
    assert got == pytest.approx([7, 12, 1, 6, 24, 270], abs=0.01)


def test_the_thirteen_are_declared_by_name_in_their_cells_and_no_other():
    bench = load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert [n for n in order if n in SPAN_OF] == NAMES  # in this order among themselves
    assert order.index("slot_idle_share") < order.index(NAMES[0])  # after PR 34's, wherever the list ends
    cells = {w["name"] for w in bench["workloads"]}
    for name in NAMES:
        entry = declared[name]
        assert {k: entry[k] for k in ("unit", "better", "source", "moves")} == {
            "unit": "us", "better": "lower", "source": "program_span", "moves": "shuffle_throughput"}
        assert entry["layer"] == ("reduce-side read" if name.startswith("read_window") else "entry points")
        # its own cells, all of them cells of the benchmark; a later PR may append more
        assert set(cells_of(name)) <= set(entry["workloads"]) <= cells
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    # layers the benchmark already names
    assert {declared[n]["layer"] for n in NAMES} <= {m["layer"] for m in bench["per_layer"] if m["name"] not in SPAN_OF}


@pytest.mark.parametrize("name", NAMES)
def test_a_cell_outside_its_workloads_is_never_asked(name):
    """``cells.load_cell`` hands a cell the metrics that list it: the frame
    metrics reach no manager cell, the window metrics no daemon or device
    cell, so their readers are not run there and the line has no such key."""
    bench = load_benchmark()
    listed = set(next(m for m in bench["per_layer"] if m["name"] == name)["workloads"])
    for cell in (w["name"] for w in bench["workloads"]):
        asked = name in {m["name"] for m in load_cell(cell).per_layer}
        assert asked == (cell in listed)
    for cell in DAEMON_CELLS + MANAGER_CELLS + ["gbt25k-devfetch-1chip", "gbt25k-devproducer-1chip"]:
        assert (cell in listed) == (cell in cells_of(name))


def rehearse(tmp_path, cell):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "compile_cache"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the test session's eight devices are not the cell's
    run_py = os.path.join(ROOT, load_benchmark()["command"][-1])
    out = subprocess.run(
        [sys.executable, run_py, "--workload", cell, "--seed", "2147483777", "--seconds", "0.5", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    trace = json.loads(next(line for line in lines if line.startswith("trace: ")).split(": ", 1)[1])
    return json.loads(lines[-1]), trace


# named for ``test_rehearsal``: the guard of test_benchmark_contract.py leaves
# out, by that name, the tests that run a job
@pytest.mark.parametrize("cell", ["gbt1k-daemon-1chip", "gbt1k-jobs-1chip"])
def test_rehearsal_carries_every_new_metric_of_the_cell(tmp_path, cell):
    last, trace = rehearse(tmp_path, cell)
    assert last["correct"] is True and last["failed"] == 0 and trace["program_spans_dropped"] == 0
    mine = [n for n in NAMES if cell in cells_of(n)]
    values = {n: last["metrics"][n]["value"] for n in mine}  # every one reports
    assert not [n for n in NAMES if n not in mine and n in last["metrics"]]  # and no other cell's
    assert all(last["metrics"][n]["unit"] == "us" and v >= 0 for n, v in values.items())
    rows = {name for name, _ in last["breakdown"]["idle_gaps"]}
    if cell == "gbt1k-daemon-1chip":
        # every phase of a frame took some time (that they partition their
        # frame is tests/test_layer_spans.py's, on the events themselves)
        assert all(values[n] > 0 for n in NAMES[:5]) and last["metrics"]["daemon_serve_p50_us"]["value"] > 0
        assert values["daemon_write_client_turn_p50_us"] > 0 and values["daemon_fetch_client_turn_p50_us"] > 0
    else:
        window = values["read_window_p50_us"]
        assert 0 < values["read_window_fetch_p50_us"] <= window
        assert values["read_window_decode_p50_us"] > 0 and values["read_window_consumer_p50_us"] > 0
        assert any(name.startswith("read.window.") for name in rows)  # the idle time names the children

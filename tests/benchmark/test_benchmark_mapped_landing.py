"""The reader of PR 60's span — the share of a window's ``fetch_block`` replies
whose body went into the landing its same-host client offered — on runs made
up by hand (0 without the child span, the ratio with it, ``None`` where the
program's spans were not recorded), on the program's own events, its
declaration, and a CPU rehearsal of a daemon cell whose traced line carries
it."""

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
NAME = "daemon_fetch_mapped_share"
SEND, MAPPED = "daemon.fetch_block.send", "daemon.fetch_block.send.mapped"
#: recorded once a round by every program that records any span (``inner_spans.MARKER``)
MARKER = ("exchange.assemble", 1 * MS, 2 * MS)
DAEMON_CELLS = ["gbt1k-daemon-1chip", "gbt25k-daemon-1chip", "gbt25k-daemon-4tasks-1chip"]


def a_run(spans):
    job = JobResult(seconds=1.0, tasks=4, failed=0, faults=0, read_task_s=[0.001])
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1000, jobs=[job],
               spans=[("job.write", 0, 400 * MS), ("job.read", 500 * MS, 900 * MS)], rounds=[1],
               stats_before={}, stats_after={}, fetch_faults=0, program_spans=list(spans))


def frames(mapped, socket):
    """``mapped + socket`` fetch frames a millisecond apart, the first
    ``mapped`` of them with the copy's span inside their ``send``."""
    out = [MARKER]
    for i in range(mapped + socket):
        t0 = (600 + i) * MS
        out += [("daemon.fetch_block", t0 - 200 * US, t0 + 900 * US), ("daemon.fetch_block.locate", t0 - 200 * US, t0),
                (SEND, t0, t0 + 900 * US)]
        if i < mapped:
            out.append((MAPPED, t0, t0 + 700 * US))
    return out


@pytest.mark.parametrize("mapped, socket, share", [(0, 7, 0.0), (7, 0, 100.0), (199, 1, 99.5), (1, 3, 25.0)])
def test_the_share_is_the_copies_over_the_sends(mapped, socket, share):
    assert reader("layer_metrics", NAME)(a_run(frames(mapped, socket))) == pytest.approx(share)


def test_the_parent_reads_zero_and_a_run_without_spans_nothing():
    read = reader("layer_metrics", NAME)
    # the parent of PR 60, traced: every reply over the socket, no child under ``send``
    assert read(a_run(frames(0, 200))) == 0.0
    assert read(a_run([])) is None  # an untraced run: the program's spans were not recorded
    assert read(a_run([(SEND, 5 * MS, 6 * MS), (MAPPED, 5 * MS, 6 * MS)])) is None  # no marker: not a recorded window
    assert read(a_run([MARKER, ("read.window", 5 * MS, 6 * MS)])) is None  # a manager cell's window: no fetch frame
    # a name that only begins like the span's is not it
    assert read(a_run(frames(1, 1) + [(MAPPED + ".x", 0, 1), (SEND + "er", 0, 1)])) == pytest.approx(50.0)


def test_the_reader_takes_what_the_daemons_own_marks_wrote():
    """From the program's events: a frame by ``span()``, its phases and the
    copy under ``send`` by one ``record_spans``, as ``_serve_traced`` lays
    them, through ``benchmark.spans.program_spans`` to the reader."""
    from sparkucx_tpu.utils.trace import Tracer

    t = Tracer(enabled=True)
    with t.span("exchange.assemble"):
        pass
    for mapped in (True, True, False):
        with t.span("daemon.fetch_block") as ctx:
            pass
        t_ack, t_end = ctx.t0 + 3 * US, ctx.t0 + 40 * US
        send = (SEND, t_ack, t_end)
        if mapped:
            send += (None, ((MAPPED, t_ack, t_ack + 30 * US),))
        t.record_spans(ctx, (("daemon.fetch_block.locate", ctx.t0, t_ack), send))
    assert reader("layer_metrics", NAME)(a_run(program_spans(t.events))) == pytest.approx(200 / 3)


def test_it_is_declared_in_the_three_daemon_cells_and_no_other():
    bench = load_benchmark()
    [entry] = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
                     "layer": "entry points", "moves": "shuffle_throughput", "workloads": DAEMON_CELLS}
    assert "entry points" in {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"))
    for cell in (w["name"] for w in bench["workloads"]):
        assert (NAME in {m["name"] for m in load_cell(cell).per_layer}) == (cell in DAEMON_CELLS)


# named for ``test_rehearsal``: the guard of test_benchmark_contract.py leaves
# out, by that name, the tests that run a job
def test_rehearsal_of_a_daemon_cell_reads_its_replies_mapped(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "compile_cache"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the test session's eight devices are not the cell's
    run_py = os.path.join(ROOT, load_benchmark()["command"][-1])
    out = subprocess.run(
        [sys.executable, run_py, "--workload", "gbt1k-daemon-1chip", "--seed", "2147483907", "--seconds", "0.5",
         "--trace", "1", "--rehearse"], capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    metric = last["metrics"][NAME]
    # the mechanism ran: the one client's first reply with a body sized the
    # landing in the warm-up job.  Not every reply: at the rehearsal's sizes a
    # reduce task's blocks run from nothing (no body: the reply it always was)
    # to five times the median, and a reply that outgrew the landing crosses
    # the socket (at the cell's own sizes, 78-121 KB a reply, one in a job does)
    assert metric["unit"] == "%" and 30.0 <= metric["value"] <= 100.0
    assert last["metrics"]["daemon_fetch_send_p50_us"]["value"] > 0

"""run.py end to end in its CPU form: every cell of BENCHMARK.json, untraced
and traced, each in a process of its own with its own compile-cache directory
(the four-chip cell on four virtual CPU devices), and the last line's exact
keys.  Counts and checks only: a number from these runs is never a rate."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell

BENCH = load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
RUN = os.path.join(ROOT, BENCH["command"][-1])


def run(tmp_path, *argv, rehearse=True):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "compile_cache"))
    env.pop("XLA_FLAGS", None)  # the test session's eight devices are not the cell's
    env["JAX_PLATFORMS"] = "cpu"  # the sandbox has no chip; never probe for one from a test
    cmd = [sys.executable, RUN, *argv] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)


def metrics_of(cell, kind):
    """The cell's metrics of that kind: those not kept to other cells and, per
    layer, only where the end-to-end metric they move is reported."""
    return {m["name"] for m in getattr(load_cell(cell), kind)}


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal(tmp_path, cell, trace):
    out = run(tmp_path, "--workload", cell, "--seed", "5", "--seconds", "0.5", "--trace", str(trace))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[0].split(": ", 1)[1])["platform"] == "cpu"
    last = json.loads(lines[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"} | ({"breakdown"} if trace else set())
    assert set(last) == want
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    device = {"platform", "kind", "count", "memory_peak_bytes"} | ({"busy_s", "window_s"} if trace else set())
    assert set(last["device"]) == device
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == CELLS[cell]["chips"]
    if trace:
        # the CPU backend has no device plane: the device readers find nothing
        # and are left out; every host reader reports
        on_device = {m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"}
        expected = metrics_of(cell, "per_layer") - on_device
        assert set(last["metrics"]) >= expected
        assert set(last["metrics"]) <= metrics_of(cell, "per_layer")
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(last["breakdown"]["idle_gaps"]) <= 10 and last["device"]["window_s"] > 0
    else:
        assert set(last["metrics"]) == metrics_of(cell, "end_to_end")
    for name, metric in last["metrics"].items():
        assert set(metric) == {"value", "unit"} and isinstance(metric["value"], float), name
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert all(metric["unit"] == units[name] for name, metric in last["metrics"].items())
    for label in ("setup", "window", "lowerings"):
        assert any(line.startswith(label + ": ") for line in lines), label


def test_without_a_chip_there_is_no_result(tmp_path):
    """No --rehearse, no TPU: non-zero exit and an empty stdout — no CPU
    fallback that could be read as a device number."""
    for cell in sorted(CELLS):
        out = run(tmp_path, "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0",
                  rehearse=False)
        assert out.returncode == 4 and out.stdout == "", (cell, out.stdout[-500:])
        assert "needs" in out.stderr


def test_an_unknown_cell_is_refused(tmp_path):
    out = run(tmp_path, "--workload", "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout == ""

"""The control of the daemon cells: the same job over the same socket with one
guarantee of the configuration broken — one acknowledged block a timed job is
never read back — has to come out as not ``correct``.

The lossy driver is a throw-away file beside a copy of the benchmark (data and
a driver added, nothing edited), so the run is ``run.py``'s own: its set-up,
its window, its comparison, its last line.  As a test it runs the CPU form; on
the chip the same file is a program that runs the control at the cell's own
size (``python3 tests/benchmark/test_benchmark_control.py --seed <n> --seconds
<s>``) and exits 0 only if the run came out as not correct."""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONTROL = "gbt25k-daemon-lossy-1chip"
#: the reduce task of every timed job that loses its last block
LOSSY_TASK = 3
LOSSY_DRIVER = '''"""A throw-away control: ``daemon-jobs`` whose client leaves one acknowledged
block of every timed job unread (the warm-up job, shuffle 0, is left whole, so
it is the window's comparison that has to notice)."""

import json
import os
import subprocess
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.cells import load_module

shipped = load_module("traffic", "daemon-jobs")


class Entry(shipped.Entry):
    def read(self, shuffle_id, reduce_id, mappers, consume):
        if shuffle_id > 0 and reduce_id == %d:
            mappers = mappers[:-1]
        return super().read(shuffle_id, reduce_id, mappers, consume)


class Traffic(shipped.Traffic):
    def __init__(self, cell, args):  # the shipped one, with this file as the client's program
        self.cell, self.daemon = cell, None
        spec = {"workload": cell.name, "rehearse": args.rehearse, "seed": args.seed,
                "seconds": args.seconds, "trace": bool(args.trace)}
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )


if __name__ == "__main__":
    shipped.Entry = Entry  # what the shipped client_main stands up
    sys.exit(shipped.client_main(json.loads(sys.argv[1])))
''' % LOSSY_TASK


def run_the_control(root, seed, seconds, rehearse, **env):
    """``run.py`` on the lossy cell in a copy of the benchmark under ``root``;
    returns the finished process."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, "daemon-lossy.json"), "w") as f:
        json.dump({"driver": "daemon-lossy"}, f)
    with open(os.path.join(traffic, "daemon-lossy.py"), "w") as f:
        f.write(LOSSY_DRIVER)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": CONTROL, "config": "groupbytest-25k", "traffic": "daemon-lossy",
                               "chips": 1, "why": "the control of gbt25k-daemon-1chip"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", CONTROL, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0"] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=900)


def verdict(out):
    """(the control came out as not correct for the reason planted, its last
    line, its ``window:`` line)."""
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    window = json.loads(next(line for line in lines if line.startswith("window: ")).split(": ", 1)[1])
    caught = (out.returncode == 0 and last["correct"] is False and window["warmup_failed_tasks"] == 0
              and last["failed"] == window["jobs"] >= 1)  # one reduce task of every timed job
    return caught, last, window


def test_a_lost_block_comes_out_as_not_correct(tmp_path):
    out = run_the_control(str(tmp_path), seed=2147483659, seconds=0.5, rehearse=True,
                          JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window = verdict(out)
    assert caught, (last, window["warmup_failed_tasks"], window["jobs"])
    assert f"reduce task {LOSSY_TASK} " not in out.stdout + out.stderr  # no task raised: the comparison found it


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.join(ROOT, ".scratch", "control")  # inside the checkout, listed in .gitignore
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    out = run_the_control(root, args.seed, args.seconds, args.rehearse)
    sys.stderr.write(out.stderr[-2000:])
    caught, last, window = verdict(out)
    print(json.dumps({"control_caught": caught, "jobs": window["jobs"],
                      "warmup_failed_tasks": window["warmup_failed_tasks"], "last": last}))
    sys.exit(0 if caught else 1)

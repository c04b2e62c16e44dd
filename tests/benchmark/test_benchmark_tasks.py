"""The cell whose tasks run side by side (driver ``daemon-tasks``): its three
readers on hand-made spans, and its two controls through ``run.py`` itself in
a copy of the benchmark with a throw-away driver beside it (data and a driver
added, nothing edited) —

* one acknowledged block of every timed job is never read back, over four
  slots: the run has to come out as not ``correct``;
* a slot process dies in the middle of the window: the run still ends in a
  result line, ``correct`` false, and leaves no process behind.

As tests they run the CPU form; on the chip this file is a program that runs
the first control at the cell's own size (``python3
tests/benchmark/test_benchmark_tasks.py --seed <n> --seconds <s>``) and exits 0
only if the run came out as not correct for the reason planted."""

import argparse
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from benchmark.cells import reader

CELL = "gbt25k-daemon-4tasks-1chip"
#: the reduce task of every timed job that loses its last block
LOSSY_TASK = 3
#: the slot that dies, and the timed job in whose reduce stage: on the first
#: reduce task it is handed there (which one depends on which slot frees first)
DOOMED_SLOT, DOOMED_JOB = 2, 2
THROWAWAY = '''"""A throw-away control: ``daemon-tasks`` with %(what)s."""

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.cells import load_module

shipped = load_module("traffic", "daemon-tasks")


class Traffic(shipped.Traffic):
    program = os.path.abspath(__file__)  # what the coordinator and the slots run
%(body)s

if __name__ == "__main__":
    spec = json.loads(sys.argv[1])
    plant(spec)
    sys.exit(shipped.child_main(spec))
'''
LOSSY = THROWAWAY % {
    "what": "slots that leave one acknowledged block of every timed job unread (the warm-up job, shuffle 0, "
            "is left whole, so it is the window's comparison that has to notice)",
    "body": '''

class Entry(shipped.Entry):
    def read(self, shuffle_id, reduce_id, mappers, consume):
        if shuffle_id > 0 and reduce_id == %d:
            mappers = mappers[:-1]
        return super().read(shuffle_id, reduce_id, mappers, consume)


def plant(spec):
    shipped.Entry = Entry  # what the shipped slot stands up
''' % LOSSY_TASK,
}
DOOMED = THROWAWAY % {
    "what": "a slot that dies in the middle of a reduce task of a timed job",
    "body": '''
run_task = shipped.run_task


def plant(spec):
    def doomed(entry, records, task):
        if (spec.get("slot"), task["shuffle_id"], task["op"]) == (%d, %d, "reduce"):
            os._exit(9)
        return run_task(entry, records, task)

    shipped.run_task = doomed
''' % (DOOMED_SLOT, DOOMED_JOB),
}


def run_a_control(root, name, driver, seed, seconds, rehearse, **env):
    """``run.py`` on the cell ``name``: the shipped cell's configuration under
    the throw-away ``driver``, in a copy of the benchmark under ``root``;
    returns the finished process."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(traffic, name + ".json"), "w") as f:
        json.dump({"driver": name}, f)
    with open(os.path.join(traffic, name + ".py"), "w") as f:
        f.write(driver)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shipped = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append({**shipped, "name": name, "traffic": name, "why": "a control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", name, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0"] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=900)


def lines_of(out):
    """(the last line, the ``window:`` line, the ``setup:`` line) of a run."""
    lines = out.stdout.strip().splitlines()

    def labelled(label):
        return json.loads(next(line for line in lines if line.startswith(label + ": ")).split(": ", 1)[1])

    return json.loads(lines[-1]), labelled("window"), labelled("setup")


def verdict(out):
    """(the lossy control came out as not correct for the reason planted, its
    last line, its ``window:`` line)."""
    last, window, _ = lines_of(out)
    caught = (out.returncode == 0 and last["correct"] is False and window["warmup_failed_tasks"] == 0
              and last["failed"] == window["jobs"] >= 1)  # one reduce task of every timed job
    return caught, last, window


def processes_of(root):
    """Command lines of the live processes that run a file under ``root``."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if str(root) in cmdline and int(pid) != os.getpid():
            found.append(cmdline[:200])
    return found


def test_a_lost_block_comes_out_as_not_correct_over_four_slots(tmp_path):
    out = run_a_control(str(tmp_path), "daemon-tasks-lossy", LOSSY, seed=2147483693, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    caught, last, window = verdict(out)
    assert caught, (last, window["warmup_failed_tasks"], window["jobs"])
    assert f"reduce task {LOSSY_TASK} " not in out.stdout + out.stderr  # no task raised: the comparison found it
    assert not processes_of(tmp_path)


def test_rehearsal_with_a_slot_killed_mid_window_still_ends_in_a_result_and_not_correct(tmp_path):
    out = run_a_control(str(tmp_path), "daemon-tasks-doomed", DOOMED, seed=7, seconds=30, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, window, setup = lines_of(out)
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is False and window["warmup_failed_tasks"] == 0 and setup["slots_lost"] == 1.0
    # the window ended with the job that lost its slot, thirty seconds early; the
    # task that died with it and the slot itself each count as a failed task
    assert window["jobs"] == DOOMED_JOB and last["failed"] == 2
    assert last["attempted"] == window["jobs"] * (5 + 200)  # the other three slots ran the rest of the job
    assert not processes_of(tmp_path)


def run_of(spans, program_spans):
    return SimpleNamespace(spans=spans, program_spans=program_spans)


def test_the_overlap_readers_on_hand_made_spans():
    write, fetch = reader("layer_metrics", "daemon_write_overlap"), reader("layer_metrics", "daemon_fetch_overlap")
    jobs = [("job.write", 0, 100), ("job.exchange", 100, 120), ("job.read", 120, 200),
            ("job.write", 1000, 1100), ("job.read", 1100, 1200)]
    # frames one after another fill the stage exactly: 1.0
    serial = [("daemon.write_partition", t, t + 10) for t in (*range(0, 100, 10), *range(1000, 1100, 10))]
    assert write(run_of(jobs, serial)) == 1.0
    # four connections never waiting: 4.0; a frame outside every stage counts for nothing
    four = serial * 4 + [("daemon.write_partition", 500, 600)]
    assert write(run_of(jobs, four)) == 4.0
    # a frame that straddles the stage's end counts with the part inside
    assert write(run_of(jobs[:1], [("daemon.write_partition", 50, 150)])) == 0.5
    assert fetch(run_of(jobs, [("daemon.fetch_block", 120, 160), ("daemon.fetch_block", 140, 200),
                               ("daemon.fetch_block", 1100, 1180)])) == 1.0
    # absent spans: nothing to read, on either side
    assert write(run_of(jobs, [])) is None and fetch(run_of(jobs, serial)) is None
    assert write(run_of([], serial)) is None
    assert write(run_of([("job.write", 5, 5)], serial)) is None


def test_the_slot_idle_share_on_hand_made_spans():
    read = reader("layer_metrics", "slot_idle_share")
    spans = [("job.slot", 0, 100)] * 2 + [("job.slot", 200, 300)] * 2 + [("job.write", 0, 50), ("job.read", 50, 100)]
    spans += [("task.map", 0, 50), ("task.map", 0, 40), ("task.reduce", 50, 100), ("task.reduce", 60, 70)]  # 150 of 200
    spans += [("task.map", 200, 300), ("task.reduce", 200, 300)]  # the second job's slots never idle
    assert read(run_of(spans, [])) == 100.0 * (0.25 + 0.0) / 2
    assert read(run_of(spans[:2] + spans[4:10], [])) == 25.0
    assert read(run_of([s for s in spans if s[0] != "job.slot"], [])) is None  # another driver's spans
    assert read(run_of(spans[:4], [])) is None


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.join(ROOT, ".scratch", "control-tasks")  # inside the checkout, listed in .gitignore
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    out = run_a_control(root, "daemon-tasks-lossy", LOSSY, args.seed, args.seconds, args.rehearse)
    sys.stderr.write(out.stderr[-2000:])
    caught, last, window = verdict(out)
    print(json.dumps({"control_caught": caught, "jobs": window["jobs"],
                      "warmup_failed_tasks": window["warmup_failed_tasks"], "last": last}))
    sys.exit(0 if caught else 1)

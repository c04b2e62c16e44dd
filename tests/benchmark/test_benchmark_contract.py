"""BENCHMARK.json against the driver's contract and against the files it names,
and that a new cell, configuration, traffic mix or per-layer metric needs only
new files and new entries."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.cells import ROOT, load_benchmark, load_cell, load_module, reader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(one_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(ROOT, path))
    program = [w for w in BENCH["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in BENCH["paths"]) for w in program)


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    names = [c["name"] for c in BENCH["configs"]]
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"]) and PATH.match(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        # the file says what was cut and why, and the entry lists the same keys
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["source"] == c["source"]
        for key in ("reference", "guarantees", "assumed", "conf", "rehearse"):
            assert key in config, key
        assert callable(load_module("references", config["reference"]).make_records)
        if config["reference"] == "groupby":
            for key in ("mappers", "pairs_per_mapper", "value_bytes", "reducers", "keys"):
                assert key in config, key
            # a cut never touches a shape of the source
            assert not set(c["reduced"]) & {"pairs_per_mapper", "value_bytes", "reducers", "keys"}


def test_workloads():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and one_line(w["why"])
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)


def test_metrics():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_with_its_files(name):
    cell = load_cell(name)
    driver = load_module("traffic", cell.traffic["driver"])
    # all that run.py asks of a driver
    assert all(callable(getattr(driver.Traffic, method)) for method in ("start", "run", "close"))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(reader("layer_metrics", m["name"]))
    for m in cell.end_to_end:
        assert callable(reader("end_to_end", m["name"]))
    tiny = load_cell(name, rehearse=True)
    changed = {key for key in cell.config if tiny.config[key] != cell.config[key]}
    assert changed <= set(cell.config["rehearse"]), "the CPU form changes only the sizes the file gives for it"


def test_every_moves_names_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert {m["moves"] for m in BENCH["per_layer"]} <= e2e


def test_files_under_paths_keep_to_the_character_set():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert PATH.match(os.path.relpath(os.path.join(folder, f), ROOT)), f


THROWAWAY_DRIVER = '''"""A throw-away driver: another kind of entry (every partition stream is
written in as many pieces as its JSON says) on the manager."""

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-jobs")


class Entry(shipped.Entry):
    def __init__(self, manager, pieces):
        super().__init__(manager)
        self.pieces = pieces

    def write_map(self, shuffle_id, map_id, parts):
        writer = self.manager.get_writer(shuffle_id, map_id)
        for reduce_id, payload in parts:
            step = -(-len(payload) // self.pieces)
            with writer.get_partition_writer(reduce_id).open_stream() as stream:
                for at in range(0, len(payload), step):
                    stream.write(payload[at : at + step])
        writer.commit_all_partitions()


class Traffic(shipped.Traffic):
    def run(self, control, parts):
        from benchmark.jobs import run_window

        parts["pieces"] = float(self.cell.traffic["pieces"])
        entry = Entry(self.manager, self.cell.traffic["pieces"])
        return run_window(entry, self.records, self.args.seconds, bool(self.args.trace), control)
'''


def a_copy_with_a_throwaway_cell(root, also_copy=()):
    """A copy of the benchmark under ``root`` with a throw-away configuration,
    traffic mix with a driver and an entry kind of its own, per-layer metric
    and cell added beside it, each **appended** to its list of a
    ``BENCHMARK.json`` there, as a later PR adds them.  Returns that
    ``BENCHMARK.json`` and the bytes of every copied file as they were."""
    for path in ("benchmark", *also_copy):
        shutil.copytree(os.path.join(ROOT, path), root / path, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "benchmark" / "configs" / "groupbytest-4k.json").write_text(json.dumps({
        "source": "a throw-away example", "reference": "groupby", "mappers": 3,
        "pairs_per_mapper": 30, "value_bytes": 4000, "reducers": 5, "keys": "uniform-int31",
        "conf": {"staging_capacity_per_executor": 1 << 20},  # conf overrides are data of the file
        "reduced": {}, "assumed": [], "guarantees": "as the others", "rehearse": {},
    }))
    (root / "benchmark" / "traffic" / "manager-pieces.json").write_text(json.dumps({
        "driver": "manager-pieces", "pieces": 3,
    }))
    (root / "benchmark" / "traffic" / "manager-pieces.py").write_text(THROWAWAY_DRIVER)
    (root / "benchmark" / "layer_metrics" / "jobs_in_window.py").write_text(
        '"""A throw-away reader."""\n\n\ndef read(run):\n    return len(run.jobs)\n'
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "groupbytest-4k", "source": "a throw-away example",
                             "file": "benchmark/configs/groupbytest-4k.json", "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gbt4k-pieces-1chip", "config": "groupbytest-4k",
                               "traffic": "manager-pieces", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "jobs_in_window", "unit": "jobs", "better": "higher",
                               "source": "program_counter", "layer": "entry points",
                               "moves": "shuffle_throughput", "workloads": ["gbt4k-pieces-1chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench, before


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A throw-away configuration, traffic mix with a driver and an entry kind
    of its own, per-layer metric and cell, added beside a copy of the benchmark
    without editing a file of it."""
    _, before = a_copy_with_a_throwaway_cell(tmp_path)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "gbt4k-pieces-1chip",
         "--seed", "3", "--seconds", "0.3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    jobs = last["metrics"]["jobs_in_window"]["value"]
    assert jobs >= 1 and last["attempted"] == jobs * (3 + 5)  # three map and five reduce tasks a job
    assert "wire_write_frame_p50_us" not in last["metrics"]  # another cell's metric
    setup = json.loads(next(line for line in lines if line.startswith("setup: ")).split(": ", 1)[1])
    assert setup["pieces"] == 3.0  # the new driver ran, with the parameter of its data file
    # 360 KB of records through 1 MiB of staging: the file's conf reached the program
    rounds = json.loads(next(line for line in lines if line.startswith("window: ")).split(": ", 1)[1])
    assert set(rounds["rounds_per_job"]) == {1}
    after = {p: p.read_bytes() for p in before}
    assert after == before


#: the tests of this directory that run a job (a process of ``run.py``): the
#: guard below leaves them out, they hold no declaration to a place.  A new
#: test that runs a job is named here, or it runs inside the guard as well.
RUN_A_JOB = ["test_rehearsal", "test_without_a_chip_there_is_no_result", "test_an_unknown_cell_is_refused",
             "test_a_new_cell_needs_only_new_files_and_entries",
             "test_the_rehearsal_prints_the_devproduce_line_and_the_write_metrics",
             "test_a_lost_block_comes_out_as_not_correct"]
GUARD = "test_appended_entries_fail_no_test_that_reads_the_declarations"
#: what the guard is there to catch, planted beside the copied tests: it must
#: be the one test that fails there
PINNED = '''from benchmark.cells import load_benchmark


def test_that_pins_the_last_cell():
    assert load_benchmark()["workloads"][-1]["name"] == {last!r}
'''


def test_appended_entries_fail_no_test_that_reads_the_declarations(tmp_path):
    """The guard: what ``test_a_new_cell_needs_only_new_files_and_entries``
    builds, and a second throw-away metric that lists every cell, appended in
    a copy that holds these tests too; every test there that runs no job
    passes.  A test that holds ``BENCHMARK.json`` to a place (the last cell,
    the tail of ``per_layer``, a ``workloads`` list equal to a fixed one)
    closes the benchmark to every PR that may add and not edit, and fails
    here in the PR that writes it."""
    bench, _ = a_copy_with_a_throwaway_cell(tmp_path, also_copy=["tests/benchmark"])
    shutil.copy(tmp_path / "benchmark" / "layer_metrics" / "jobs_in_window.py",
                tmp_path / "benchmark" / "layer_metrics" / "jobs_in_any_window.py")
    bench["per_layer"].append({"name": "jobs_in_any_window", "unit": "jobs", "better": "higher",
                               "source": "program_counter", "layer": "entry points", "moves": "shuffle_throughput",
                               "workloads": [w["name"] for w in bench["workloads"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "tests" / "benchmark" / "test_planted_pin.py").write_text(PINNED.format(last=CELLS[-1]))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(tmp_path), ROOT]), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-p", "no:cacheprovider", "-p", "no:xdist",
         "-p", "no:randomly", "-k", "not (" + " or ".join(RUN_A_JOB + [GUARD]) + ")"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=600,
    )
    summary = out.stdout.strip().splitlines()[-1]
    failed = [line for line in out.stdout.splitlines() if line.startswith("FAILED ")]
    assert len(failed) == 1 and "test_that_pins_the_last_cell" in failed[0], out.stdout[-4000:] + out.stderr[-2000:]
    assert out.returncode == 1 and "1 failed" in summary and " passed" in summary and "error" not in summary

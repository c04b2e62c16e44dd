"""The query cell (``q18sf10-queryjobs-1chip``): its configuration against the
reference's geometry and the program's conf, the reference's tables against
dbgen's laws, the five readers, the driver's refusal of a program without the
batch lane, and the cell through ``run.py``.

The controls, through ``run.py`` itself in a copy of the benchmark with a
throw-away driver and configuration (data and a driver added, nothing edited):
``withheld`` — every timed query's shuffle C lacks the block the reference
names, one that holds a line of a surviving order, so that order's sum comes
out short (the warm-up query is left whole: it is the window's comparison that
has to notice) — must come out not ``correct``; ``planted`` — orders whose
keys differ from a source order's only in their high four bytes and an order
whose quantities sum past 2**32 hundredths (``references/tpch-q18.py``
``Planted``) — and ``none`` must come out ``correct``.  As tests they run the
CPU form; on the chip this file is a program that runs them at the cell's own
size (``python3 tests/benchmark/test_benchmark_query.py --seed <n> --seconds
<s> [--modes withheld,planted,none]``) and exits 0 only if every one came out
as planted."""

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import numpy as np
import pytest

from benchmark.cells import load_benchmark, load_cell, load_module, reader
from benchmark.device_trace import Reduction
from benchmark.jobs import JobResult
from benchmark.measured import Run

reference = load_module("references", "tpch-q18")
CELL = "q18sf10-queryjobs-1chip"
CONFIG = "tpch-q18-sf10-hbm"
SEED = 3_000_000_061  # the driver's seeds pass 2**31


@pytest.fixture(scope="module")
def tiny():
    return load_cell(CELL, rehearse=True).config


@pytest.fixture(scope="module")
def query(tiny):
    return reference.make_records(tiny, SEED)


def test_the_cell_is_the_configuration_under_the_query_traffic_on_one_chip():
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "manager-queryjobs", 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and load_cell(CELL).config["reduced"] == {}
    mine = {m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert mine == {"query_task_p50_us", "query_sort_device_ms_per_job", "query_aggregate_roofline",
                    "query_join_roofline", "query_result_d2h_s_per_job"}


def test_the_configuration_states_the_sources_shapes_and_its_own_geometry():
    config = load_cell(CELL).config
    assert (config["scale_factor"], config["orders"], config["customers"]) == (10, 15_000_000, 1_500_000)
    assert (config["partitions"], config["quantity_threshold"], config["limit"]) == (200, 300, 100)
    assert (config["lineitem_splits"], config["orders_splits"]) == (57, 13)
    assert config["record_key_bytes"] == config["column_bytes"] == 8
    for key in ("source", "deployment", "guarantees", "kept", "reduced", "assumed", "store", "geometry", "rehearse"):
        assert key in config, key
    stated = {k: v for k, v in config["geometry"].items() if k != "from"}
    assert stated == reference.geometry(config, 1)  # the layout alone: the same for every seed
    assert stated["blocks"] == 25_400 and stated["map_tasks"] == 127 and stated["reduce_tasks"] == 200
    assert 1.67e9 < stated["job_bytes"] < 1.69e9
    assert all(s["staged_bytes"] <= config["store"]["staging_bytes"] for s in stated["shuffles"].values())
    assert stated["hbm_bytes_held"] == 6 * config["store"]["staging_bytes"] < 16e9


def test_the_configurations_conf_is_the_programs(tiny):
    from sparkucx_tpu.config import TpuShuffleConf

    for config in (load_cell(CELL).config, tiny):
        conf = TpuShuffleConf(**config["conf"])
        assert conf.keep_device_recv and conf.host_recv_mode == "device"
        assert conf.staging_capacity_per_executor == config["store"]["staging_bytes"]
        assert conf.block_alignment == config["store"]["alignment"]
        assert conf.replication_factor == 0
    full = TpuShuffleConf(**load_cell(CELL).config["conf"])
    assert full.max_host_pool_bytes == 3 * full.staging_capacity_per_executor  # a buffer a live shuffle


def test_the_tables_keep_dbgens_laws_and_the_layout_is_the_seeds_for_none(tiny, query):
    orders = np.concatenate([s.records for s in query.shuffles["B"]]).view("<u8").reshape(-1, 4)
    lines = np.concatenate([s.records for s in query.shuffles["C"]]).view("<u8").reshape(-1, 2)
    keys = np.sort(orders[:, 0])
    assert np.array_equal(keys, reference.order_keys(1, tiny["orders"]))
    assert list(keys[:9]) == [1, 2, 3, 4, 5, 6, 7, 32, 33] and (keys & np.uint64(24) == 0).all()
    assert (orders[:, 1] % 3 != 0).all() and 1 <= orders[:, 1].min() and orders[:, 1].max() <= tiny["customers"]
    assert reference.FIRST_ORDERDATE <= orders[:, 3].min() and orders[:, 3].max() <= reference.LAST_ORDERDATE
    assert reference.LAST_ORDERDATE - reference.FIRST_ORDERDATE == 2405
    per_order = np.bincount(np.searchsorted(keys, lines[:, 0]))
    assert per_order.min() >= 1 and per_order.max() <= 7 and set(lines[:, 0]) == set(keys)
    assert lines[:, 1].min() >= 100 and lines[:, 1].max() <= 5000 and (lines[:, 1] % 100 == 0).all()
    # a map task's partial sums: a row an order a split
    sums = np.concatenate([s.records for s in query.shuffles["A"]]).view("<u8").reshape(-1, 2)
    assert len(orders) <= len(sums) <= len(orders) + tiny["lineitem_splits"] - 1
    assert sums[:, 1].sum() == lines[:, 1].sum()
    # every record lies in the partition its key hashes to
    for splits in query.shuffles.values():
        for split in splits:
            part = reference.partition_of(split.records[:, :8].copy().view("<u8").ravel(), query.partitions)
            assert np.array_equal(np.searchsorted(part, np.arange(query.partitions + 1)), split.bounds)
    # the same seed makes the same tables; another seed other values in the same blocks
    same, other = reference.make_records(tiny, SEED), reference.make_records(tiny, SEED + 1)
    for name in reference.SHUFFLES:
        for a, b, c in zip(query.shuffles[name], same.shuffles[name], other.shuffles[name]):
            assert np.array_equal(a.records, b.records) and np.array_equal(a.bounds, c.bounds)
    assert not np.array_equal(query.shuffles["C"][0].records, other.shuffles["C"][0].records)
    assert query.answer == same.answer != other.answer


def test_the_answer_is_the_query_over_the_columns(query):
    """Computed once more, a row at a time, from the map output itself."""
    totals, order_of = {}, {}
    for split in query.shuffles["C"]:
        for key, quantity in split.records.view("<u8").reshape(-1, 2).tolist():
            totals[key] = totals.get(key, 0) + quantity
    for split in query.shuffles["B"]:
        for key, custkey, price, date in split.records.view("<u8").reshape(-1, 4).tolist():
            order_of[key] = (custkey, price, date)
    rows = [(k, t, *order_of[k]) for k, t in totals.items() if t > query.threshold]
    assert sorted(rows) == sorted(map(tuple, query.rows.tolist())) and len(rows) >= 5
    rows.sort(key=lambda r: (-r[3], r[4], r[0]))
    assert query.answer == [(reference.customer_name(c), c, k, d, p, t) for k, t, c, p, d in rows[: query.limit]]
    assert query.answer[0][0].startswith("Customer#") and len(query.answer[0][0]) == 18
    assert query.records_aggregated == sum(len(s.records) for s in query.shuffles["A"]) + sum(
        1 for s in query.shuffles["C"] for k in s.records.view("<u8").reshape(-1, 2)[:, 0].tolist()
        if totals[k] > query.threshold)
    split, part = query.survivor_block
    block = query.shuffles["C"][split]
    held = block.records[block.bounds[part] : block.bounds[part + 1]].view("<u8").reshape(-1, 2)[:, 0]
    assert set(held.tolist()) & {r[0] for r in rows}


def a_run(module_s, spans=(), program_spans=()):
    return Run(chips=1, device_kind="TPU v5 lite", setup_s=1.0, job_bytes=1, jobs=[JobResult(1.0, 1, 0, 0, [])],
               spans=list(spans), rounds=[1], stats_before={}, stats_after={}, fetch_faults=0,
               program_spans=list(program_spans),
               reduction=Reduction(1.0, 0.5, 0.5, [], [], module_s, devices=1, planes=1) if module_s is not None else None)


def test_the_five_readers_on_a_run_made_up_by_hand():
    from benchmark import query_path
    config = load_cell(CELL).config
    shuffles = config["geometry"]["shuffles"]
    agg, join = query_path.aggregate_bytes(config), query_path.join_bytes(config)
    assert shuffles["A"]["bytes"] < agg < shuffles["A"]["bytes"] + 3_000_000
    assert shuffles["B"]["bytes"] + shuffles["C"]["bytes"] < join < shuffles["B"]["bytes"] + shuffles["C"]["bytes"] + 3_000_000
    run = a_run({"jit_grouped_sum_records(1)": 0.020, "jit_grouped_sum_records(2)": 0.010,
                 "jit_merge_join_records(7)": 0.040, "jit_ordered_records(3)": 0.5, "jit_other(9)": 9.0})
    assert reader("layer_metrics", "query_aggregate_roofline")(run) == pytest.approx(100 * agg / 819e9 / 0.030)
    assert reader("layer_metrics", "query_join_roofline")(run) == pytest.approx(100 * join / 819e9 / 0.040)
    assert reader("layer_metrics", "query_sort_device_ms_per_job")(run) == pytest.approx(500.0)
    ms = 1_000_000
    spans = [("job.read", 0, 100 * ms)]
    program = [("exchange.assemble", 0, 1), ("query.task", 0, 3 * ms), ("query.task", 3 * ms, 4 * ms),
               ("query.task", 4 * ms, 9 * ms), ("query.result.d2h", 1 * ms, 3 * ms), ("query.result.d2h", 8 * ms, 9 * ms),
               ("query.result.d2h", 200 * ms, 300 * ms)]  # the last one outside the query's read
    run = a_run(None, spans, program)
    assert reader("layer_metrics", "query_task_p50_us")(run) == pytest.approx(3000.0)
    assert reader("layer_metrics", "query_result_d2h_s_per_job")(run) == pytest.approx(0.003)
    # a program without the spans or the executables (the parent commit): left out, never zero
    blind = a_run({"jit_local_fn(1)": 0.1}, spans, [("exchange.assemble", 0, 1)])
    for name in ("query_task_p50_us", "query_sort_device_ms_per_job", "query_aggregate_roofline",
                 "query_join_roofline", "query_result_d2h_s_per_job"):
        assert reader("layer_metrics", name)(blind) is None, name


def test_a_program_without_the_batch_lane_is_refused_before_any_row_is_made(monkeypatch):
    """The parent commit's shape: ``sparkucx_tpu.query.batch`` does not
    import.  ``start`` exits non-zero and has made no record."""
    driver = load_module("traffic", "manager-queryjobs")
    made = []
    monkeypatch.setattr(reference, "make_records", lambda *a: made.append(a))
    import sparkucx_tpu.query

    monkeypatch.delattr(sparkucx_tpu.query, "batch")
    monkeypatch.setitem(sys.modules, "sparkucx_tpu.query.batch", None)  # ``import`` raises ImportError
    traffic = driver.Traffic(load_cell(CELL, rehearse=True), argparse.Namespace(seed=1, seconds=1.0, trace=0))
    with pytest.raises(SystemExit) as refused:
        traffic.start(None, {})
    assert "batch lane" in str(refused.value) and refused.value.code not in (0, None)
    assert made == [] and traffic.manager is None
    traffic.close()


# -- the cell and its controls through run.py -----------------------------------------

DAMAGED = "q18sf10-queryjobs-control"
DAMAGED_DRIVER = '''"""A throw-away control: ``manager-queryjobs`` whose timed queries (the
warm-up query is left whole, so it is the window's comparison that has to
notice) run what the traffic file's ``damage`` says: ``withheld`` — shuffle C
lacks the block the reference names (``survivor_block``: a map task's block
that holds a line of a surviving order) — and nothing for ``planted`` /
``none`` (the planted orders are the throw-away configuration's)."""

import numpy as np

from benchmark.cells import load_module

shipped = load_module("traffic", "manager-queryjobs")


class Traffic(shipped.Traffic):
    def make_inputs(self):
        inputs = super().make_inputs()
        self.damaged = None
        if self.cell.traffic["damage"] == "withheld":
            split, part = self.records.survivor_block
            whole = inputs["lineitem"][split]
            lo, hi = int(whole.bounds[part]), int(whole.bounds[part + 1])
            bounds = whole.bounds.copy()
            bounds[part + 1:] -= hi - lo
            lines = list(inputs["lineitem"])
            lines[split] = self.batch.RecordSplit(np.delete(whole.records, slice(lo, hi), axis=0), bounds)
            self.damaged = dict(inputs, lineitem=lines)
            print("control: " + repr({"withheld_block": [split, part], "lines": hi - lo}), flush=True)
        return inputs

    def run_query(self, log, control, full):
        if not full and self.damaged is not None:
            self.inputs = self.damaged
        return super().run_query(log, control, full)
'''
#: mode -> (the throw-away configuration's ``planted``, correct)
CONTROLS = {
    "withheld": (None, False),
    "planted": ({"high_lane": 3, "large_sum": True}, True),
    "none": (None, True),
}


def run_py(root, cell, seed, seconds, trace, rehearse, **env):
    env = dict(os.environ, PYTHONPATH=ROOT, **env)
    env.pop("XLA_FLAGS", None)  # a test session's eight devices are not the cell's
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=3000)


def run_a_control(root, mode, seed, seconds, rehearse, **env):
    """``run.py`` on the damaged cell in a copy of the benchmark under
    ``root``; returns the finished process."""
    shutil.rmtree(os.path.join(root, "benchmark"), ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    made = os.path.join(root, "benchmark")
    with open(os.path.join(made, "traffic", "manager-queryjobs-damaged.json"), "w") as f:
        json.dump({"driver": "manager-queryjobs-damaged", "damage": mode}, f)
    with open(os.path.join(made, "traffic", "manager-queryjobs-damaged.py"), "w") as f:
        f.write(DAMAGED_DRIVER)
    bench = load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if CONTROLS[mode][0]:
        config["planted"] = CONTROLS[mode][0]
    with open(os.path.join(made, "configs", CONFIG + "-control.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({**entry, "name": CONFIG + "-control",
                             "file": f"benchmark/configs/{CONFIG}-control.json"})
    bench["workloads"].append({"name": DAMAGED, "config": CONFIG + "-control",
                               "traffic": "manager-queryjobs-damaged", "chips": 1, "why": "the control of " + CELL})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return run_py(root, DAMAGED, seed, seconds, 0, rehearse, **env)


def lines_of(out):
    lines = out.stdout.strip().splitlines()
    found = lambda label: json.loads(next(l for l in lines if l.startswith(label + ": ")).split(": ", 1)[1])
    return json.loads(lines[-1]), found


def verdict(out, mode):
    """(the run came out as planted, its last line, its ``window:`` line)."""
    last, found = lines_of(out)
    window, line = found("window"), found("query")
    correct = CONTROLS[mode][1]
    as_planted = out.returncode == 0 and last["correct"] is correct and window["jobs"] >= 1
    if correct:
        as_planted &= last["failed"] == 0 and window["warmup_failed_tasks"] == 0 and line["unsound"] == []
    else:  # every timed query failed the task that lost a line, by the comparison, and the counters saw it too
        as_planted &= last["failed"] >= window["jobs"] and "its rows are not the reference's" in out.stdout
        as_planted &= any("records_aggregated" in why for why in line["unsound"])
    return bool(as_planted), last, window


@pytest.mark.parametrize("mode", sorted(CONTROLS))
def test_a_lost_block_comes_out_as_not_correct_under_the_query_too(tmp_path, mode):
    out = run_a_control(str(tmp_path), mode, seed=2147483659, seconds=0.5, rehearse=True,
                        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    as_planted, last, window = verdict(out, mode)
    assert as_planted, (last, window["warmup_failed_tasks"], window["jobs"], out.stdout[-1500:])
    assert "Error" not in out.stdout  # no task raised: the comparison found it


def test_rehearsal_of_the_query_cell_prints_the_query_line(tmp_path):
    """The traced CPU run: 200-partition shape at eight partitions, every
    task on the device lane, the host readers report."""
    out = run_py(ROOT, CELL, SEED, 0.5, 1, True,
                 JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, found = lines_of(out)
    tiny = load_cell(CELL, rehearse=True).config
    assert last["correct"] is True and last["failed"] == 0
    window, line = found("window"), found("query")
    tasks = tiny["lineitem_splits"] * 2 + tiny["orders_splits"] + tiny["partitions"]
    assert last["attempted"] == window["jobs"] * tasks
    metrics = {name: m["value"] for name, m in last["metrics"].items()}
    assert metrics["query_task_p50_us"] > 0 and metrics["query_result_d2h_s_per_job"] > 0
    assert metrics["staging_rounds_per_job"] == 1 and metrics["read_s_per_job"] > metrics["query_result_d2h_s_per_job"]
    assert "ordered_read_task_p50_us" not in metrics and "query_aggregate_roofline" not in metrics
    queries = line["queries"]
    assert queries == window["jobs"] + 1 and line["unsound"] == [] and line["gather"] == ["xla"]
    counted = line["counters"]
    assert counted["device_tasks"] == queries * tiny["partitions"]
    assert counted["records_aggregated"] == queries * line["records_aggregated_a_query"]
    assert counted["ordered_d2h_bytes"] == 0 and counted["overflow_checks"] == 2 * counted["device_tasks"]
    assert line["ordered_in_flight"] == [0] and line["ordered_in_flight_peak"] == [1]
    assert len(line["bytes_in_use_after_query"]) == queries
    # from the second query on every staging buffer comes from the free list
    assert line["stores"][0]["pool_misses"] == 3 and line["stores"][0]["pool_hits"] == 3 * (queries - 1)
    assert window["compiles_in_window"]["compiles"] == 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description="the controls of " + CELL + " at the cell's own size")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default=",".join(sorted(CONTROLS)))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    root = os.path.join(ROOT, ".scratch", "control")  # inside the checkout, listed in .gitignore
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    all_as_planted = True
    for i, mode in enumerate(args.modes.split(",")):
        out = run_a_control(root, mode, args.seed + i, args.seconds, args.rehearse)
        sys.stderr.write(out.stderr[-2000:])
        as_planted, last, window = verdict(out, mode)
        all_as_planted &= as_planted
        print(json.dumps({"mode": mode, "as_planted": as_planted, "wanted_correct": CONTROLS[mode][1],
                          "jobs": window["jobs"], "warmup_failed_tasks": window["warmup_failed_tasks"],
                          "compiles_in_window": window["compiles_in_window"]["compiles"],
                          "query": lines_of(out)[1]("query"), "last": last}), flush=True)
    sys.exit(0 if all_as_planted else 1)

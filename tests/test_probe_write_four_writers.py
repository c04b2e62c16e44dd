"""``scripts/probe_write_four_writers.py`` runs (at a toy size: it proves the
script, not a rate): every order writes every block once through the store's
own path, the orders that put leave the seal the piece the writers stood in,
and each reads the store's counters — a copy outside the lock only where
more than one writer was open."""

import importlib.util
import json
import os

import pytest

import sparkucx_tpu.store.hbm_store as hbm_store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIECE = 1 << 19


@pytest.fixture(scope="module")
def probe():
    spec = importlib.util.spec_from_file_location(
        "probe_write_four_writers", os.path.join(ROOT, "scripts", "probe_write_four_writers.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run(probe, tmp_path, orders, jobs=2):
    out = tmp_path / "probe.json"
    before = hbm_store.SEAL_PUT_PIECE_BYTES
    assert probe.main(["--capacity", str(1 << 23), "--tasks", "5", "--blocks", "6", "--block-bytes", "100000",
                       "--small", "4x10", "--piece", str(PIECE), "--jobs", str(jobs), "--orders", orders,
                       "--out", str(out)]) == 0
    assert hbm_store.SEAL_PUT_PIECE_BYTES == before
    report = json.loads(out.read_text())
    assert [r["order"] for r in report["runs"]] == orders.split(",")
    assert (report["tasks"], report["blocks"], report["small_blocks"]) == (5, 30, 40)
    return report, {r["order"]: r["jobs"] for r in report["runs"]}


@pytest.mark.parametrize("order", ["store-small", "store1", "store4", "store1+put", "store4+put"])
def test_a_store_order_reads_the_programs_own_counters(order, probe, tmp_path):
    report, runs = run(probe, tmp_path, order)
    assert report["small_bytes"] == sum(len(p) for t in probe.make_small_tasks(4, 10) for p in t)
    assert report["job_bytes"] == sum(len(p) for t in probe.make_tasks(5, 6, 100000) for p in t)
    assert len(runs[order]) == 2
    for j in runs[order]:
        # judged by what is counted: a duration rounded to four places of a toy job may read 0.0
        assert j["write_s"] >= 0 and j["copy_s"] >= 0
        assert j["staged_blocks"] == (40 if order == "store-small" else 30) and j["early_put_dropped"] == 0
        # one writer at a time keeps the lock through its copies; four copy outside it while they overlap
        if probe.threads_of(order.partition("+")[0]) == 1:
            assert j["unlocked_copy_blocks"] == 0
        else:
            assert 0 <= j["unlocked_copy_blocks"] <= 30
        if "+put" in order:  # every block read back from the sealed round on the device
            assert j["equal"] is True and (j["early_put_pieces"], j["seal_put_pieces"]) == (5, 1)
        else:
            assert "equal" not in j and j["early_put_pieces"] == 0

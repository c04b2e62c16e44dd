"""``scripts/probe_put_behind_writer.py`` runs (at a toy size: it proves the
script, not a rate), every order puts the pieces it says it puts, and its
``store`` order reads the store's early-put counters."""

import importlib.util
import json
import os

import sparkucx_tpu.store.hbm_store as hbm_store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_probe_runs_every_order_and_leaves_the_piece_size_as_it_was(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "probe_put_behind_writer", os.path.join(ROOT, "scripts", "probe_put_behind_writer.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    out = tmp_path / "probe.json"
    before = hbm_store.SEAL_PUT_PIECE_BYTES
    orders = "copy,put,serial,same,worker,store"
    assert probe.main(["--capacity", str(1 << 22), "--blocks", "48", "--piece", str(1 << 18), "--jobs", "2",
                       "--orders", orders, "--out", str(out)]) == 0
    assert hbm_store.SEAL_PUT_PIECE_BYTES == before
    report = json.loads(out.read_text())
    layout = probe.block_layout(48, 1 << 22)
    used = layout[-1][0] + layout[-1][1]
    reached = -(-used // (1 << 18))
    assert report["blocks"] == 48 and report["pieces_reached"] == reached > 2
    assert [run["order"] for run in report["runs"]] == orders.split(",")
    runs = {run["order"]: run["jobs"] for run in report["runs"]}
    assert all(len(jobs) == 2 for jobs in runs.values())
    for job in runs["put"] + runs["serial"]:
        assert (job["early_pieces"], job["seal_pieces"]) == (0, reached)
    for job in runs["same"] + runs["worker"]:
        assert (job["early_pieces"], job["seal_pieces"]) == (used // (1 << 18), reached - used // (1 << 18))
    for job in runs["store"]:  # the program's own path: the same pieces, by its counters
        assert (job["early_put_pieces"], job["seal_put_pieces"]) == (used // (1 << 18), reached - used // (1 << 18))
        assert job["early_put_bytes"] == job["early_put_pieces"] << 18 and job["early_put_dropped"] == 0
        assert job["equal"] is True  # the sealed round read back from the device, against the script's own buffer
    # the store's first job writes into fresh pages: the seal puts all of it
    first = report["store_first_job"]
    assert (first["early_put_pieces"], first["seal_put_pieces"]) == (0, reached) and first["equal"] is True

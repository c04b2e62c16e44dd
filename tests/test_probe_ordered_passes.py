"""``scripts/probe_ordered_passes.py`` runs (at a toy size: it proves the
script, not a time): every form of the ordering executable it compares gives
NumPy's stable order of the covered places over poisoned segments, its
oracle notices what the cell's controls plant, and the D2H phase brings every
array across from as many threads as asked."""

import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def probe():
    spec = importlib.util.spec_from_file_location(
        "probe_ordered_passes", os.path.join(ROOT, "scripts", "probe_ordered_passes.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("form", ["parent", "keys", "change", "bare", "slices"])
def test_a_form_orders_a_poisoned_task_as_numpy_does(form, probe, tmp_path):
    out = tmp_path / "probe.json"
    assert probe.main(["--slots", "40", "--tasks", "2", "--equal", "--forms", form, "--out", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["equal"] == {form: True} and report["record_places"] == 40 * 128
    [row] = report["forms"]
    assert row["form"] == form and row["tasks"] == 2 and row["wall_ms_a_task"] > 0


def test_the_oracle_notices_what_the_controls_plant(probe):
    """A record dropped, two neighbours exchanged, padding that is not zero:
    each is not ``equal``; a form that leaves the padding as it falls is held
    to its records alone."""
    table, segment, (records, covered) = probe.make_task(30, seed=7)
    want = probe.oracle(records, covered)
    assert len(want) == table[1].sum() < 30 * 128
    good = np.zeros((30 * 128, 25), np.uint32)
    good[: len(want)] = want
    assert probe.equal("change", good.reshape(-1), records, covered)
    swapped = good.copy()
    swapped[[10, 11]] = swapped[[11, 10]]
    dropped = good.copy()
    dropped[5 : len(want) - 1] = good[6 : len(want)]
    dropped[len(want) - 1] = 0
    leaked = good.copy()
    leaked[-1] = 0xFFFFFFFF
    for bad in (swapped, dropped, leaked):
        assert not probe.equal("change", bad.reshape(-1), records, covered)
    assert probe.equal("bare", leaked.reshape(-1), records, covered)


@pytest.mark.parametrize("pieces", [1, 2])
@pytest.mark.parametrize("threads", [1, 4])
def test_the_d2h_phase_brings_every_array_across(threads, pieces, probe):
    [row] = probe.d2h_in_flight(places=256, depths=[threads], arrays=6, jobs=2, pieces=pieces)
    assert (row["threads"], row["pieces"], row["arrays"], row["bytes_each"]) == (threads, pieces, 6, 256 * 100)
    assert len(row["jobs"]) == 2 and all(j["gb_s"] > 0 for j in row["jobs"])

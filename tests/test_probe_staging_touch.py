"""``scripts/probe_staging_touch.py`` runs (at a toy size: it proves the script,
not a rate) and its ``store`` variant reads the free list's counters."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_probe_runs_and_reports_every_variant_job_by_job(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "probe_staging_touch", os.path.join(ROOT, "scripts", "probe_staging_touch.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    out = tmp_path / "probe.json"
    assert probe.main(["--capacity", str(1 << 20), "--blocks", "40", "--jobs", "3", "--out", str(out)]) == 0
    report = json.loads(out.read_text())
    layout = probe.block_layout(40, 1 << 20)
    assert report["blocks"] == 40 and report["job_bytes"] == sum(n for _, n in layout)
    assert all(o % probe.ALIGN == 0 for o, _ in layout) and layout[-1][0] + layout[-1][1] <= 1 << 20
    assert [run["variant"] for run in report["runs"]] == ["fresh", "kept", "store", "fresh"]
    assert all(len(run["jobs"]) == 3 for run in report["runs"])
    store = report["runs"][2]["jobs"]
    # one buffer, job after job: allocated once, taken from the free list after
    assert [(j["pool_hits"], j["pool_misses"], j["pool_dropped_busy"]) for j in store] == [(0, 1, 0), (1, 1, 0), (2, 1, 0)]
    assert all(j["pool_held_bytes"] == 1 << 20 for j in store)

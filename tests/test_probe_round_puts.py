"""``scripts/probe_round_puts.py`` runs (at a toy size: it proves the script,
not a rate): every order exchanges the bytes it staged, the ``early`` order
puts every round before the exchange, and the overlap arithmetic is right."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    spec = importlib.util.spec_from_file_location(
        "probe_round_puts", os.path.join(ROOT, "scripts", "probe_round_puts.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    return probe


def test_the_probe_runs_every_order_and_receives_what_it_staged(tmp_path):
    probe = load()
    out = tmp_path / "probe.json"
    assert probe.main(["--rounds", "4", "--rows", "4096", "--jobs", "2", "--out", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["rounds"] == 4 and report["round_bytes"] == 4096 * 512 and report["depth"] == 2
    assert list(report["orders"]) == ["chain", "early", "copy"]
    for order in ("chain", "early"):
        jobs = report["orders"][order]["jobs"]
        assert len(jobs) == 2 and all(job["equal"] is True and job["exchange_s"] > 0 for job in jobs)
    assert all("hold_ms_sum" in job and job["write_s"] > 0 for job in report["orders"]["early"]["jobs"])
    assert all(set(job) == {"write_s"} for job in report["orders"]["copy"]["jobs"])


def test_the_overlap_share_counts_the_time_two_transfers_were_open_at_once():
    probe = load()
    assert probe.overlap_share([(0, 10), (10, 20), (20, 30)]) == 0.0  # one after another
    assert probe.overlap_share([(0, 10), (0, 10)]) == 1.0  # side by side throughout
    assert probe.overlap_share([(0, 10), (5, 15)]) == 0.5  # half of each beside the other
    assert probe.overlap_share([]) == 0.0

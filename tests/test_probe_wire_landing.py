"""``scripts/probe_wire_landing.py`` runs (at a toy size: it proves the script,
not a rate): every row of the three ways a reply travels reads its bytes back
equal, the mapping's name is gone once both processes hold it, and the ratio
the issue's rule reads is in the report."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    spec = importlib.util.spec_from_file_location(
        "probe_wire_landing", os.path.join(ROOT, "scripts", "probe_wire_landing.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    return probe


def test_the_probe_runs_every_way_and_reads_back_what_was_served(tmp_path):
    probe = load()
    out = tmp_path / "probe.json"
    before = set(os.listdir(probe.SHM_DIR))
    assert probe.main(["--large", "5x6003", "--small", "7x160", "--pool-mb", "1", "--tasks", "6",
                       "--decode-us", "20,1", "--out", str(out)]) == 0
    assert {n for n in set(os.listdir(probe.SHM_DIR)) - before if n.startswith("probe-landing-")} == set()
    report = json.loads(out.read_text())
    rows = {(r["shape"], r["mode"], bool(r["decode_us"])): r for r in report["rows"]}
    assert set(rows) == {(s, m, d) for s in ("large", "small") for m in probe.MODES for d in (False, True)}
    assert all(r["equal"] is True and r["tasks"] == 6 and r["task_p50_us"] > 0 and r["sender_p50_us"] > 0
               for r in rows.values())
    assert rows[("large", "mapped", True)]["blocks"] == 5 and rows[("small", "socket_block", False)]["bytes"] == 160
    # the stand-in holds the receiver for its time a block
    assert rows[("large", "mapped", True)]["task_p50_us"] >= 5 * 20
    for shape in ("large", "small"):
        assert set(report["ratio"][shape]) == {"transfer", "with_decode", "block_at_a_time_with_decode"}
        assert report["ratio"][shape]["transfer"] == (
            rows[(shape, "mapped", False)]["task_p50_us"] / rows[(shape, "socket_whole", False)]["task_p50_us"])


def test_a_task_reads_places_of_the_pool_no_task_near_it_reads():
    probe = load()
    assert probe.task_offsets(0, 3, 100, 1000) == [0, 100, 200]
    assert probe.task_offsets(1, 3, 100, 1000) == [300, 400, 500]
    assert probe.task_offsets(3, 3, 100, 1000) == [900, 0, 100]  # round the pool
    assert probe.parse_shape("13x625475") == {"blocks": 13, "bytes": 625475}
    assert (probe.make_pool(60, 64) == probe.make_pool(60, 64)).all()
    assert probe.touch(memoryview(bytes(range(16)))) == sum(
        int.from_bytes(bytes(range(i, i + 8)), "little") for i in (0, 8))
    assert probe.touch(memoryview(b"abc")) == 0

"""``scripts/probe_wire_batches.py`` runs (at a toy size: it proves the script,
not a rate): every row reads its blocks back equal, a bound sends the frames
it should, and the bound the probe fixed is the module's."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load():
    spec = importlib.util.spec_from_file_location(
        "probe_wire_batches", os.path.join(ROOT, "scripts", "probe_wire_batches.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    return probe


def test_the_probe_runs_every_bound_and_reads_back_what_it_wrote(tmp_path):
    probe = load()
    out = tmp_path / "probe.json"
    assert probe.main(["--small", "6x5x160", "--large", "2x4x6000", "--jobs", "2", "--connections", "1,2",
                       "--bounds", "at_once,0,1000,8388608", "--out", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["write_batch_bytes"] == 64 << 20
    rows = {(r["shape"], r["connections"], r["bound"]): r for r in report["rows"]}
    assert len(rows) == 2 * 2 * 4 and all(r["equal"] is True and r["write_s"] > 0 for r in rows.values())
    for connections in (1, 2):
        # a frame a block both ways; five 160 B blocks under 1,000 B and a map task under 8 MiB: one frame a task
        assert [rows[("small", connections, b)]["frames_a_job"] for b in ("at_once", 0, 1000, 8388608)] == [30, 30, 6, 6]
        assert rows[("small", connections, 8388608)]["blocks_a_frame"] == 5
        # a 6,000 B block is over 1,000 B: alone; four of them are a frame at 8 MiB
        assert [rows[("large", connections, b)]["frames_a_job"] for b in ("at_once", 0, 1000, 8388608)] == [8, 8, 8, 2]
    # a row's ``write_stats`` are each connection's over the row's last job
    at_once = rows[("small", 1, "at_once")]["write_stats"][0]
    assert at_once["sent_at_once"] == at_once["write_frames"] == 30 and at_once["flushes_full"] == 0
    full = rows[("large", 2, 1000)]["write_stats"]
    assert [s["flushes_full"] for s in full] == [4, 4] and all(s["sent_at_once"] == 0 for s in full)


def test_the_blocks_of_a_map_task_are_the_same_in_every_process():
    probe = load()
    assert probe.map_blocks(59, 3, 4, 100) == probe.map_blocks(59, 3, 4, 100)
    assert probe.map_blocks(59, 3, 4, 100) != probe.map_blocks(59, 4, 4, 100)
    assert [len(b) for b in probe.map_blocks(1, 0, 3, 7)] == [7, 7, 7]
    assert probe.parse_shape("13x200x625475") == {"maps": 13, "blocks": 200, "bytes": 625475}

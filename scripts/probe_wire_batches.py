#!/usr/bin/env python
"""What does a ``WritePartition`` frame cost against the bytes it carries?
The probe of PR 59: it fixed ``shuffle/daemon.py`` ``WRITE_BATCH_BYTES`` and is
kept because it is the only reading of the wire's write leg apart from the
benchmark's three daemon cells.

A real ``ShuffleDaemon`` in this process (on the chip's host it holds the
chip, as the benchmark's harness does) and ``DaemonClient``s in processes of
their own that never touch a device, as a Spark node's task slots are.  A
*job* is the map stage of one of two shapes — ``small``: the 1k gate job,
100 map tasks of 63 blocks of 1.6 KB; ``large``: the 25k gate job at one
chip's share, 13 map tasks of 200 blocks of 625 KB — written by one
connection or by four side by side (map task ``m`` goes to connection ``m``
mod 4), each task open → ``write_partition`` a block → ``commit_map``.  The
clock runs from the harness's "go" to the last connection's last commit
acknowledged.

For every shape and number of connections the job is run at each *bound*:
``at_once`` (the blocks handed over as ``memoryview``s: one one-block frame a
block, the frame of before PR 59, byte for byte), then ``WRITE_BATCH_BYTES`` =
0 (one frame a block in the several-block form), 64 KiB, 1 MiB, 8 MiB and
64 MiB (set in the client processes only: the daemon knows no bound).  A row
gives the median ``write_s`` of ``--jobs`` jobs, ``us_a_block``, the frames a
job and ``blocks_a_frame`` the daemon counted (``op_stats()``), the daemon's
``serve_us_a_block`` (``serve_ns`` ÷ blocks) and the clients' own
``write_stats()``.  The first job of every row is exchanged and **every block
read back and compared** (``equal``), off the clock; a job before the first
row of a shape warms the store's round buffers, untimed.

The table this gave on the chip's host (TPU v5 lite, one chip, 13 cores;
PR 59, my chip run) is in ``PERF.md`` section 6 under PR 59; what it fixed:
the bound is 64 MiB.  At 1.6 KB blocks every bound from 1 MiB up sends a map
task in one frame and reads the same (55-60 us a block from one connection
where a frame a block reads 404).  At 625 KB blocks the cost a block falls all
the way: 675 us alone, 532 at 1 MiB, 305 at 8 MiB, 237 at 64 MiB from one
connection (368 / 251 / 158 / 145 from four) — a frame's fixed cost there is
about 1.2 ms, most of it the client's turn, so 14 blocks a frame still pay
84 us each of it and 100 pay 12; the benchmark's one-client 25k cell read
810-877 MB/s at 8 MiB and 929-964 at 64 MiB on the same seeds.  Beyond
64 MiB there are 12 us a block left to win, and a frame is a reactor worker's
for as long as it lasts (20 ms at 64 MiB).

Run on the chip:  ``python scripts/probe_wire_batches.py``; the table goes to
stdout and ``chiprun_out/probe_wire_batches.json``.  ``--small`` / ``--large``
(``maps x blocks x bytes``), ``--bounds``, ``--connections`` and ``--jobs``
shrink it to prove here that the script works; a time from this sandbox says
nothing about the chip's host.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

AT_ONCE = "at_once"
BOUNDS = (AT_ONCE, 0, 64 << 10, 1 << 20, 8 << 20, 64 << 20)


def map_blocks(seed: int, map_id: int, blocks: int, nbytes: int) -> list:
    """The blocks of one map task, the same in every process that asks."""
    data = np.random.default_rng((seed, map_id)).integers(0, 256, size=blocks * nbytes, dtype=np.uint8).tobytes()
    return [data[i * nbytes : (i + 1) * nbytes] for i in range(blocks)]


def parse_shape(text: str) -> dict:
    maps, blocks, nbytes = (int(x) for x in text.split("x"))
    return {"maps": maps, "blocks": blocks, "bytes": nbytes}


# -- a connection: a process of its own, told what to write over its stdin ---


def client_main(spec: dict) -> int:
    from sparkucx_tpu.shuffle import daemon as wire

    out = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    sys.stdout = sys.stderr
    shape, seed = spec["shape"], spec["seed"]
    mine = {
        m: map_blocks(seed, m, shape["blocks"], shape["bytes"])
        for m in range(spec["index"], shape["maps"], spec["connections"])
    }
    client = wire.DaemonClient(tuple(spec["address"]))
    print(json.dumps({"ready": spec["index"]}), file=out, flush=True)
    for line in sys.stdin:
        job = json.loads(line)
        at_once = job["bound"] == AT_ONCE
        if not at_once:
            wire.WRITE_BATCH_BYTES = job["bound"]
        before = client.write_stats()
        t0 = time.perf_counter()
        for m, blocks in mine.items():
            writer = client.open_map_writer(job["shuffle_id"], m)
            for r, block in enumerate(blocks):
                client.write_partition(writer, r, memoryview(block) if at_once else block)
            lengths = client.commit_map(writer)
            if int(lengths.sum()) != len(blocks) * shape["bytes"]:
                raise AssertionError(f"map {m} committed {int(lengths.sum())} bytes")
        seconds = time.perf_counter() - t0
        stats = {k: v - before[k] for k, v in client.write_stats().items()}
        print(json.dumps({"write_s": seconds, "write_stats": stats}), file=out, flush=True)
    client.close()
    return 0


# -- the harness: the daemon, the clock and the comparison ------------------


def write_row(daemon) -> dict:
    return next((r for r in daemon.op_stats() if r["op"] == "write_partition"), None) or dict.fromkeys(
        ("frames", "blocks", "serve_ns"), 0
    )


def run_shape(daemon, ctl, name, shape, connections, bounds, jobs, seed, next_sid) -> list:
    from sparkucx_tpu.core.block import ShuffleBlockId

    env = dict(os.environ, JAX_PLATFORMS="cpu")  # a device is the harness's
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--client",
             json.dumps({"address": list(daemon.address), "shape": shape, "seed": seed,
                         "index": k, "connections": connections})],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        )
        for k in range(connections)
    ]
    rows = []
    try:
        for p in procs:
            assert "ready" in json.loads(p.stdout.readline())
        blocks_a_job = shape["maps"] * shape["blocks"]

        def write_job(bound):
            """One job's map stage at ``bound``: (shuffle id, seconds, the clients' replies)."""
            sid = next_sid()
            ctl.create_shuffle(sid, shape["maps"], shape["blocks"])
            t0 = time.perf_counter()
            for p in procs:
                p.stdin.write(json.dumps({"bound": bound, "shuffle_id": sid}) + "\n")
                p.stdin.flush()
            replies = [json.loads(p.stdout.readline()) for p in procs]
            return sid, time.perf_counter() - t0, replies

        ctl.remove_shuffle(write_job(bounds[0])[0])  # the store's first job touches fresh round buffers
        for bound in bounds:
            seconds, replies, equal = [], [], None
            before = write_row(daemon)
            for job in range(jobs):
                sid, took, replies = write_job(bound)
                seconds.append(took)
                if job == 0:  # off the clock: every block back, byte for byte
                    ctl.run_exchange(sid)
                    equal = True
                    for m in range(shape["maps"]):
                        want = map_blocks(seed, m, shape["blocks"], shape["bytes"])
                        got = ctl.fetch_blocks([ShuffleBlockId(sid, m, r) for r in range(shape["blocks"])])
                        equal = equal and all(g is not None and g == w for g, w in zip(got, want))
                ctl.remove_shuffle(sid)
            after = write_row(daemon)
            frames, blocks = (after[k] - before[k] for k in ("frames", "blocks"))
            write_s = statistics.median(seconds)
            rows.append({
                "shape": name, "connections": connections, "bound": bound, "write_s": write_s,
                "write_s_all": seconds, "us_a_block": write_s / blocks_a_job * 1e6,
                "frames_a_job": frames / jobs, "blocks_a_frame": blocks / frames if frames else 0.0,
                "serve_us_a_block": (after["serve_ns"] - before["serve_ns"]) / max(blocks, 1) / 1e3,
                "client_write_s": [r["write_s"] for r in replies],
                "write_stats": [r["write_stats"] for r in replies], "equal": equal,
            })
            print(
                f"{name:>5} x{connections} bound {bound!s:>9}: write {write_s:8.4f} s  "
                f"{rows[-1]['us_a_block']:8.2f} us a block  {rows[-1]['frames_a_job']:7.1f} frames a job  "
                f"{rows[-1]['blocks_a_frame']:6.2f} blocks a frame  daemon {rows[-1]['serve_us_a_block']:7.2f} us a block  "
                f"equal {equal}",
                flush=True,
            )
    finally:
        for p in procs:
            p.stdin.close()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            p.stdout.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--client", help=argparse.SUPPRESS)
    ap.add_argument("--small", default="100x63x1600")
    ap.add_argument("--large", default="13x200x625475")
    ap.add_argument("--bounds", default=",".join(str(b) for b in BOUNDS))
    ap.add_argument("--connections", default="1,4")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=59)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "probe_wire_batches.json"))
    args = ap.parse_args(argv)
    if args.client:
        return client_main(json.loads(args.client))

    import jax

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.daemon import WRITE_BATCH_BYTES, DaemonClient, ShuffleDaemon

    bounds = [b if b == AT_ONCE else int(b) for b in args.bounds.split(",")]
    device = jax.devices()[0]
    report = {"device": {"platform": device.platform, "kind": device.device_kind},
              "cpu_count": os.cpu_count(), "write_batch_bytes": WRITE_BATCH_BYTES, "rows": []}
    daemon = ShuffleDaemon(TpuShuffleConf(), num_executors=1, port=0)
    ctl = DaemonClient(daemon.address)
    sids = iter(range(1, 1 << 30))
    try:
        for name in ("small", "large"):
            shape = parse_shape(getattr(args, name))
            for connections in (int(c) for c in args.connections.split(",")):
                report["rows"] += run_shape(
                    daemon, ctl, name, shape, connections, bounds, args.jobs, args.seed, lambda: next(sids)
                )
    finally:
        ctl.close()
        daemon.close()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0 if all(row["equal"] for row in report["rows"]) else 1


if __name__ == "__main__":
    sys.exit(main())

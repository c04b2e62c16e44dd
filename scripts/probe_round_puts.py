#!/usr/bin/env python
"""Is a multi-round exchange's 10 ms a round its own ``device_put``?  The
probe of PR 57 (ISSUE "Price it first"), kept because it is the chip's only
reading of the exchange's cycle apart from the program.  Needs a device.

``gbt25k-jobs-1chip`` exchanges a job's 1.63 GB as 25 staging rounds of
64 MiB through ``RoundPipeline`` at depth 2: submit(k) puts round k on the
chip (one ``jax.device_put`` of the host round), dispatches the collective
and starts the received shard's D2H; drain(k) waits for that D2H.  A round's
device chain is H2D → collective → D2H, and round k's D2H cannot start before
round k's H2D has ended.  This script runs that loop — the program's own
``RoundPipeline``, exchange executable (``TpuShuffleCluster._exchange_fn``)
and landing pool (``_landing``) — over 25 held host rounds, job after job,
in these orders:

* ``chain``  — the parent's: every submit puts its own host round;
* ``early``  — a writer thread copies the job's 1.63 GB as 625 KB blocks into
  the 25 held rounds and puts each round on the chip (one ``device_put``)
  the moment its last block is copied, going on with the next round's copies
  while the DMA reads; then the exchange takes the device rounds and puts
  nothing.  ``hold_ms`` is what a put's call held the writer;
* ``copy``   — the same copies with no put: what the writer costs alone
  (``early``'s write minus this is the holds plus what the copies lose to
  the DMA's reads).

For the exchange of every job: ``exchange_s``, ``cycle_ms`` (exchange ÷
rounds), the median ``submit_ms`` / ``drain_ms`` of a round, and
``d2h_overlap``: of the rounds' D2H intervals (landing started → shard
readable on the host, by the host's clock) the share of their summed length
during which two were open at once — 0 where they run one after another.
Off the clock every received shard is compared with its host round.

Run on the chip:  ``python scripts/probe_round_puts.py``; the table goes to
stdout and ``chiprun_out/probe_round_puts.json``.  ``--rounds``, ``--rows``
and ``--jobs`` shrink it to prove here that the script works; a time from
this sandbox says nothing about the chip.
"""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from sparkucx_tpu.config import TpuShuffleConf  # noqa: E402
from sparkucx_tpu.transport.pipeline import RoundPipeline  # noqa: E402
from sparkucx_tpu.transport.tpu import TpuShuffleCluster, _start_landing  # noqa: E402

ALIGN = 512
LANE = ALIGN // 4
RECORD = 25_019  # a framed GroupByTest record of 25,000 value bytes
BLOCK = 25 * RECORD  # about 625 KB: a (map, reduce) block of the 25k job


def overlap_share(intervals):
    """Of the summed length of ``intervals`` (start, end), the share during
    which at least two were open."""
    events = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals])
    open_now, last, doubled = 0, None, 0
    for t, step in events:
        if open_now >= 2:
            doubled += (t - last) * open_now
        open_now, last = open_now + step, t
    total = sum(e - s for s, e in intervals)
    return doubled / total if total else 0.0


def copy_round(buf, payloads, used_bytes):
    """One round's blocks back to back from the buffer's start."""
    at = i = 0
    while at + BLOCK <= used_bytes:
        buf[at : at + BLOCK] = payloads[i % len(payloads)]
        at += -(-BLOCK // ALIGN) * ALIGN
        i += 1
    return i


def run_exchange(fn, pool, sharding, device, sources, used_rows, depth):
    """25 rounds through the program's pipeline; ``sources[k]`` is a host
    round (put at its submit) or a device round (taken as it is)."""
    rows = int(sources[0].shape[0])
    sizes = np.array([[used_rows]], dtype=np.int32)
    marks = [[0, 0] for _ in sources]
    submit_ms, drain_ms = [], []

    def submit(k):
        t0 = time.perf_counter_ns()
        piece = sources[k]
        if not isinstance(piece, jax.Array):
            piece = jax.device_put(piece, device)
        data = jax.make_array_from_single_device_arrays((rows, LANE), sharding, [piece])
        size_mat = jax.device_put(sizes, sharding)
        recv, recv_sizes = fn(data, size_mat)
        shard = recv.addressable_shards[0].data
        marks[k][0] = time.perf_counter_ns()
        with pool.allocating() if pool is not None else contextlib.nullcontext():
            _start_landing(shard)
        recv_sizes.copy_to_host_async()
        submit_ms.append((time.perf_counter_ns() - t0) / 1e6)
        return shard, recv_sizes

    def drain(k, ticket):
        t0 = time.perf_counter_ns()
        shard, recv_sizes = ticket
        np.asarray(recv_sizes)
        host = np.asarray(shard)
        marks[k][1] = time.perf_counter_ns()
        drain_ms.append((marks[k][1] - t0) / 1e6)
        return host

    t0 = time.perf_counter()
    received = RoundPipeline(depth, submit, drain, name="probe").run(len(sources))
    took = time.perf_counter() - t0
    row = {
        "exchange_s": round(took, 4), "cycle_ms": round(took / len(sources) * 1e3, 3),
        "submit_ms_p50": round(statistics.median(submit_ms), 3),
        "drain_ms_p50": round(statistics.median(drain_ms), 3),
        "d2h_ms_p50": round(statistics.median((e - s) / 1e6 for s, e in marks), 3),
        "d2h_overlap": round(overlap_share([tuple(m) for m in marks]), 3),
    }
    return row, received


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--rows", type=int, default=(64 << 20) // ALIGN)
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--orders", default="chain,early,copy")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "probe_round_puts.json"))
    args = ap.parse_args(argv)

    cluster = TpuShuffleCluster(TpuShuffleConf(num_executors=1), num_executors=1)
    device = cluster.mesh.devices.reshape(-1)[0]
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(cluster.mesh, P(cluster.conf.mesh_axis_name, None))
    fn = cluster._exchange_fn(args.rows)
    pool = cluster._landing()
    nbytes = args.rows * ALIGN
    # 1.63 GB over 25 rounds of 64 MiB: a round is 97% used
    used_bytes = nbytes * 97 // 100 // ALIGN * ALIGN
    rng = np.random.default_rng(57)
    payloads = [rng.integers(0, 256, BLOCK, dtype=np.uint8) for _ in range(16)]
    rounds = [np.zeros(nbytes, dtype=np.uint8) for _ in range(args.rounds)]
    for buf in rounds:  # held pages with a job's bytes in them
        copy_round(buf, payloads, used_bytes)
    views = [buf.view(np.int32).reshape(-1, LANE) for buf in rounds]
    used_rows = used_bytes // ALIGN

    result = {"platform": device.platform, "device_kind": device.device_kind,
              "rounds": args.rounds, "round_bytes": nbytes, "used_bytes": used_bytes,
              "depth": args.depth, "landing_pool": pool is not None, "orders": {}}
    for order in args.orders.split(","):
        jobs = []
        for job in range(args.jobs + 1):  # the first warms every executable and the pool
            row = {}
            sources = views
            if order in ("early", "copy"):
                holds, early = [], []
                t0 = time.perf_counter()
                for buf, view in zip(rounds, views):
                    copy_round(buf, payloads, used_bytes)
                    if order == "early":
                        t_put = time.perf_counter_ns()
                        early.append(jax.device_put(view, device))
                        holds.append((time.perf_counter_ns() - t_put) / 1e6)
                row["write_s"] = round(time.perf_counter() - t0, 4)
                if holds:
                    row["hold_ms_p50"] = round(statistics.median(holds), 3)
                    row["hold_ms_sum"] = round(sum(holds), 2)
                    sources = early
            if order != "copy":
                ex, received = run_exchange(fn, pool, sharding, device, sources, used_rows, args.depth)
                row.update(ex)
                row["equal"] = all(
                    np.array_equal(got[:used_rows], view[:used_rows])
                    for got, view in zip(received, views))
                del received, sources
            if job:
                jobs.append(row)
        med = {k: (statistics.median(j[k] for j in jobs) if k != "equal" else all(j[k] for j in jobs))
               for k in jobs[0]}
        result["orders"][order] = {"median": med, "jobs": jobs}
        print(order, json.dumps(med), flush=True)
    if pool is not None:
        result["pool"] = pool.stats()
    result["peak_bytes_in_use"] = (device.memory_stats() or {}).get("peak_bytes_in_use")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["orders"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

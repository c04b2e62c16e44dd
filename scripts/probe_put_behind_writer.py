#!/usr/bin/env python
"""Can a one-round job's staging go to the chip while it is still being
written?  The probe of PR 51 (ISSUE "Probe first"); kept because its
``store`` order is the chip's only whole-round check of the put behind the
writer — the benchmark's full comparison is of a store's first job, which
puts nothing early (ROADMAP queue 1 item 15, open).  Needs a device.

``gbt25k-devfetch-1chip`` copies a job's 5,000 blocks of about 625 KB into ONE
held 4 GiB round buffer (0.36 s) and only then, at ``seal``, puts the 3.13 GB
on the chip in 64 MiB pieces, two in flight (0.22 s): the link and the chip
idle through the write, the writer through the put.  This script runs the same
copies and the same puts (``SEAL_PUT_PIECE_BYTES`` pieces, at most
``SEAL_PUT_PIECES_IN_FLIGHT`` awaiting their transfer, ``_update_rows_fn``
into a zeroed device buffer that each update donates back: ``_put_round``'s
own) in these orders, job after job, and times each:

* ``copy``    — the copies alone, nothing put: what the writer costs alone;
* ``put``     — the puts alone, of a buffer already written: the link alone;
* ``serial``  — every copy, then every put (the parent's order);
* ``same``    — a piece is put by the copying thread as soon as the copies
  have passed its end, the piece the writer stands in and the wait for the
  transfers after the last copy: what the puts' calls hold the writer for;
* ``worker``  — the copying thread only hands a completed piece's offset to
  ONE worker thread, which puts it: whether ``device_put`` leaves the
  interpreter to the writer, and what the copies lose to the DMA's reads;
* ``store``   — the program's own path: ``HbmBlockStore`` with a device,
  ``map_writer`` → ``write_partition`` → ``commit`` → ``seal`` →
  ``block_until_ready`` → ``remove_shuffle`` under the cell's conf, with the
  store's ``early_put_*`` / ``seal_put_pieces`` counters where it has them
  (a store without them puts at the seal: the serial order through the
  program); its first job, into a buffer of fresh pages as a run's warm-up
  job writes, is reported apart (``store_first_job``).  Off the clock, every
  job's sealed round is read back from the device and compared byte for byte
  (``equal``) with this script's own buffer, which ``copy_blocks`` wrote with
  the same blocks at the same places: the benchmark's full comparison is of
  its warm-up job, a store's first, which puts nothing early.

For every job: ``write_s`` (first copy → last copy done, puts made meanwhile
included), ``seal_s`` (last copy → the last put's call returned), ``ready_s``
(→ the whole round readable on the device), ``total_s``, and for the orders
that put: the seconds the ``device_put`` + update calls held their thread
(``hold_s``), the seconds that thread waited for a transfer because two were
in flight (``wait_s``), and the pieces put before / after the last copy.

Blocks lie back to back from the buffer's start, each from a fresh 512 B row
(one region, as one executor's staging has); their lengths come from a fixed
stream, their bytes from ``bytes`` payloads made once and held.

Run on the chip:  ``python scripts/probe_put_behind_writer.py``; the table goes
to stdout and ``chiprun_out/probe_put_behind_writer.json``.  ``--capacity``,
``--blocks`` and ``--piece`` shrink it to prove here that the script works; a
time from this sandbox says nothing about the chip.
"""

import argparse
import gc
import json
import os
import queue
import statistics
import sys
import threading
import time
from collections import deque

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from sparkucx_tpu.config import TpuShuffleConf  # noqa: E402
from sparkucx_tpu.store import hbm_store  # noqa: E402

ALIGN = 512
LANE = ALIGN // 4
RECORD = 25_019  # a framed GroupByTest record of 25,000 value bytes
STORE_COUNTERS = ("early_put_pieces", "early_put_bytes", "seal_put_pieces", "early_put_dropped")


def block_layout(blocks, capacity):
    """``(offset, length)`` of every block: about 25 records each (the gate
    job's 5,000 pairs over 200 reducers), from a fixed stream; scaled down
    where ``capacity`` is too small to hold them."""
    lengths = np.random.default_rng(51).binomial(5000, 1 / 200, size=blocks).clip(1) * RECORD
    rows = -(-lengths // ALIGN)
    if int(rows.sum()) * ALIGN > capacity:
        lengths = np.maximum(lengths * (capacity // 2) // (int(rows.sum()) * ALIGN), 1)
        rows = -(-lengths // ALIGN)
    offsets = (np.cumsum(rows) - rows) * ALIGN
    return [(int(o), int(n)) for o, n in zip(offsets, lengths)]


class Putter:
    """``_put_round``'s update chain, a piece at a call: one owner at a time."""

    def __init__(self, buf, device, piece_bytes):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self.device = device
        self.payload = buf.view(np.int32).reshape(-1, LANE)
        self.piece_rows = piece_bytes // ALIGN
        self.update = hbm_store._update_rows_fn()
        self.round = jnp.zeros(self.payload.shape, dtype=jnp.int32, device=device)
        self.in_flight = deque()
        self.hold_ns = self.wait_ns = 0
        self.pieces = 0

    def put(self, at):
        """The piece that starts at row ``at``."""
        t0 = time.perf_counter_ns()
        if len(self.in_flight) == hbm_store.SEAL_PUT_PIECES_IN_FLIGHT:
            self.in_flight.popleft().block_until_ready()
        t1 = time.perf_counter_ns()
        piece = self._jax.device_put(self.payload[at : at + self.piece_rows], self.device)
        self.round = self.update(self.round, piece, np.int32(at))
        self.in_flight.append(piece)
        self.wait_ns += t1 - t0
        self.hold_ns += time.perf_counter_ns() - t1
        self.pieces += 1

    def finish(self):
        self.round.block_until_ready()
        self.in_flight.clear()
        self.round.delete()


def copy_blocks(buf, payloads, layout, passed=None):
    """A job's block copies, as ``close_partition`` makes them: slice
    assignment out of a ``bytes`` payload, one ``memcpy`` a block;
    ``passed(end)`` after each where the order puts behind the writer."""
    for (offset, length), payload in zip(layout, payloads):
        buf[offset : offset + length] = np.frombuffer(payload, dtype=np.uint8)
        if passed is not None:
            passed(offset + length)


def run_order(order, buf, payloads, layout, device, piece_bytes):
    """One job in ``order``; the row of its times."""
    used = layout[-1][0] + layout[-1][1]
    piece_rows = piece_bytes // ALIGN
    starts = list(range(0, -(-used // ALIGN), piece_rows))  # rows of the pieces a used byte reaches
    putter = Putter(buf, device, piece_bytes) if order != "copy" else None
    if putter is not None:
        putter.round.block_until_ready()  # the zero fill is the seal's in every order: off these clocks
    cursor = [0]  # index into ``starts`` of the next piece to put
    worker = handoff = None
    passed = None
    if order == "same":
        def passed(end):
            while cursor[0] < len(starts) and (starts[cursor[0]] + piece_rows) * ALIGN <= end:
                putter.put(starts[cursor[0]])
                cursor[0] += 1
    elif order == "worker":
        handoff = queue.SimpleQueue()

        def drain():
            while True:
                at = handoff.get()
                if at is None:
                    return
                putter.put(at)

        worker = threading.Thread(target=drain, name="probe-put-worker")
        worker.start()

        def passed(end):
            while cursor[0] < len(starts) and (starts[cursor[0]] + piece_rows) * ALIGN <= end:
                handoff.put(starts[cursor[0]])
                cursor[0] += 1

    t0 = time.perf_counter()
    if order != "put":
        copy_blocks(buf, payloads, layout, passed)
    t_written = time.perf_counter()
    early = cursor[0]
    if worker is not None:
        handoff.put(None)
        worker.join()
    if putter is not None:
        for at in starts[cursor[0]:]:
            putter.put(at)
    t_sealed = time.perf_counter()
    row = {"write_s": round(t_written - t0, 4), "seal_s": round(t_sealed - t_written, 4)}
    if putter is not None:
        putter.round.block_until_ready()
        t_ready = time.perf_counter()
        row.update(ready_s=round(t_ready - t_sealed, 4), total_s=round(t_ready - t0, 4),
                   hold_s=round(putter.hold_ns / 1e9, 4), wait_s=round(putter.wait_ns / 1e9, 4),
                   early_pieces=early, seal_pieces=putter.pieces - early)
        putter.finish()
    else:
        row["total_s"] = round(t_sealed - t0, 4)
    return row


def same_bytes(payload, buf, step=64 << 20):
    """Whether the device round ``payload`` holds ``buf``'s bytes, all of them."""
    host = np.asarray(payload).reshape(-1).view(np.uint8)
    return host.size == buf.size and all(
        np.array_equal(host[at : at + step], buf[at : at + step]) for at in range(0, buf.size, step))


def run_store(store, sid, payloads, buf):
    """One job through the program's own write and seal; ``buf`` holds what
    the sealed round has to hold."""
    before = store.write_stats()
    store.create_shuffle(sid, 1, len(payloads))
    t0 = time.perf_counter()
    writer = store.map_writer(sid, 0)
    for reduce_id, payload in enumerate(payloads):
        writer.write_partition(reduce_id, payload)
    writer.commit()
    t_written = time.perf_counter()
    [(payload, _)] = store.seal(sid)
    t_sealed = time.perf_counter()
    payload.block_until_ready()
    t_ready = time.perf_counter()
    equal = same_bytes(payload, buf)
    t_remove = time.perf_counter()
    del payload
    store.remove_shuffle(sid)
    after = store.write_stats()
    return {"write_s": round(t_written - t0, 4), "seal_s": round(t_sealed - t_written, 4),
            "ready_s": round(t_ready - t_sealed, 4), "total_s": round(t_ready - t0, 4),
            "remove_s": round(time.perf_counter() - t_remove, 4), "equal": equal,
            **{k: after[k] - before[k] for k in STORE_COUNTERS if k in after}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capacity", type=int, default=4 << 30, help="bytes of the staging buffer")
    ap.add_argument("--blocks", type=int, default=5000)
    ap.add_argument("--piece", type=int, default=hbm_store.SEAL_PUT_PIECE_BYTES, help="bytes of one put")
    ap.add_argument("--jobs", type=int, default=5)
    ap.add_argument("--orders", default="copy,put,serial,same,worker,store,serial,same,worker,copy")
    ap.add_argument("--out", default="chiprun_out/probe_put_behind_writer.json")
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    piece_before, hbm_store.SEAL_PUT_PIECE_BYTES = hbm_store.SEAL_PUT_PIECE_BYTES, args.piece  # the ``store`` order's
    layout = block_layout(args.blocks, args.capacity)
    total = sum(length for _, length in layout)
    report = {"capacity": args.capacity, "blocks": len(layout), "job_bytes": total, "piece_bytes": args.piece,
              "pieces_reached": -(-(layout[-1][0] + layout[-1][1]) // args.piece),
              "device": f"{device.platform} {device.device_kind}", "cpus": os.cpu_count(), "runs": []}
    # held and touched before the first job, as a job's records are
    payloads = [bytes([1 + i % 255]) * length for i, (_, length) in enumerate(layout)]
    buf = np.zeros(args.capacity, dtype=np.uint8)
    copy_blocks(buf, payloads, layout)  # the buffer's pages are held, as the free list's are
    run_order("serial", buf, payloads, layout, device, args.piece)  # compiles the update, off the clock
    store = hbm_store.HbmBlockStore(
        TpuShuffleConf(staging_capacity_per_executor=args.capacity, block_alignment=ALIGN), device=device)
    sid = 0
    try:
        if "store" in args.orders.split(","):
            # the store's buffer allocated and first touched, as a run's warm-up job does: a row of its own
            report["store_first_job"] = run_store(store, sid, payloads, buf)
            print("store   first  " + " ".join(f"{k}={v}" for k, v in report["store_first_job"].items()), flush=True)
            sid += 1
        for order in args.orders.split(","):
            gc.collect()
            rows = []
            for _ in range(args.jobs):
                if order == "store":
                    rows.append(run_store(store, sid, payloads, buf))
                    sid += 1
                else:
                    rows.append(run_order(order, buf, payloads, layout, device, args.piece))
            medians = {k: round(statistics.median(row[k] for row in rows), 4) for k in rows[0] if k.endswith("_s")}
            report["runs"].append({"order": order, "median": medians, "jobs": rows})
            print(f"{order:7s} median " + " ".join(f"{k}={v}" for k, v in medians.items()), flush=True)
            for row in rows:
                print(f"{'':7s} " + " ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    finally:
        store.close()
        hbm_store.SEAL_PUT_PIECE_BYTES = piece_before
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "runs"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

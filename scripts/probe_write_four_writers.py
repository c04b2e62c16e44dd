#!/usr/bin/env python
"""What do four map tasks that write ONE store at once cost, through the
program's own path?  The probe of PR 53 (ISSUE "The probe first"); kept
because it prices the four-writer path — ``MapWriter.close_partition`` over
the store's ``take_extent`` / ``record_extent`` (``store/writer.py``, PR 56)
and the puts behind it, ROADMAP queue 1 item 5(c), open — from either side
of a change to it.  Needs a device for the orders that put.

``ts10gb-sortedjobs-4tasks-1chip`` has four slot threads write one store:
19 map tasks of 75 blocks of 1.79 MB, 2.55 GB a job into one held 4 GiB
round, 39 pieces of 64 MiB put behind the writers.  At the parent a block's
allocate + copy + record run under the store's one lock, so the four copies
run one after another (0.506 s a job where one writer takes 0.346).  This
script runs that job, job after job, and times each:

* ``store<N>`` / ``store<N>+put`` / ``store-small`` — ``HbmBlockStore``
  (with a device for ``+put``; ``store-small`` is the 1k job: 6,300 blocks
  of 1–3 records of 1,019 B into the default 64 MiB), N threads taking map
  tasks off one list, ``map_writer`` → ``write_partition`` × 75 →
  ``commit``, then ``seal`` → ready → ``remove_shuffle``; the store's
  counters where it has them, so the same script reads the parent and the
  change.  Off the clock every block of a ``+put`` job is read back from the
  sealed round on the device and compared with its payload (``equal``).

For every job: ``write_s`` (first block → the last thread's last block),
``seal_s``, ``ready_s``, ``total_s``; summed over the writers ``copy_s``
(inside the copies), ``lock_wait_s`` (waiting for the store's lock) and
``inflight_wait_s``; the pieces put before the seal and at it.

As run before the change was written (calls 1–3 of PR 53) the script also
had the same copies hand-written into a held buffer — ``lock<N>`` (allocate
+ copy + record under one lock), ``split<N>`` (the copy outside it),
``...+put`` beside the chain of 64 MiB puts, ``small-*`` and a ``sweep`` of
block sizes from 2 KiB to 512 KiB.  Their numbers are in ``PERF.md``
section 6 (PR 53); the arms were taken out after the review, since the
store's own path now does what they modelled.

Run on the chip:  ``python scripts/probe_write_four_writers.py``; the table
goes to stdout and ``chiprun_out/probe_write_four_writers.json``.
``--capacity``, ``--tasks``, ``--blocks``, ``--block-bytes``, ``--small`` and
``--piece`` shrink it to prove here that the script works; a time from this
sandbox says nothing about the chip.
"""

import argparse
import gc
import json
import os
import re
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
STORE_COUNTERS = (
    "staged_blocks", "copy_ns", "lock_wait_ns", "inflight_wait_ns", "unlocked_copy_blocks",
    "unlocked_copy_bytes", "early_put_pieces", "seal_put_pieces", "early_put_dropped",
)
DEFAULT_ORDERS = "store-small,store1,store4,store1+put,store4+put,store4+put,store1+put,store4,store1,store-small"


def make_tasks(tasks, blocks, block_bytes, seed=53):
    """``tasks`` lists of ``bytes`` payloads: a map task's blocks in reduce
    order, lengths within 2% of ``block_bytes`` from a fixed stream."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(block_bytes - block_bytes // 50, block_bytes + block_bytes // 50 + 1,
                           size=(tasks, blocks))
    return [[bytes([1 + (t * blocks + r) % 255]) * int(n) for r, n in enumerate(row)]
            for t, row in enumerate(lengths)]


def make_small_tasks(tasks, blocks, seed=53):
    """The 1k job's blocks: 1–3 framed records of 1,019 B."""
    counts = np.random.default_rng(seed).choice([1, 2, 3], p=[0.55, 0.33, 0.12], size=(tasks, blocks))
    return [[bytes([1 + r % 255]) * (int(c) * 1019) for r, c in enumerate(row)] for row in counts]


def threads_of(name):
    """The slot threads an order's name asks for: its trailing number, else 1."""
    digits = re.search(r"\d+$", name)
    return int(digits.group()) if digits else 1


def run_threads(threads, tasks, work):
    """``threads`` slot threads take task indices off one list and call
    ``work(index)``."""
    todo = deque(range(len(tasks)))
    errors = []

    def slot():
        try:
            while True:
                try:
                    index = todo.popleft()
                except IndexError:
                    return
                work(index)
        except BaseException as e:  # noqa: BLE001 — a probe: shown, and the job fails
            errors.append(e)

    if threads == 1:
        slot()
    else:
        pool = [threading.Thread(target=slot, name=f"probe-slot-{i}") for i in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join()
    if errors:
        raise errors[0]


def run_store(store, sid, threads, tasks):
    """One job through the program's own write and seal."""
    blocks = len(tasks[0])
    before = store.write_stats()
    store.create_shuffle(sid, len(tasks), blocks)

    def work(index):
        writer = store.map_writer(sid, index)
        for reduce_id, payload in enumerate(tasks[index]):
            writer.write_partition(reduce_id, payload)
        writer.commit()

    t0 = time.perf_counter()
    run_threads(threads, tasks, work)
    t_written = time.perf_counter()
    [(payload, _)] = store.seal(sid)
    t_sealed = time.perf_counter()
    if store.device is not None:
        payload.block_until_ready()
    t_ready = time.perf_counter()
    after = store.write_stats()
    row = {"write_s": round(t_written - t0, 4), "seal_s": round(t_sealed - t_written, 4),
           "ready_s": round(t_ready - t_sealed, 4), "total_s": round(t_ready - t0, 4)}
    if store.device is not None:  # off the clock: every block where the table says, on the chip
        host = np.asarray(payload).reshape(-1).view(np.uint8)
        row["equal"] = all(
            host[at : at + len(p)].tobytes() == p
            for m, task in enumerate(tasks) for r, p in enumerate(task)
            for at in (store.block_offset(sid, m, r),))
        del host
    del payload
    store.remove_shuffle(sid)
    row.update({k: after[k] - before[k] for k in STORE_COUNTERS if k in after})
    for k in ("copy_ns", "lock_wait_ns", "inflight_wait_ns"):
        if k in row:
            row[k[:-3] + "_s"] = round(row.pop(k) / 1e9, 4)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capacity", type=int, default=4 << 30, help="bytes of the staging buffer")
    ap.add_argument("--tasks", type=int, default=19)
    ap.add_argument("--blocks", type=int, default=75, help="blocks a map task")
    ap.add_argument("--block-bytes", type=int, default=1_789_500)
    ap.add_argument("--small", default="100x63", help="the small job: tasks x blocks")
    ap.add_argument("--piece", type=int, default=hbm_store.SEAL_PUT_PIECE_BYTES, help="bytes of one put")
    ap.add_argument("--jobs", type=int, default=5)
    ap.add_argument("--orders", default=DEFAULT_ORDERS)
    ap.add_argument("--out", default="chiprun_out/probe_write_four_writers.json")
    args = ap.parse_args(argv)
    orders = args.orders.split(",")

    device = None
    if any("+put" in o for o in orders):
        import jax

        device = jax.devices()[0]
    piece_before, hbm_store.SEAL_PUT_PIECE_BYTES = hbm_store.SEAL_PUT_PIECE_BYTES, args.piece  # the stores'
    tasks = make_tasks(args.tasks, args.blocks, args.block_bytes)
    small = make_small_tasks(*(int(x) for x in args.small.split("x")))
    report = {"capacity": args.capacity, "tasks": len(tasks), "blocks": sum(len(t) for t in tasks),
              "job_bytes": sum(len(p) for t in tasks for p in t), "small_blocks": sum(len(t) for t in small),
              "small_bytes": sum(len(p) for t in small for p in t), "piece_bytes": args.piece,
              "device": f"{device.platform} {device.device_kind}" if device is not None else None,
              "cpus": os.cpu_count(), "runs": []}
    conf = TpuShuffleConf(staging_capacity_per_executor=args.capacity, block_alignment=ALIGN)
    stores = {}  # (with a device, small) -> store
    sid = 0
    try:
        for order in orders:
            gc.collect()
            name, _, puts = order.partition("+")
            job = small if "small" in name else tasks
            rows = []
            key = (bool(puts), job is small)
            if key not in stores:  # its first job — fresh pages, the update's compile — is off the clock
                small_conf = TpuShuffleConf(block_alignment=ALIGN)  # the 1k cell's: the default 64 MiB
                stores[key] = hbm_store.HbmBlockStore(
                    small_conf if job is small else conf, device=device if puts else None)
                run_store(stores[key], sid, 1, job)
                sid += 1
            for _ in range(args.jobs):
                rows.append(run_store(stores[key], sid, threads_of(name), job))
                sid += 1
            medians = {k: round(statistics.median(row[k] for row in rows), 5) for k in rows[0] if k.endswith("_s")}
            report["runs"].append({"order": order, "median": medians, "jobs": rows})
            print(f"{order:12s} median " + " ".join(f"{k}={v}" for k, v in medians.items()), flush=True)
            for row in rows:
                print(f"{'':12s} " + " ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    finally:
        for store in stores.values():
            store.close()
        hbm_store.SEAL_PUT_PIECE_BYTES = piece_before
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "runs"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

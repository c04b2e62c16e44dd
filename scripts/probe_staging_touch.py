#!/usr/bin/env python
"""What does a one-round job's staging buffer cost when it is new every job?
The probe of PR 47 (ISSUE step (b)).  Host only: no device is touched.

``gbt25k-devfetch-1chip`` stages a job's 5,000 blocks of about 625 KB into ONE
4 GiB round buffer.  Over ``max_host_pool_bytes`` that buffer was ``np.zeros``
at a job's first write and unmapped at ``remove_shuffle``, so every job's
3.13 GB of block copies were the first touch of their pages.  This script
times, off the benchmark and job after job, the same copies into:

* ``fresh``  — a new ``np.zeros(capacity)`` a job, dropped after it (the
  parent's path): seconds of the copies, and of the release (the ``munmap``);
* ``kept``   — ONE buffer for every job, each block's bytes set back to zero
  after the job (what ``_recycle_rounds`` does to a buffer the free list
  keeps): seconds of the copies, and of the zeroing;
* ``store``  — the program's own path, ``HbmBlockStore.map_writer`` →
  ``write_partition`` → ``commit`` → ``remove_shuffle`` under the cell's conf
  (4 GiB staging, the default 2 GiB ``max_host_pool_bytes``): seconds of the
  writes and of the removal, and the store's ``pool_*`` counters after each
  job — the parent reads ``pool_misses`` 1 a job there, a store whose free
  list keeps its own staging size reads ``pool_hits`` 1 from the second job.

Blocks lie back to back from the buffer's start, each from a fresh 512 B row
(one region, as one executor's staging has); their lengths come from a fixed
stream, their bytes from ``bytes`` payloads made once and held (a 3.13 GB
working set read and written once a job, as the job's records are).

Run on the chip's host:  ``python scripts/probe_staging_touch.py``; the table
goes to stdout and ``chiprun_out/probe_staging_touch.json``.  ``--capacity``
and ``--blocks`` shrink it to prove here that the script works; a time from
this sandbox says nothing about the chip's host.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from sparkucx_tpu.config import TpuShuffleConf  # noqa: E402
from sparkucx_tpu.store.hbm_store import HbmBlockStore, _mem_available_bytes  # noqa: E402

ALIGN = 512
RECORD = 25_019  # a framed GroupByTest record of 25,000 value bytes
POOL_COUNTERS = ("pool_hits", "pool_misses", "pool_dropped_busy", "pool_kept_over_budget", "pool_held_bytes")


def _s(t0):
    return round(time.perf_counter() - t0, 4)


def _rss_gb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) * 1024 / 1e9, 3)
    except OSError:
        pass
    return None


def _thp():
    try:
        with open("/sys/kernel/mm/transparent_hugepage/enabled") as f:
            return f.read().strip()
    except OSError:
        return None


def block_layout(blocks, capacity):
    """``(offset, length)`` of every block: about 25 records each (the gate
    job's 5,000 pairs over 200 reducers), from a fixed stream; scaled down
    where ``capacity`` is too small to hold them."""
    lengths = np.random.default_rng(47).binomial(5000, 1 / 200, size=blocks).clip(1) * RECORD
    rows = -(-lengths // ALIGN)
    if int(rows.sum()) * ALIGN > capacity:
        lengths = np.maximum(lengths * (capacity // 2) // (int(rows.sum()) * ALIGN), 1)
        rows = -(-lengths // ALIGN)
    offsets = (np.cumsum(rows) - rows) * ALIGN
    return [(int(o), int(n)) for o, n in zip(offsets, lengths)]


def copy_job(buf, payloads, layout):
    """A job's block copies, as ``close_partition`` makes them: slice
    assignment out of a ``bytes`` payload, one ``memcpy`` a block."""
    for (offset, length), payload in zip(layout, payloads):
        buf[offset : offset + length] = np.frombuffer(payload, dtype=np.uint8)


def probe_fresh(capacity, payloads, layout, jobs):
    rows = []
    for _ in range(jobs):
        t0 = time.perf_counter()
        buf = np.zeros(capacity, dtype=np.uint8)
        alloc_s = _s(t0)
        t0 = time.perf_counter()
        copy_job(buf, payloads, layout)
        write_s = _s(t0)
        t0 = time.perf_counter()
        del buf
        rows.append({"alloc_s": alloc_s, "write_s": write_s, "release_s": _s(t0), "rss_gb": _rss_gb()})
    return rows


def probe_kept(capacity, payloads, layout, jobs):
    rows = []
    buf = np.zeros(capacity, dtype=np.uint8)
    used = layout[-1][0] + layout[-1][1]
    for _ in range(jobs):
        t0 = time.perf_counter()
        copy_job(buf, payloads, layout)
        write_s = _s(t0)
        t0 = time.perf_counter()
        buf[:used] = 0  # one region: its used prefix, as ``_recycle_rounds`` zeroes it
        rows.append({"write_s": write_s, "zero_s": _s(t0), "rss_gb": _rss_gb()})
    return rows


def probe_store(capacity, payloads, layout, jobs):
    """The program's own write path and removal, one map task of all the
    blocks a job (the store neither knows nor cares how many tasks wrote)."""
    import jax  # noqa: F401  (a removal's first look at a device payload imports it: not on the clock)

    store = HbmBlockStore(TpuShuffleConf(staging_capacity_per_executor=capacity, block_alignment=ALIGN))
    rows = []
    try:
        for sid in range(jobs):
            store.create_shuffle(sid, 1, len(layout))
            t0 = time.perf_counter()
            writer = store.map_writer(sid, 0)
            for reduce_id, payload in enumerate(payloads):
                writer.write_partition(reduce_id, payload)
            writer.commit()
            write_s = _s(t0)
            t0 = time.perf_counter()
            store.remove_shuffle(sid)
            stats = store.write_stats()
            rows.append({"write_s": write_s, "remove_s": _s(t0), "rss_gb": _rss_gb(),
                         **{k: stats[k] for k in POOL_COUNTERS if k in stats}})
    finally:
        store.close()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capacity", type=int, default=4 << 30, help="bytes of the staging buffer")
    ap.add_argument("--blocks", type=int, default=5000)
    ap.add_argument("--jobs", type=int, default=5)
    ap.add_argument("--variants", default="fresh,kept,store,fresh")
    ap.add_argument("--out", default="chiprun_out/probe_staging_touch.json")
    args = ap.parse_args(argv)

    layout = block_layout(args.blocks, args.capacity)
    total = sum(length for _, length in layout)
    report = {"capacity": args.capacity, "blocks": len(layout), "job_bytes": total,
              "mem_available_gb": round((_mem_available_bytes() or 0) / 1e9, 3),
              "transparent_hugepage": _thp(), "cpus": os.cpu_count(), "runs": []}
    # held and touched before the first job, as a job's records are
    payloads = [bytes([1 + i % 255]) * length for i, (_, length) in enumerate(layout)]
    probes = {"fresh": probe_fresh, "kept": probe_kept, "store": probe_store}
    for name in args.variants.split(","):
        gc.collect()
        rows = probes[name](args.capacity, payloads, layout, args.jobs)
        report["runs"].append({"variant": name, "jobs": rows})
        print(f"{name:6s} " + "  ".join(
            "/".join(f"{row[k]}" for k in row if k.endswith("_s")) for row in rows), flush=True)
        print(f"{'':6s} " + "  ".join(
            ",".join(f"{k}={row[k]}" for k in row if not k.endswith("_s")) for row in rows), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "runs"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

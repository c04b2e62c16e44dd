#!/usr/bin/env python
"""What does the host side of ONE sub-exchange of the degraded re-run cost?
The probe of PR 49, written and run before the change.  Needs two devices.

``gbt25k-execloss-4chip`` re-runs nine staging rounds as 24 sub-exchanges on a
two-chip sub-mesh, 43 ms each (ledger, PR 48).  At that cell's geometry — four
executors, two survivors on the sub-mesh, 64 MiB of staging an executor, a
16 MiB peer region of 32,768 rows of 512 B — a sub-exchange sends, from each
of two senders, the 32 MiB of its sealed round that lie in the consumers'
wave: rows ``[lo, hi)``, contiguous.  This script times that send three ways,
``--repeats`` in a row each, seconds each:

* ``today``   — what ``_recover_and_rerun`` does: zero a 64 MiB ``host``
  array; for each sender zero a 32 MiB ``block``, copy the wave into it, copy
  that into ``host``; ONE ``jax.device_put(host, sharding)`` over both chips.
  Columns: ``assemble_s`` (the zeroing and the copies), ``put_s`` (until the
  call returns), ``ready_s`` (from the call's return until the bytes are on
  the chips);
* ``pooled``  — the same with the three arrays allocated from a
  ``native.LandingPool``, as the recovery's are on a chip (pages the process
  holds already); left out where the pool cannot be made;
* ``views``   — two ``jax.device_put(src[lo:hi], device)``, a device each, of
  VIEWS of the sealed rounds, then ``jax.make_array_from_single_device_arrays``
  (what the full mesh's ``_submit`` does): ``assemble_s`` is the slicing.

The sealed rounds are ``--rounds`` distinct 64 MiB arrays a sender, written
once and held, taken in turn (a job's rounds are read cold: 1.15 GB of them).

Run on a host with chips:  ``python scripts/probe_recover_assembly.py``; the
table goes to stdout and ``chiprun_out/probe_recover_assembly.json``.
``--slot-rows`` shrinks it to prove here that the script works; a time from
this sandbox says nothing about the chip's host.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from sparkucx_tpu.native import LandingPool  # noqa: E402

LANE = 128  # a 512 B row of int32


def _s(t0):
    return round(time.perf_counter() - t0, 5)


def sealed_rounds(rounds, senders, send_rows):
    """``rounds`` sealed rounds a sender: ``(send_rows, LANE)`` int32, every
    page written (a sealed round's are: the store zeroed or filled them)."""
    out = []
    for r in range(rounds):
        out.append([np.full((send_rows, LANE), 1 + r * senders + p, dtype=np.int32) for p in range(senders)])
    return out


def probe_today(srcs, lo, hi, m, slot_rows, devices, sharding, allocating):
    bucketed = m * slot_rows
    t0 = time.perf_counter()
    with allocating():
        host = np.zeros((m * bucketed, LANE), dtype=np.int32)
        for p, src in enumerate(srcs):
            block = np.zeros((m * slot_rows, LANE), dtype=np.int32)
            block[: hi - lo] = src[lo:hi]
            host[p * bucketed : (p + 1) * bucketed] = block
    assemble_s = _s(t0)
    t0 = time.perf_counter()
    data = jax.device_put(host, sharding)
    put_s = _s(t0)
    t0 = time.perf_counter()
    jax.block_until_ready(data)
    return {"assemble_s": assemble_s, "put_s": put_s, "ready_s": _s(t0)}, data


def probe_views(srcs, lo, hi, m, slot_rows, devices, sharding, allocating):
    bucketed = m * slot_rows
    t0 = time.perf_counter()
    pieces = [src[lo:hi] for src in srcs]
    assemble_s = _s(t0)
    t0 = time.perf_counter()
    placed = [jax.device_put(piece, dev) for piece, dev in zip(pieces, devices)]
    data = jax.make_array_from_single_device_arrays((m * bucketed, LANE), sharding, placed)
    put_s = _s(t0)
    t0 = time.perf_counter()
    jax.block_until_ready(data)
    return {"assemble_s": assemble_s, "put_s": put_s, "ready_s": _s(t0)}, data


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slot-rows", type=int, default=32768, help="rows of one peer region (16 MiB at 512 B)")
    ap.add_argument("--executors", type=int, default=4)
    ap.add_argument("--submesh", type=int, default=2, help="devices of the shrunk mesh")
    ap.add_argument("--rounds", type=int, default=9)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--variants", default="today,pooled,views,today,pooled,views")
    ap.add_argument("--out", default="chiprun_out/probe_recover_assembly.json")
    args = ap.parse_args(argv)

    m, n, slot_rows = args.submesh, args.executors, args.slot_rows
    devices = jax.devices()[:m]
    if len(devices) < m:
        print(f"needs {m} devices, found {len(jax.devices())}", file=sys.stderr)
        return 4
    sharding = NamedSharding(Mesh(np.array(devices), ("ex",)), P("ex", None))
    rounds = sealed_rounds(args.rounds, m, n * slot_rows)
    piece_bytes = m * slot_rows * LANE * 4
    pool = LandingPool.create(2 << 30, 1 << 20)
    allocators = {"today": contextlib.nullcontext, "views": contextlib.nullcontext}
    if pool is not None:
        allocators["pooled"] = pool.allocating
    probes = {"today": probe_today, "pooled": probe_today, "views": probe_views}
    report = {"platform": devices[0].platform, "device_kind": devices[0].device_kind, "devices": m,
              "piece_bytes": piece_bytes, "host_bytes": m * piece_bytes, "rounds": args.rounds,
              "pool": pool is not None, "cpus": os.cpu_count(), "runs": []}
    # the runtime's first transfer sets its own staging up: off the clock
    jax.block_until_ready(jax.device_put(np.zeros((m * m * slot_rows, LANE), dtype=np.int32), sharding))
    turn = 0
    for name in args.variants.split(","):
        if name not in allocators:
            continue
        gc.collect()
        rows = []
        for _ in range(args.repeats):
            wave = turn % (n // m)  # the consumers' wave: which half of the round is sent
            lo, hi = wave * m * slot_rows, (wave + 1) * m * slot_rows
            row, data = probes[name](rounds[turn % args.rounds], lo, hi, m, slot_rows, devices, sharding,
                                     allocators[name])
            del data
            rows.append(row)
            turn += 1
        report["runs"].append({"variant": name, "subexchanges": rows})
        print(f"{name:7s} assemble/put/ready  " + "  ".join(
            "/".join(f"{row[k]:.4f}" for k in ("assemble_s", "put_s", "ready_s")) for row in rows), flush=True)
    if pool is not None:
        report["pool_stats"] = pool.stats()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "runs"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

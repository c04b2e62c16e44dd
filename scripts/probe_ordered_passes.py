#!/usr/bin/env python
"""What does each pass of the ordering executable cost, and what do four
D2Hs in flight sustain?  The probe of PR 55 (ISSUE "Price it first"); kept
because ``--d2h`` / ``--pieces`` price ROADMAP queue 1 item 2(a), which is
open: the latency of one D2H stream under a reduce task's serial chain.
Needs the chip for its times.

``ts10gb-sortedjobs-4tasks-1chip`` runs ``jit_ordered_records`` once a reduce
task, 75 times a job, at one shape: one gathered segment ``int32[66950, 128]``
(342,784 record places of 25 lanes in 2,678 slots of 128), a ``(2, 32)`` block
table of 19 blocks, a 10-byte key.  The ledger's trace of PR 54 has the
executable at 5.5 ms a task of which the sort is 0.78.

(a) ``--forms``: whole forms of the executable, each compiled as ONE jitted
function and run ``--tasks`` times inside ONE ``jax.profiler`` trace of its
own; the table is every device operation of that trace, ms a task — the
operation's own time on the chip, no dispatch and no ``block_until_ready`` in
it.  The forms:

* ``parent``   — PR 54's body, kept here word for word: validity as a
  (places, blocks) compare, ``rows[:, i]`` a key lane, the padding zeroed by
  a select over the 2-D array, then the reshape to the host's form;
* ``keys``     — the key lanes taken once (``ops.sort.key_order``) and the
  validity a compare a place; the parent's select and reshape;
* ``change``   — the package's ``ordered_records`` as it stands;
* ``bare``     — ``change`` with the padding left as it falls (no zeroing at
  all): what the zeroing costs is ``change`` less this.  Not a candidate.
* ``slices``   — ``change`` with the key lanes taken from the slots as
  lane-strided slices, ``(slots, 3200)[:, c::25]``, and not from the padded
  records.

(b) ``--d2h``: 75 device arrays of the sorted form (``int32[8569600]``,
34.3 MB), made before any clock starts, brought to the host by 1, 2, 4 and 8
threads that each ``copy_to_host_async()`` inside ``LandingPool.allocating()``
and wait in ``np.asarray`` — ``ordered_to_host``'s own calls, with no chip
work in front of them — three jobs a depth (the first fills the pool); GB/s
of the 75 together and ms a transfer.  ``--pieces 1,2,4``: the same bytes as
2 and 4 device arrays a transfer, started together — what a task's D2H would
take as several streams (each lands in a block of its own: no program path
does this; the figure is for whoever joins the landings).

``--equal``: every form's output at this shape against NumPy's stable sort of
the covered places by their ten key bytes, padding last and zero, with the
uncovered places of the segment poisoned; exit 1 where one differs.

Run on the chip:  ``python scripts/probe_ordered_passes.py --equal --d2h``;
the tables go to stdout and ``chiprun_out/probe_ordered_passes.json``.
``--slots`` and ``--tasks`` shrink it to prove here that the script works; a
time from this sandbox says nothing about the chip.
"""

import argparse
import contextlib
import glob
import json
import os
import queue
import statistics
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.device_trace import MODULES_LINE, OPS_LINE, short_op_name  # noqa: E402
from sparkucx_tpu.native import LandingPool  # noqa: E402
from sparkucx_tpu.ops.exchange import gather_rows  # noqa: E402
from sparkucx_tpu.ops.sort import KEY_MAX, _byteswap32, key_lanes_of, key_order  # noqa: E402
from sparkucx_tpu.transport.tpu import ordered_records  # noqa: E402

LANE, RECORD_LANES, KEY_BYTES, SLOT_ROWS, SLOT_RECORDS, BLOCKS = 128, 25, 10, 25, 128, 19


# -- the forms ---------------------------------------------------------------

def _parent_sort_rows(rows, key_lanes, valid, key_bytes):
    """``ops.sort.sort_rows`` as PR 54 had it."""
    n = rows.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    lanes = [jax.lax.bitcast_convert_type(rows[:, i], jnp.uint32) for i in range(key_lanes)]
    lanes = [_byteswap32(lane) for lane in lanes]
    tail = key_bytes - 4 * (key_lanes - 1)
    lanes[-1] = lanes[-1] & jnp.uint32((0xFFFFFFFF << (8 * (4 - tail))) & 0xFFFFFFFF)
    lanes = [jnp.where(valid, lane, KEY_MAX) for lane in lanes]
    order = jax.lax.sort((*lanes, idx), num_keys=len(lanes) + 1, is_stable=False)[-1]
    count = valid.sum(dtype=jnp.int32)
    return jnp.where((idx < count)[:, None], gather_rows(rows, order), jnp.zeros((), rows.dtype))


def parent(table, segment):
    """``transport.tpu.ordered_records`` as PR 54 had it (one segment, flat)."""
    records = segment.reshape(-1, RECORD_LANES)
    place = jnp.arange(records.shape[0], dtype=jnp.int32)[:, None]
    first, count = table[0][None, :], table[1][None, :]
    valid = ((place >= first) & (place < first + count)).any(axis=1)
    return _parent_sort_rows(records, key_lanes_of(KEY_BYTES), valid, KEY_BYTES).reshape(-1)


def _covered(table, records):
    first, count = table[0][None, :], table[1][None, :]
    base = (jnp.arange(records.shape[0] // SLOT_RECORDS, dtype=jnp.int32) * SLOT_RECORDS)[:, None]
    covered = jnp.where(first <= base, jnp.clip(first + count - base, 0, SLOT_RECORDS), 0).max(axis=1)
    return (jnp.arange(SLOT_RECORDS, dtype=jnp.int32)[None, :] < covered[:, None]).reshape(-1)


def _gathered(table, segment, lanes=None):
    """(the records in key order as ``(places, 25)``, how many are data): the
    key lanes taken once, or ``lanes``; validity a compare a place."""
    records = segment.reshape(-1, RECORD_LANES)
    keys = records[:, : key_lanes_of(KEY_BYTES)].T if lanes is None else lanes
    order, n = key_order(keys, _covered(table, records), KEY_BYTES)
    return gather_rows(records, order), n


def _zeroed_flat(gathered, n):
    out = gathered.reshape(-1)
    return jnp.where(jnp.arange(out.size, dtype=jnp.int32) < n * RECORD_LANES, out, 0)


def keys(table, segment):
    gathered, n = _gathered(table, segment)
    idx = jnp.arange(gathered.shape[0], dtype=jnp.int32)
    return jnp.where((idx < n)[:, None], gathered, 0).reshape(-1)


def bare(table, segment):
    return _gathered(table, segment)[0].reshape(-1)


def slices(table, segment):
    """``change`` with the key lanes as lane-strided slices of the slots
    (``(slots, 3200)[:, c::25]``): never a pass over the padded form."""
    slots = segment.reshape(-1, SLOT_ROWS * LANE)
    lanes = [jax.lax.slice(slots, (0, c), slots.shape, (1, RECORD_LANES)).reshape(-1)
             for c in range(key_lanes_of(KEY_BYTES))]
    return _zeroed_flat(*_gathered(table, segment, lanes))


def change(table, segment):
    return ordered_records.__wrapped__(table, segment, record_lanes=RECORD_LANES, key_bytes=KEY_BYTES, flat=True)


FORMS = {"parent": parent, "keys": keys, "change": change, "bare": bare, "slices": slices}
ZEROES_PADDING = {"parent", "keys", "change", "slices"}


# -- a task's inputs and its oracle -----------------------------------------

def make_task(slots, seed):
    """(table, segment, places): 19 blocks of unequal record counts, each
    from a slot boundary, filling ``slots`` slots but for under a slot a
    block; every place no block covers poisoned (all ones, or a key of zeros
    that would sort before every real key)."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, slots), size=BLOCKS - 1, replace=False))
    block_slots = np.diff(np.concatenate([[0], cuts, [slots]]))
    counts = block_slots * SLOT_RECORDS - rng.integers(0, SLOT_RECORDS, size=BLOCKS)
    firsts = (np.cumsum(block_slots) - block_slots) * SLOT_RECORDS
    places = slots * SLOT_RECORDS
    records = rng.integers(0, 1 << 32, size=(places, RECORD_LANES), dtype=np.uint32)
    records[:, :3] |= 0x01010101  # no real key of zeros: the poison's would come first
    covered = np.zeros(places, dtype=bool)
    for f, c in zip(firsts, counts):
        covered[f:f + c] = True
    poison = np.flatnonzero(~covered)
    records[poison[0::2]] = 0xFFFFFFFF
    records[poison[1::2]] = 0
    table = np.zeros((2, 32), dtype=np.int32)
    table[0, :BLOCKS], table[1, :BLOCKS] = firsts, counts
    segment = records.view(np.int32).reshape(slots * SLOT_ROWS, LANE)
    return table, segment, (records, covered)


def oracle(records, covered):
    """The covered records in the order of their ten key bytes, stable."""
    rows = records[covered]
    key = rows[:, :3].astype("<u4").view(np.uint8).reshape(len(rows), 12)[:, :KEY_BYTES]
    return rows[np.lexsort(key.T[::-1])]


def equal(name, out, records, covered):
    want = oracle(records, covered)
    got = np.asarray(out).view(np.uint32).reshape(-1, RECORD_LANES)
    data = np.array_equal(got[: len(want)], want)
    padding = name not in ZEROES_PADDING or not got[len(want):].any()
    return bool(data and padding)


# -- (a) the passes inside one trace ----------------------------------------

def device_ops(trace_dir):
    """{operation: seconds} and {module: seconds} of every device plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    ops, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                dst, name = (ops, short_op_name(ev.name)) if line.name == OPS_LINE else (modules, ev.name)
                dst[name] = dst.get(name, 0.0) + ev.duration_ns / 1e9
    return ops, modules


def trace_form(name, fn, table, segment, tasks):
    dev_segment = jax.device_put(segment)
    t0 = time.perf_counter()
    out = fn(table, dev_segment)
    out.block_until_ready()
    compile_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="probe_ordered_") as trace_dir:
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        outs = [fn(table, dev_segment) for _ in range(tasks)]
        jax.block_until_ready(outs)
        wall_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        ops, modules = device_ops(trace_dir)
    row = {
        "form": name, "tasks": tasks, "first_call_s": round(compile_s, 2),
        "wall_ms_a_task": round(wall_s / tasks * 1e3, 4),
        "device_ms_a_task": round(sum(ops.values()) / tasks * 1e3, 4),
        "modules_ms_a_task": {k: round(v / tasks * 1e3, 4) for k, v in modules.items()},
        "ops_ms_a_task": {k: round(v / tasks * 1e3, 4)
                          for k, v in sorted(ops.items(), key=lambda kv: -kv[1]) if v / tasks >= 1e-6},
    }
    return row, out


# -- (b) the D2Hs in flight --------------------------------------------------

def d2h_in_flight(places, depths, arrays, jobs, pieces=1):
    """``arrays`` sorted forms a job brought across by ``depth`` threads, each
    as ``pieces`` device arrays of equal size whose transfers the thread
    starts together and then awaits in turn (1: ``ordered_to_host``'s one
    D2H; more: what a task's transfer would take as several streams)."""
    size = places * RECORD_LANES // pieces
    gen = jax.jit(lambda k: jnp.arange(size, dtype=jnp.int32) + k)
    pool = LandingPool.create(8 << 30)
    rows = []
    for depth in depths:
        per_job = []
        for job in range(jobs):
            todo = queue.SimpleQueue()
            for k in range(arrays):
                todo.put([gen(np.int32(depth * 10000 + job * 1000 + k * pieces + i)) for i in range(pieces)])
            jax.block_until_ready(gen(np.int32(0)))
            time.sleep(0.2)  # the chip has made them all: nothing runs in front of a transfer
            took, lock = [], threading.Lock()

            def drain():
                while True:
                    try:
                        parts = todo.get_nowait()
                    except queue.Empty:
                        return
                    t0 = time.perf_counter_ns()
                    with pool.allocating() if pool is not None else contextlib.nullcontext():
                        for a in parts:
                            a.copy_to_host_async()
                    hosts = [np.asarray(a) for a in parts]
                    dt = time.perf_counter_ns() - t0
                    assert all(h[1] - h[0] == 1 for h in hosts)
                    with lock:
                        took.append(dt / 1e6)
                    del parts, hosts

            threads = [threading.Thread(target=drain) for _ in range(depth)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            per_job.append({
                "wall_s": round(wall, 4), "gb_s": round(arrays * pieces * size * 4 / wall / 1e9, 3),
                "ms_a_transfer_p50": round(statistics.median(took), 3),
            })
        rows.append({"threads": depth, "pieces": pieces, "arrays": arrays, "bytes_each": pieces * size * 4,
                     "jobs": per_job, "pool": pool.stats() if pool is not None else None})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default="parent,keys,change,bare")
    ap.add_argument("--tasks", type=int, default=25)
    ap.add_argument("--slots", type=int, default=2678)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--equal", action="store_true")
    ap.add_argument("--d2h", action="store_true")
    ap.add_argument("--depths", default="1,2,4,8")
    ap.add_argument("--arrays", type=int, default=75)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--pieces", default="1", help="a transfer as this many streams (comma-separated: 1,2,4)")
    ap.add_argument("--out", default=os.path.join("chiprun_out", "probe_ordered_passes.json"))
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    result = {"device": {"platform": device.platform, "kind": device.device_kind}, "slots": args.slots,
              "record_places": args.slots * SLOT_RECORDS, "forms": [], "equal": {}, "d2h": []}
    table, segment, places = make_task(args.slots, args.seed)
    for name in filter(None, args.forms.split(",")):
        fn = jax.jit(FORMS[name])
        row, out = trace_form(name, fn, table, segment, args.tasks)
        if args.equal:
            result["equal"][name] = equal(name, out, *places)
        result["forms"].append(row)
        print(json.dumps(row), flush=True)
        del out
    if args.d2h:
        for pieces in (int(p) for p in args.pieces.split(",")):
            result["d2h"] += d2h_in_flight(args.slots * SLOT_RECORDS, [int(d) for d in args.depths.split(",")],
                                           args.arrays, args.jobs, pieces)
        for row in result["d2h"]:
            print(json.dumps(row), flush=True)
    print(json.dumps({"equal": result["equal"]}))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
    return 0 if all(result["equal"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())

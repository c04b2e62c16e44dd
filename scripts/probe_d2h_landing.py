#!/usr/bin/env python
"""Where does a received shard land?  The probe of PR 43 (ISSUE step 0).

The exchange's drain waits for each 64 MiB received shard to become
host-readable (span ``exchange.d2h``).  This script times, on the chip's own
host and off the benchmark, the ways such a shard can land:

* ``pageable`` — today's: ``copy_to_host_async()`` then ``np.asarray``: the
  runtime copies into a numpy array it allocates for that one ``jax.Array``;
* ``pinned`` — ``jax.device_put`` into the device's ``pinned_host`` memory,
  ``block_until_ready``, then the host view of where it landed;
* ``kept`` — today's calls, made while a NumPy data allocator that keeps its
  large blocks (``sparkucx_tpu.native.LandingPool``) is the thread's: the
  runtime's destination array is then a block an earlier landing gave back,
  pages the process already holds, after the first "job".

Phases (a shard is ``int32[131072, 128]`` = 64 MiB, a fresh device array every
time because the host value is cached per ``jax.Array``; landings alternate
order between repetitions; every figure is a median):

* ``jobs``     (a)/(b): N shards landed one after another and all retained,
  then all released, three "jobs" in a row — ms a shard, the first job
  against the later ones; ``did_copy`` of the view; N = 25 and 39 (1.6 and
  2.55 GB held at once);
* ``inflight`` (c): 1, 2 and 4 transfers issued before the first wait, alone
  and beside a 64 MiB ``device_put`` the other way;
* ``read``     (d): one cold pass of ``np.add.reduce`` over the landed bytes;
* ``copyout``  (form 2's cost): ``np.copyto`` of a pinned landing into a held
  pageable buffer; ``pinned_split``: a pinned landing's put, wait and
  ``np.asarray`` apart;
* ``cycle``    the exchange's own alternation at depth 2: put 64 MiB, run a
  copy on the chip, start the landing, wait for the round before.

Run on the chip:  ``python scripts/probe_d2h_landing.py``; the table goes to
stdout and ``chiprun_out/probe_d2h_landing.json``.  On the CPU backend it runs
at a tiny shape (``--rows``) and proves only that the script works.
"""

import argparse
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from sparkucx_tpu.native import LandingPool  # noqa: E402
from sparkucx_tpu.store.hbm_store import _mem_available_bytes  # noqa: E402

LANE = 128


def _ms(t0):
    return (time.perf_counter_ns() - t0) / 1e6


def _median(xs):
    return round(statistics.median(xs), 3) if xs else None


class Probe:
    def __init__(self, rows):
        self.dev = jax.devices()[0]
        self.shape = (rows, LANE)
        self.nbytes = rows * LANE * 4
        self.pinned = SingleDeviceSharding(self.dev, memory_kind="pinned_host")
        self._gen = jax.jit(lambda k: jnp.arange(rows * LANE, dtype=jnp.int32).reshape(rows, LANE) + k)
        self._copy = jax.jit(lambda x: x + 1)
        self._k = 0
        # a held, touched host buffer for the puts the other way
        self.held = np.ones(self.shape, dtype=np.int32)
        # the 'kept' landing: today's, into blocks a NumPy allocator keeps
        self.pool = LandingPool.create(4 << 30)

    def fresh(self, count):
        """``count`` new device arrays, ready before any clock starts."""
        out = []
        for _ in range(count):
            self._k += 1
            out.append(self._gen(np.int32(self._k)))
        jax.block_until_ready(out)
        return out

    # -- the landings: start() is asynchronous, finish() hands the view --

    def start(self, landing, a):
        if landing == "pageable":
            a.copy_to_host_async()
            return a
        if landing == "kept":
            with self.pool.allocating():  # the runtime allocates the destination here
                a.copy_to_host_async()
            return a
        return jax.device_put(a, self.pinned)

    def finish(self, landing, h):
        """(flat uint8 view, did_copy) once the bytes are host-readable."""
        if landing == "pinned":
            h.block_until_ready()
        did_copy = None
        try:
            arr, did_copy = h._single_device_array_to_np_array_did_copy()
        except AttributeError:
            arr = np.asarray(h)
        return np.asarray(arr).reshape(-1).view(np.uint8), did_copy

    # -- phases ---------------------------------------------------------------

    def jobs(self, landing, shards, jobs=3):
        """(a)/(b): ms a shard, job by job, ``shards`` views retained a job."""
        per_job, did = [], set()
        for _ in range(jobs):
            src = self.fresh(shards)
            kept, times = [], []
            for a in src:
                t0 = time.perf_counter_ns()
                view, did_copy = self.finish(landing, self.start(landing, a))
                times.append(_ms(t0))
                did.add(did_copy)
                kept.append(view)
            # a view outlives its jax.Array (the transport drops the array)
            del src, a
            gc.collect()
            assert int(kept[-1][:4].view(np.int32)[0]) == self._k, "landed bytes differ"
            per_job.append(_median(times))
            del kept, view
            gc.collect()
        return {"ms_a_shard_by_job": per_job, "did_copy": sorted(map(str, did))}

    def inflight(self, landing, k, beside_h2d, reps=5):
        """(c): ``k`` landings issued, then awaited; ms a shard."""
        out, put_ms = [], []
        for _ in range(reps):
            src = self.fresh(k)
            t0 = time.perf_counter_ns()
            handles = [self.start(landing, a) for a in src]
            if beside_h2d:
                t1 = time.perf_counter_ns()
                up = jax.device_put(self.held, self.dev)
            views = [self.finish(landing, h)[0] for h in handles]
            out.append(_ms(t0) / k)
            if beside_h2d:
                up.block_until_ready()
                put_ms.append(_ms(t1))
                del up
            del src, handles, views
        row = {"ms_a_shard": _median(out)}
        if beside_h2d:
            row["h2d_ms"] = _median(put_ms)
        return row

    def pinned_split(self, shards=5):
        """Where a pinned landing's time goes: the put's call, the wait until
        it is ready, the ``np.asarray`` of the result; ms, medians."""
        put, ready, asarray = [], [], []
        for a in self.fresh(shards):
            t0 = time.perf_counter_ns()
            h = jax.device_put(a, self.pinned)
            put.append(_ms(t0))
            t0 = time.perf_counter_ns()
            h.block_until_ready()
            ready.append(_ms(t0))
            t0 = time.perf_counter_ns()
            np.asarray(h)
            asarray.append(_ms(t0))
            del h
        return {"put_ms": _median(put), "ready_ms": _median(ready), "asarray_ms": _median(asarray)}

    def h2d_alone(self, reps=5):
        out = []
        for _ in range(reps):
            t0 = time.perf_counter_ns()
            jax.device_put(self.held, self.dev).block_until_ready()
            out.append(_ms(t0))
        return _median(out)

    def read(self, landing, reps=5):
        """(d): one cold pass over the landed bytes, GB/s."""
        out = []
        for _ in range(reps):
            (a,) = self.fresh(1)
            view, _ = self.finish(landing, self.start(landing, a))
            t0 = time.perf_counter_ns()
            np.add.reduce(view, dtype=np.uint64)
            out.append(self.nbytes / _ms(t0) / 1e6)
            del view, a
        return _median(out)

    def copyout(self, reps=5):
        """Form 2's extra step: pinned landing -> a held pageable buffer."""
        dst = np.ones(self.nbytes, dtype=np.uint8)
        out = []
        for _ in range(reps):
            (a,) = self.fresh(1)
            view, _ = self.finish("pinned", self.start("pinned", a))
            t0 = time.perf_counter_ns()
            np.copyto(dst, view)
            out.append(_ms(t0))
            del view, a
        return _median(out)

    def cycle(self, landing, rounds=25, jobs=3):
        """The exchange at depth 2: put, copy on the chip, start the landing,
        wait for the round before; ms a round, job by job."""
        per_job = []
        for _ in range(jobs):
            kept, times, prev = [], [], None
            t_job = time.perf_counter_ns()
            for _ in range(rounds):
                t0 = time.perf_counter_ns()
                y = self._copy(jax.device_put(self.held, self.dev))
                h = self.start(landing, y)
                if prev is not None:
                    kept.append(self.finish(landing, prev)[0])
                prev = h
                times.append(_ms(t0))
            kept.append(self.finish(landing, prev)[0])
            per_job.append({"ms_a_round": _median(times), "job_ms": round(_ms(t_job), 1)})
            del kept, prev, h, y
            gc.collect()
        return per_job


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=None, help="rows a shard (default: 131072 on a TPU, 1024 elsewhere)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="probe_d2h_landing.json", help="file name under chiprun_out/")
    ap.add_argument("--split-only", action="store_true", help="only the pinned landing's put / wait / asarray")
    ap.add_argument("--landings", default="pageable,pinned,kept", help="which landings to time, comma-separated")
    args = ap.parse_args()
    dev = jax.devices()[0]
    rows = args.rows or (131072 if dev.platform == "tpu" else 1024)
    probe = Probe(rows)
    report = {
        "jax": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "memory_kinds": [m.kind for m in dev.addressable_memories()],
        "shard_bytes": probe.nbytes,
        "cpus": os.cpu_count(),
        "mem_available_gb": round((_mem_available_bytes() or 0) / 2**30, 1),
        "phases": [],
    }

    # does a landing compile anything?  (the benchmark's window may not)
    compiles = [0]

    def _on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(_on_event)

    def phase(name, fn, **kw):
        t0 = time.perf_counter()
        c0 = compiles[0]
        try:
            value = fn(**kw)
        except Exception as e:  # noqa: BLE001 — one call has to answer every question
            value = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        row = {"phase": name, **kw, "result": value, "took_s": round(time.perf_counter() - t0, 2),
               "compiles": compiles[0] - c0}
        report["phases"].append(row)
        print(json.dumps(row), flush=True)

    probe.fresh(1)  # compile the generator off every clock
    jax.block_until_ready(probe._copy(probe.fresh(1)[0]))
    if args.split_only:
        phase("pinned_split", probe.pinned_split)
        args.reps = 0
    landings = [name for name in args.landings.split(",") if name != "kept" or probe.pool is not None]
    report["landings"] = landings
    for rep in range(args.reps):
        order = landings if rep % 2 == 0 else landings[::-1]
        for landing in order:
            phase("jobs", probe.jobs, landing=landing, shards=25)
        for landing in order:
            phase("jobs", probe.jobs, landing=landing, shards=39)
        for landing in order:
            for k in (1, 2, 4):
                phase("inflight", probe.inflight, landing=landing, k=k, beside_h2d=False)
                phase("inflight", probe.inflight, landing=landing, k=k, beside_h2d=True)
        phase("h2d_alone", probe.h2d_alone)
        for landing in order:
            phase("read_gb_s", probe.read, landing=landing)
        if "pinned" in landings:
            phase("copyout_ms", probe.copyout)
            phase("pinned_split", probe.pinned_split)
        for landing in order:
            phase("cycle", probe.cycle, landing=landing)
    if probe.pool is not None:
        report["pool"] = probe.pool.stats()
    try:
        report["memory_stats"] = {
            k: v for k, v in (dev.memory_stats() or {}).items() if "bytes" in k
        }
    except Exception:  # noqa: BLE001
        pass
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.out), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

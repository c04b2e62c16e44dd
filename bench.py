#!/usr/bin/env python
"""Headline benchmark: shuffle superstep throughput through the TPU transport.

Measures the data plane SparkUCX exists to accelerate — the reduce-side block
exchange (per-batch fetch bandwidth, UcxPerfBenchmark.scala:140-143; BASELINE.json
north star: shuffle-read GB/s vs TCP).

What is timed: the compiled shuffle superstep (ops/exchange.py — the ragged
all_to_all that replaces UCX active messages) moving realistically skewed block
payloads that are *resident in HBM*.  Supersteps are chained K deep before
synchronizing so per-dispatch latency is amortized (the analogue of the
reference benchmark's outstanding-request window,
UcxPerfBenchmark.scala:129-151).  Host<->device staging is outside this clock;
the served path end to end is ``chip_smoke.py``'s subject (counts only) and
the cells of ROADMAP queue 1 item 1 (rates).

Baseline measured in the same run: the same byte volume served over a localhost TCP
socket into preallocated buffers (the stock Spark Netty-shuffle transport
analogue).  ``vs_baseline`` = tpu_gbps / tcp_gbps.

Sub-metrics (same JSON line): ``gather_gbps`` — the device-side ragged block
gather (ops/pallas_kernels.py), ``sort_mrows_s`` — the device-resident TeraSort
step (ops/sort.py), and the host-plane loopback measurements of
perf/benchmark.py (``wire``, ``failover``, ``gray``, ``tenants``, ``fanin``,
``compress``, ``obs``), each described where it is taken below.

A small end-to-end shuffle (stage -> commit -> exchange -> fetch vs oracle) runs
untimed first as an integrity gate.

Failure contract: one process, which takes the chip itself.  With no TPU the
run says so on stderr, prints no result and exits 4.  A phase that raises is
recorded as ``<phase>_error`` in the JSON line, the remaining phases still
run, and the process exits 1 naming every failed phase on stderr.  A phase the
hardware cannot run (too few devices) records ``"skipped: ..."`` and does not
fail the run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device", ...}.
"""

import json
import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEND_ROWS = int(os.environ.get("BENCH_SEND_ROWS", "2097152"))
FILL = float(os.environ.get("BENCH_FILL", "0.9"))
# supersteps chained per synchronization (the outstanding-request window)
CHAIN = int(os.environ.get("BENCH_CHAIN", "256"))
REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
TCP_BYTES = int(os.environ.get("BENCH_TCP_BYTES", str(256 << 20)))
SKIP_SUBMETRICS = os.environ.get("BENCH_SKIP_SUBMETRICS", "") == "1"
#: exit code for "no chip" (1 = a phase failed), as in chip_smoke.py
NO_CHIP = 4

RESULT = {
    "metric": "shuffle_superstep_throughput",
    "value": None,
    "unit": "GB/s",
    "vs_baseline": None,
}
#: names of the phases that raised, in order
FAILED = []


def phase(name: str, fn) -> None:
    """Run one measurement.  A failure is recorded (``<name>_error``), named
    at exit, and fails the run — it never turns into a silent null."""
    try:
        fn()
    except Exception as e:
        RESULT[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
        FAILED.append(name)
        print(f"# phase {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)


def tcp_shuffle_read_gbps(total_bytes: int, chunk: int = 1 << 20) -> float:
    """Serve ``total_bytes`` over a localhost socket and time the client reading
    all of it into preallocated buffers (what a TCP shuffle fetch does)."""
    payload = b"\xab" * total_bytes
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def server():
        conn, _ = srv.accept()
        with conn:
            conn.sendall(payload)

    th = threading.Thread(target=server, daemon=True)
    th.start()
    cli = socket.create_connection(("127.0.0.1", port))
    cli.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    dest = bytearray(total_bytes)
    view = memoryview(dest)
    t0 = time.perf_counter()
    got = 0
    while got < total_bytes:
        n = cli.recv_into(view[got:], min(chunk, total_bytes - got))
        if n == 0:
            break
        got += n
    dt = time.perf_counter() - t0
    cli.close()
    srv.close()
    th.join()
    assert got == total_bytes
    return got / dt / 1e9


def integrity_gate():
    """Tiny end-to-end shuffle vs oracle through the full stack (untimed)."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
    from sparkucx_tpu.core.operation import OperationStatus
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    conf = TpuShuffleConf(staging_capacity_per_executor=1 << 20, num_executors=1)
    cluster = TpuShuffleCluster(conf, num_executors=1)
    M, R = 4, 8
    meta = cluster.create_shuffle(0, M, R)
    rng = np.random.default_rng(7)
    oracle = {}
    for m in range(M):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(0, m)
        for r in range(R):
            payload = rng.integers(0, 256, size=int(rng.integers(1, 2000)), dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(0)
    t = cluster.transport(0)
    for (m, r), expect in oracle.items():
        buf = MemoryBlock(np.zeros(4096, dtype=np.uint8), size=4096)
        [req] = t.fetch_blocks_by_block_ids(0, [ShuffleBlockId(0, m, r)], [buf], [None])
        res = req.wait(30)
        assert res.status == OperationStatus.SUCCESS, str(res.error)
        assert buf.host_view()[: buf.size].tobytes() == expect, f"integrity fail at {(m, r)}"
    cluster.remove_shuffle(0)


def device_superstep_gbps(send_rows: int) -> tuple:
    """Chained shuffle supersteps over HBM-resident payloads.
    Returns (best GB/s, executed exchange impl)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange, make_mesh

    n = 1
    spec = ExchangeSpec(
        num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=128, impl="auto"
    )
    mesh = make_mesh(n)
    fn = build_exchange(mesh, spec)

    rng = np.random.default_rng(0)
    slot = spec.slot_rows
    sizes = np.minimum((rng.uniform(0.8, 1.0, size=(n, n)) * FILL * slot).astype(np.int32), slot)
    bytes_per_step = int(sizes.sum()) * spec.row_bytes

    data = jax.device_put(
        rng.integers(-(2**31), 2**31 - 1, size=(n * send_rows, spec.lane), dtype=np.int32),
        NamedSharding(mesh, P("ex", None)),
    )
    size_mat = jax.device_put(sizes, NamedSharding(mesh, P("ex", None)))

    out, _ = fn(data, size_mat)  # warmup/compile; donation consumed `data`
    jax.block_until_ready(out)

    best = 0.0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        cur = out
        for _ in range(CHAIN):
            cur, _ = fn(cur, size_mat)
        jax.block_until_ready(cur)
        dt = time.perf_counter() - t0
        out = cur
        best = max(best, CHAIN * bytes_per_step / dt / 1e9)
    return best, fn.spec.impl


def main() -> int:
    from sparkucx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    RESULT["device"] = {
        "platform": platform, "kind": devs[0].device_kind, "count": len(devs),
    }
    if platform != "tpu":
        print(
            f"bench: no chip found — JAX offers {len(devs)} x {platform} "
            f"({devs[0].device_kind!r}); nothing measured",
            file=sys.stderr,
        )
        return NO_CHIP

    # 1. TCP baseline.
    def tcp():
        RESULT["tcp_gbps"] = round(tcp_shuffle_read_gbps(TCP_BYTES), 3)

    phase("tcp", tcp)

    # 1b. Striped-wire sub-metric (loopback peer wire).  Measured AFTER the
    # TCP baseline so it cannot perturb tcp_gbps.
    def wire():
        from sparkucx_tpu.perf.benchmark import measure_wire

        w = measure_wire(streams_list=(1, 4), num_blocks=8, block_bytes=32 << 20,
                         iterations=4)
        RESULT["wire"] = {
            f"streams{s}_gbps": round(r["gbps"], 3) for s, r in w.items()
        }
        if w.get(1, {}).get("gbps") and w.get(4, {}).get("gbps"):
            RESULT["wire"]["stripe_speedup"] = round(w[4]["gbps"] / w[1]["gbps"], 3)
            RESULT["wire"]["syscalls_per_mb"] = round(w[4]["syscalls_per_mb"], 3)

    phase("wire", wire)

    # 1c. Failover sub-metric (3-executor loopback cluster with
    # replication.factor=1, testing/faults.kill_executor as the SIGKILL
    # stand-in): steady fetch GB/s vs GB/s with the primary killed at t=50%,
    # recovery time (kill -> first replica-served block), p99 frame stall.
    def failover():
        from sparkucx_tpu.perf.benchmark import measure_failover

        fo = measure_failover(num_blocks=8, block_bytes=8 << 20, iterations=3)
        RESULT["failover"] = {
            "steady_gbps": round(fo["steady_gbps"], 3),
            "killed_gbps": round(fo["killed_gbps"], 3),
            "recovery_ms": round(fo["recovery_ms"], 1),
            "failovers": fo["failovers"],
            "rx_stall_p99_ms": round(fo["rx_stall_p99_ms"], 2),
        }

    phase("failover", failover)

    # 1c2. Gray-failure sub-metric (the failover cluster shape, but the
    # primary is throttled to ~10% of the healthy rate instead of killed):
    # GB/s and p99 frame stall with hedging off vs on (fetch.hedgeMs), hedge
    # win counts, bit-equality asserted outside the clock
    # (perf/benchmark.py measure_gray).
    def gray():
        from sparkucx_tpu.perf.benchmark import measure_gray

        gr = measure_gray(num_blocks=8, block_bytes=8 << 20, iterations=3)
        RESULT["gray"] = {
            "healthy_gbps": round(gr["healthy_gbps"], 3),
            "degraded_gbps": round(gr["degraded_gbps"], 3),
            "hedged_gbps": round(gr["hedged_gbps"], 3),
            "degraded_p99_ms": round(gr["degraded_p99_ms"], 2),
            "hedged_p99_ms": round(gr["hedged_p99_ms"], 2),
            "hedge_wins": gr["hedge_wins"],
            "fetch_timeouts": gr["fetch_timeouts"],
            "bit_identical": gr["bit_identical"],
        }

    phase("gray", gray)

    # 1d. Multi-tenant serving-plane sub-metric (one tenants-enabled loopback
    # server on the shared-selector reactor plane, N concurrent apps each
    # fetching through its own tenant namespace): aggregate GB/s, the min/max
    # per-app fairness ratio, and p99 per-block fetch latency under
    # concurrent fan-in (perf/benchmark.py measure_tenants).
    def tenants():
        from sparkucx_tpu.perf.benchmark import measure_tenants

        tn = measure_tenants(
            num_apps=8, num_blocks=8, block_bytes=1 << 20, iterations=2
        )
        RESULT["tenants"] = {
            "apps": tn["apps"],
            "agg_gbps": round(tn["agg_gbps"], 3),
            "fairness": round(tn["fairness"], 3),
            "p99_fetch_ms": round(tn["p99_fetch_ms"], 2),
        }

    phase("tenants", tenants)

    # 1d2. Popularity-aware fan-in sub-metric (per replica-set width,
    # single-worker loopback servers under a fixed per-request service stall;
    # 8 concurrent readers fan in on ONE hot block promoted past
    # serve.hotThresholdFetchesPerSec and spread across the
    # HOT_SET_PULL-advertised holders): aggregate GB/s + pooled p99 per
    # width, and the width-4/width-1 speedup (perf/benchmark.py
    # measure_fanin; bit-identical from every holder off the clock).
    def fanin():
        from sparkucx_tpu.perf.benchmark import measure_fanin

        fn = measure_fanin(
            num_readers=8, block_bytes=256 << 10, iterations=2,
            fetches_per_reader=3,
        )
        RESULT["fanin"] = {
            "per_width": {
                str(w): {
                    "agg_gbps": round(m["agg_gbps"], 3),
                    "p99_fetch_ms": round(m["p99_fetch_ms"], 2),
                }
                for w, m in fn["per_width"].items()
            },
            "speedup": round(fn["speedup"], 3),
        }

    phase("fanin", fanin)

    # 1e. Compression sub-metric (loopback peer wire with the tier-(a) chunk
    # codecs).  Reports ratio x effective GB/s, never ratio alone: a codec
    # only counts if DECODED bytes per wall-second go up.  Every iteration is
    # bit-compared against the source outside the clock.
    def compress():
        from sparkucx_tpu.perf.benchmark import measure_compress

        comp = measure_compress(
            num_blocks=4, block_bytes=4 << 20, iterations=3, e2e=True
        )
        RESULT["compress"] = {
            name: {
                codec: {
                    k: round(cell[k], 3)
                    for k in ("gbps", "ratio", "speedup_vs_off", "e2e_gbps")
                    if k in cell
                }
                for codec, cell in cells.items()
            }
            for name, cells in comp.items()
        }

    phase("compress", compress)

    # 1f. Observability sub-metric (2-executor loopback fetch): GB/s with
    # tracing off / ring-only (the always-on flight recorder's steady state)
    # / full wire-context export.  measure_obs asserts the recorder's
    # accounted overhead (events/pass x ns/record) < 1%.
    def obs():
        from sparkucx_tpu.perf.benchmark import measure_obs

        ob = measure_obs(num_blocks=8, block_bytes=4 << 20, iterations=3)
        RESULT["obs"] = {
            "off_gbps": round(ob["off_gbps"], 3),
            "ring_gbps": round(ob["ring_gbps"], 3),
            "full_gbps": round(ob["full_gbps"], 3),
            "ring_overhead_pct": round(ob["ring_overhead_pct"], 3),
            "span_disabled_ns": round(ob["span_disabled_ns"], 1),
            "span_record_ns": round(ob["span_record_ns"], 1),
            "merged_events": ob["merged_events"],
            "export_ms": round(ob["export_ms"], 1),
        }

    phase("obs", obs)

    # 2. The headline, behind the integrity gate.
    def integrity():
        integrity_gate()
        RESULT["integrity"] = "pass"

    phase("integrity", integrity)

    def superstep():
        tpu, RESULT["superstep_impl"] = device_superstep_gbps(SEND_ROWS)
        RESULT["send_rows"] = SEND_ROWS
        RESULT["superstep_window"] = CHAIN
        RESULT["value"] = round(tpu, 3)
        if RESULT.get("tcp_gbps"):
            RESULT["vs_baseline"] = round(tpu / RESULT["tcp_gbps"], 3)

    phase("superstep", superstep)

    if not SKIP_SUBMETRICS:
        device_submetrics()

    print(json.dumps(RESULT), flush=True)
    if FAILED:
        print(f"bench: FAILED phases: {', '.join(FAILED)}", file=sys.stderr)
        return 1
    return 0


def device_submetrics() -> None:
    import jax

    from sparkucx_tpu.perf.benchmark import (
        measure_gather,
        measure_groupby,
        measure_sort,
    )

    # Gather: 256 x 2 MiB blocks with the Pallas DMA lowering REQUESTED
    # EXPLICITLY and the executed lowering recorded, plus the XLA lowering
    # side by side — so this run can never time one and call it the other.
    def gather():
        impls = []
        gather_window = 64
        RESULT["gather_gbps"] = round(
            measure_gather(
                256, 2 << 20, REPEATS, outstanding=gather_window, impl="dma",
                report=lambda it, dt, tot, impl: impls.append(impl),
            ), 3,
        )
        RESULT["gather_impl"] = impls[-1]
        RESULT["gather_window"] = gather_window

    phase("gather", gather)

    def gather_xla():
        RESULT["gather_xla_gbps"] = round(
            measure_gather(256, 2 << 20, REPEATS, outstanding=8, impl="xla"), 3
        )

    phase("gather_xla", gather_xla)

    def sort():
        sort_impls = []
        RESULT["sort_mrows_s"] = round(
            measure_sort(
                1, 1 << 21, REPEATS,
                report=lambda it, dt, rows, impl: sort_impls.append(impl),
            ), 3,
        )
        if sort_impls:  # report never fires when BENCH_REPEATS=0
            RESULT["sort_impl"] = sort_impls[-1]

    phase("sort", sort)

    # The Pallas LSD radix sort (ops/radix.py) head-to-head against the
    # argsort floor above; a Mosaic compile failure lands in sort_radix_error
    # and fails the run while the argsort number stands.
    def sort_radix():
        RESULT["sort_radix_mrows_s"] = round(
            measure_sort(1, 1 << 21, REPEATS, sort_impl="radix"), 3
        )

    phase("sort_radix", sort_radix)

    # GROUP BY — the reference's gate workload (GroupByTest,
    # buildlib/test.sh:163-173) as one on-device hash-exchange +
    # segment-reduce step; 2M x 100 B rows, 100-key keyspace like the small
    # gate's.
    def groupby():
        gb_impls = []
        wire = []
        RESULT["groupby_mrows_s"] = round(
            measure_groupby(
                1, 1 << 21, REPEATS,
                report=lambda it, dt, rows, impl: gb_impls.append(impl),
                wire_rows=wire,
            ), 3,
        )
        if gb_impls:
            RESULT["groupby_impl"] = gb_impls[-1]
        if wire:
            RESULT["groupby_wire_rows"] = wire[0]

    phase("groupby", groupby)

    # Same workload with map-side partial aggregation below the exchange
    # (conf partialAggregation, on by default for jobs): wire rows collapse
    # from ~2M to ~n_senders * 100 keys.
    def groupby_partial():
        wire_p = []
        gb_rows = 1 << 21
        RESULT["groupby_partial_mrows_s"] = round(
            measure_groupby(1, gb_rows, REPEATS, partial=True, wire_rows=wire_p),
            3,
        )
        if wire_p and wire_p[0]:
            RESULT["groupby_partial_wire_rows"] = wire_p[0]
            RESULT["groupby_wire_reduction"] = round(gb_rows / wire_p[0], 1)

    phase("groupby_partial", groupby_partial)

    # Multi-round (spilled) shuffle with host staging in the loop, at
    # pipeline depths 1/2/3 (transport/pipeline.py): depth 1 is the serial
    # engine, deeper rings overlap H2D staging, the collective, and the D2H
    # drain.
    def pipeline():
        from sparkucx_tpu.perf.benchmark import measure_pipeline

        pl = measure_pipeline(1, 8 << 20, 6, REPEATS)
        RESULT["pipeline"] = {f"depth{d}": round(v, 3) for d, v in pl.items()}
        if pl.get(1) and pl.get(2):
            RESULT["pipeline_overlap_speedup"] = round(pl[2] / pl[1], 3)

    phase("pipeline", pipeline)

    # Elastic recovery: full-mesh exchange GB/s vs one pass with an executor
    # killed mid-superstep — the cluster shrinks to the surviving pow2
    # bucket, restages the dead executor's rounds from ring-successor
    # replicas, and re-runs in degraded waves (output asserted bit-identical
    # inside the measurement).
    def elastic():
        n_el = min(4, jax.device_count())
        if n_el < 2:
            RESULT["elastic"] = "skipped: elastic recovery needs >= 2 devices"
            return
        from sparkucx_tpu.perf.benchmark import measure_elastic

        el = measure_elastic(n_el, 8 << 10, REPEATS)
        RESULT["elastic"] = {
            "steady_gbps": round(el["steady_gbps"], 3),
            "degraded_gbps": round(el["degraded_gbps"], 3),
            "recovery_ms": round(el["recovery_ms"], 1),
            "mesh": f"{n_el}->{el['degraded_mesh']}",
        }

    phase("elastic", elastic)

    # Map-output staging: host byte path (memcpy into host staging + seal's
    # H2D) vs the device staging path (write_partition_device + block-scatter
    # kernel, seal returns the HBM payload directly).
    def write():
        from sparkucx_tpu.perf.benchmark import measure_write

        wr = measure_write(8, 1 << 20, REPEATS)
        RESULT["write"] = {impl: round(v, 3) for impl, v in wr.items()}
        if wr.get("host") and wr.get("device"):
            RESULT["write_device_speedup"] = round(wr["device"] / wr["host"], 3)

    phase("write", write)

    # Skew-aware exchange planning (ops/skew.py): quota-capped chunked plan
    # vs the max-sized single-shot bucket on a Zipf-skewed size matrix.
    # 40000 rows sits just past the 32768 pow2 boundary, the case where
    # single-shot doubles its staging bucket but chunking pays only extra
    # sub-rounds; quota 8192 forces 5 chunks.  Bit equality of the two plans
    # is asserted inside measure_skew.
    def skew():
        from sparkucx_tpu.perf.benchmark import measure_skew

        sk = measure_skew(1, 40000, REPEATS, quota_rows=8192)
        RESULT["skew"] = {
            "quota_gbps": round(sk["quota"]["gbps"], 3),
            "max_gbps": round(sk["max"]["gbps"], 3),
            "subrounds": sk["subrounds"],
            "quota_padding": round(sk["quota"]["padding_fraction"], 4),
            "max_padding": round(sk["max"]["padding_fraction"], 4),
            "staged_rows_cut": round(
                sk["max"]["staged_rows"] / max(sk["quota"]["staged_rows"], 1), 3
            ),
        }

    phase("skew", skew)

    # Adaptive exchange planning (ops/planner.py): the telemetry-fed
    # AdaptivePlanner re-planning per cell of a skew x payload-entropy x
    # fault matrix vs every static (quota, codec) config held fixed across
    # it.  The exchange leg is measured, the serve-plane legs are modeled
    # from measured inputs (encode time/bytes, hedge vs a gray straggler);
    # bit-equality of every chunked schedule against the single-shot
    # reference is asserted inside measure_adaptive.
    def adaptive():
        n_ad = min(8, jax.device_count())
        from sparkucx_tpu.perf.benchmark import measure_adaptive

        ad = measure_adaptive(n_ad, 512, max(2, REPEATS))
        worst = max(ad["cells"], key=lambda c: c["distance_from_oracle"])
        RESULT["adaptive"] = {
            "executors": n_ad,
            "cells": len(ad["cells"]),
            "aggregate_adaptive_gbps": ad["aggregate_adaptive_gbps"],
            "best_static": ad["best_static"],
            "best_static_gbps": ad["best_static_gbps"],
            "beats_every_static": ad["adaptive_beats_every_static"],
            "worst_cell_distance": ad["worst_cell_distance"],
            "worst_cell": f"alpha={worst['alpha']} entropy={worst['entropy']} "
                          f"fault={worst['fault']}",
            "bit_identical": all(c["bit_identical"] for c in ad["cells"]),
        }

    phase("adaptive", adaptive)

    # FAST-scheduled ring exchange (ops/ici_exchange.py) vs the stock
    # collective at the widest mesh this host exposes, plus the fused send
    # side's single-launch check.  Bit equality between the impls is asserted
    # inside measure_ici.
    def ici():
        if jax.device_count() < 2:
            RESULT["ici"] = "skipped: the ring exchange needs >= 2 devices"
            return
        from sparkucx_tpu.perf.benchmark import measure_ici

        ic = measure_ici((2, 4, 8), 1024, 128, iterations=REPEATS)
        widest = max(ic["per_n"])
        p = ic["per_n"][widest]
        RESULT["ici"] = {
            "executors": widest,
            "stock_gbps": round(p["stock_gbps"], 3),
            "pallas_gbps": round(p["pallas_gbps"], 3),
            "pallas_per_link_gbps": round(p["pallas_per_link_gbps"], 4),
            "supersteps": p["supersteps"],
            "chunks": p["chunks"],
            "lowering": p["lowering"],
            "fused_single_launch": ic["fused"]["launches"] == 1,
        }

    phase("ici", ici)

    # compute-in-exchange: the receive-side fused combine vs the unfused
    # exchange-then-fold reference.  Bit equality is asserted inside
    # measure_combine; the drain ratio is the O(rows) landed grid over the
    # O(groups) accumulator each device drains instead.
    def combine():
        if jax.device_count() < 2:
            RESULT["combine"] = "skipped: the fused combine needs >= 2 devices"
            return
        from sparkucx_tpu.perf.benchmark import measure_combine

        cb = measure_combine(8, 1024, 128, iterations=REPEATS)
        RESULT["combine"] = {
            "executors": cb["executors"],
            "fused_gbps": round(cb["fused_gbps"], 3),
            "unfused_gbps": round(cb["unfused_gbps"], 3),
            "drain_ratio": round(cb["drain"]["ratio"], 1),
            "lowering": cb["lowering"],
            "fused_single_launch": cb["launches"] == 1,
        }

    phase("combine", combine)

    # End-to-end query DAGs with lineage-keyed cross-query shuffle reuse
    # (sparkucx_tpu/query): M concurrent tenant DAGs repeat a
    # GroupByTest-shaped pipeline; the cached pass serves repeated exchanges
    # from the sealed store tiers instead of re-executing.  Cached-hit
    # results are asserted bit-identical to the cold pass inside
    # measure_queries.
    def queries():
        from sparkucx_tpu.perf.benchmark import measure_queries

        qr = measure_queries(
            num_apps=4, queries_per_app=4, rows_per_query=2000,
        )
        RESULT["queries"] = {
            "apps": qr["apps"],
            "cold_qps": round(qr["cold_qps"], 2),
            "warm_qps": round(qr["warm_qps"], 2),
            "speedup": round(qr["speedup"], 3),
            "hit_rate": round(qr["hit_rate"], 3),
            "p99_stage_ms": round(qr["p99_stage_ms"], 2),
            "bit_identical": qr["bit_identical"],
        }

    phase("queries", queries)


if __name__ == "__main__":
    sys.exit(main())

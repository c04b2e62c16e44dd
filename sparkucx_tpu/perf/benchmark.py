"""Standalone transport benchmark CLI — the ``UcxPerfBenchmark`` analogue.

Counterpart of ``shuffle/ucx/perf/UcxPerfBenchmark.scala`` (221 LoC): a
no-Spark-required driver for the transport layers.  Same CLI shape
(UcxPerfBenchmark.scala:41-59):

========  ==========================================  =====================
flag      reference meaning                            here
========  ==========================================  =====================
-a        server socket address                        same (host:port)
-f        file to serve blocks from                    same (optional)
-n        number of blocks                             same
-s        block size                                   same (byte suffixes ok)
-i        iterations                                   same
-o        outstanding requests per batch               same
-r        requests in flight / reuse address           iterations per print
-t        client threads                               same
========  ==========================================  =====================

Modes:

* ``server`` — register -n blocks of -s bytes (file-backed when -f is given,
  synthetic otherwise) on a PeerTransport BlockServer and wait
  (UcxPerfBenchmark.scala:156-208).
* ``client`` — connect, issue -o-deep batches of ``fetch_blocks_by_block_ids``
  across -t threads, spin ``progress()``, print per-batch bandwidth
  (UcxPerfBenchmark.scala:100-154, bandwidth print :140-143).
* ``wire`` — loopback peer-fetch throughput at several ``wire.streams`` lane
  counts (the striped zero-copy wire path): one in-process BlockServer, one
  client per streams value fetching -n blocks of -s bytes per iteration.
  Prints GB/s, receive syscalls/MB, and p99 frame stall per streams value;
  ``--streams 1`` is the byte-identical pre-striping wire, so it doubles as
  the before/after baseline.
* ``compress`` — tier-(a)/(b) payload reduction, ratio x GB/s: loopback fetch
  throughput at codec in {off, dict, rle, delta} on a dictionary-heavy
  (clustered low-cardinality u32 keys) and an incompressible matrix, with
  bit-equality asserted on EVERY lossless pass and compression ratio /
  encoded-chunk-pool hits from the server's ``compress_stats``; an
  end-to-end ``TpuShuffleReader`` pass per codec (credit gate budgets
  decoded bytes); and, when >= 2 devices are up, the quantized-vs-f32 ICI
  exchange (int8 / blockfloat) with the dequant error bound asserted.
* ``failover`` — executor-loss robustness under traffic: a 3-executor
  loopback cluster with ``replication.factor = 1`` (seal pushes every round
  to the ring neighbor), a reducer streaming -n blocks of -s bytes from the
  primary.  Steady-state fetch GB/s first, then one pass where the primary
  is killed at t=50% (testing/faults.kill_executor) and the reader fails
  over to the replica holder.  Prints both GB/s, the recovery time (kill ->
  first replica-served block), failovers, and p99 frame stall.
* ``gray`` — gray-failure robustness under traffic: the ``failover`` cluster
  shape, but the primary is THROTTLED to ~10% of the measured healthy rate
  (every served frame stalls) instead of killed — the degraded-but-alive
  peer that trips no deadline.  Measures GB/s + p99 frame stall healthy,
  throttled with hedging off, and throttled with ``fetch.hedgeMs`` on
  (hedges rescue straggling blocks from the replica holder); one unclocked
  hedged pass asserts every block bit-identical to the staged payload.
* ``tenants`` — multi-tenant serving plane under concurrent fan-in: one
  tenants-enabled loopback server (the shared-selector reactor plane,
  service/reactor.py) stages -n blocks of -s bytes per registered app;
  ``--apps`` synthetic applications then stream their own set back
  CONCURRENTLY, each through its own client transport carrying its app_id
  as the FETCH_BLOCK_REQ extension (tenant-local shuffle ids, server-side
  TenantRegistry translation).  Prints aggregate GB/s, per-app GB/s, the
  min/max per-app fairness ratio, and p50/p99 per-block fetch latency.
* ``fanin`` — popularity-aware serving under N-reducer fan-in on ONE hot
  block: per replica-set width (1/2/4 holders), a fresh loopback cluster of
  single-worker servers with a fixed per-FETCH_BLOCK_REQ service stall (the
  deterministic single-server ceiling); a bootstrap storm promotes the block
  (``serve.hotThresholdFetchesPerSec``), the primary advertises every holder
  over HOT_SET_PULL, and -t (default 8) concurrent readers rotate their
  fetches across the set.  Prints aggregate GB/s + pooled p99 per-fetch
  latency per width and the width-4/width-1 speedup; off the clock the block
  is asserted bit-identical from EVERY holder.
* ``elastic`` — degraded-mode exchange recovery under chaos: an
  ``--executors``-wide loopback cluster with ``elastic.enabled`` and
  ``replication.factor = 1`` runs multi-round shuffles of -s-byte blocks.
  Steady-state full-mesh exchange GB/s first, then one pass where an
  executor is killed MID-SUPERSTEP — the cluster shrinks to the surviving
  pow2 bucket, restages the dead executor's rounds from ring-successor
  replicas, and re-runs in degraded waves (output asserted byte-identical).
  Prints both GB/s, the recovery time, and the shrunk mesh shape.
* ``superstep`` — the TPU-only mode with no reference counterpart: time the
  collective exchange on the local mesh (what bench.py wraps).
* ``pipeline`` — multi-round (spilled) shuffle throughput with host staging in
  the loop, at pipeline depths 1/2/3 (transport/pipeline.py): -n rounds of -s
  bytes each through H2D -> collective -> D2H; depth 1 is the serial engine,
  deeper rings overlap the three stages.  Prints GB/s per depth.
* ``gather`` — time the device-side ragged block gather (ops/pallas_kernels.py),
  the reply-packing hot path (UcxWorkerWrapper.scala:397-448 analogue): -n
  blocks of -s bytes scattered through a source buffer, packed into one HBM
  buffer.  ``--impl`` selects the lowering (dma | tiled | xla | auto).
* ``sort`` — time the device-resident TeraSort step (ops/sort.py): -n rows of
  100 B (uint32 key + 24 int32 lanes) through sample-sort over ``--executors``
  devices; prints M rows/s.  The on-device analogue of the reference harness's
  TeraSort workload (BASELINE.json configs[1]).  ``--batches B`` > 1 instead
  drives the out-of-core driver (run_external_sort): the -n rows pass through
  B device batches and a stable host merge — the "TeraSort 10GB on one chip"
  path; expect host-merge-bound numbers.
* ``columnar`` — time the device-resident columnar shuffle (ops/columnar.py,
  the GpuColumnarExchange analogue; BASELINE.json columnar config): -n rows of
  -s bytes repartitioned in HBM by a random owner vector; prints GB/s.
* ``groupby`` — time the device-resident GROUP BY (ops/relational.py): -n rows
  of 100 B (uint32 key from ``--keys`` distinct values + 24 summed int32
  lanes) through hash exchange + segment reduction over ``--executors``
  devices; prints M rows/s.  The on-device analogue of the workload the
  reference gates on — ``GroupByTest`` generates random (key, value) pairs and
  groups them by key (buildlib/test.sh:163-173, BASELINE.json configs[0]).
* ``ici`` — the FAST-scheduled ring exchange (ops/ici_exchange.py) vs the
  stock collective at mesh widths 2/4/8 (``--executors N`` pins one width):
  aggregate and per-directed-link GB/s for both impls, superstep/occupancy
  telemetry (utils/stats.py), bit-equality asserted, plus the fused
  scatter+exchange single-launch check.  ``--chunks`` sets the FAST
  per-destination interleave depth.
* ``join`` — time the device-resident hash join (ops/relational.py): a PK-FK
  inner join in the TPC-H shape (BASELINE.json configs[2]) — ``--build-rows``
  dimension rows (unique keys, 8 int32 lanes) probed by -n fact rows (16
  lanes), both sides hash-exchanged then matched; prints M probe rows/s.
* ``combine`` — the receive-side fused-combine exchange
  (ops/ici_exchange.build_combine_exchange) vs the unfused reference
  (scheduled exchange, then a separate fold over the landed grid): partial
  aggregate rows with ``--keys`` distinct groups, -s bytes per peer slot,
  over ``--executors`` devices.  Asserts the fused accumulator bit-identical
  to the reference fold off the clock and prints the drain-bytes collapse
  (O(rows) landed grid vs O(groups) accumulator) plus the launch-count
  collapse (one fused kernel vs one dispatch per schedule item + the fold).
"""

from __future__ import annotations

import argparse
import sys
import threading
import time
from typing import List

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf, parse_size
from sparkucx_tpu.core.block import BytesBlock, FileBackedBlock, MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus
from sparkucx_tpu.transport.peer import PeerTransport


def _parse_args(argv):
    p = argparse.ArgumentParser(prog="sparkucx-tpu-perf", description=__doc__.split("\n")[0])
    p.add_argument(
        "mode",
        choices=[
            "server", "client", "superstep", "pipeline", "gather", "sort",
            "columnar", "groupby", "join", "write", "skew", "adaptive", "wire",
            "ici", "combine", "failover", "elastic", "compress", "tenants",
            "obs", "gray", "fanin", "queries",
        ],
    )
    p.add_argument("-a", "--address", default="127.0.0.1:13337", help="server host:port")
    p.add_argument("-f", "--file", default=None, help="file to serve blocks from (server)")
    p.add_argument("-n", "--num-blocks", type=int, default=8)
    p.add_argument("-s", "--block-size", default="4m")
    p.add_argument("-i", "--iterations", type=int, default=5)
    p.add_argument("-o", "--outstanding", type=int, default=8)
    p.add_argument("-r", "--reports", type=int, default=1, help="batches per bandwidth print")
    p.add_argument("-t", "--threads", type=int, default=1)
    p.add_argument("--executors", type=int, default=1, help="mesh size (superstep mode)")
    p.add_argument(
        "--slices", type=int, default=1,
        help="factor the superstep mesh into this many slices (two-phase ICI+DCN route)",
    )
    p.add_argument(
        "--impl", default="auto",
        help="block-gather lowering: auto|dma|tiled|xla (gather mode), or a "
        "comma list of staging paths to compare: host,device (write mode)",
    )
    p.add_argument(
        "--keys", type=int, default=100,
        help="distinct group keys (groupby mode; GroupByTest's numKVPairs keyspace)",
    )
    p.add_argument(
        "--build-rows", type=int, default=0,
        help="dimension-side rows (join mode); 0 means -n // 4",
    )
    p.add_argument(
        "--partial", action="store_true",
        help="map-side partial aggregation below the exchange (groupby mode; "
        "conf spark.shuffle.tpu.partialAggregation)",
    )
    p.add_argument(
        "--join-type", default="inner",
        choices=["inner", "left_outer", "left_semi", "left_anti",
                 "right_outer", "full_outer"],
        help="join arm to benchmark (join mode); half the probe keys miss so "
        "every arm's matched AND unmatched branches do real work",
    )
    p.add_argument(
        "--sort-impl", default="auto",
        choices=["auto", "single", "radix", "ragged", "dense"],
        help="sort lowering (sort mode); 'radix' = the Pallas LSD radix "
        "kernel with fused key+payload segment-DMA scatter (n=1 only)",
    )
    p.add_argument(
        "--batches", type=int, default=1,
        help="device batches for the out-of-core sort driver (sort mode)",
    )
    p.add_argument(
        "--depths", default="1,2,3",
        help="comma-separated pipeline depths to compare (pipeline mode)",
    )
    p.add_argument(
        "--streams", default="1,2,4",
        help="comma-separated wire.streams values to compare (wire mode)",
    )
    p.add_argument(
        "--chunk-bytes", default="4m",
        help="chunk frame size for striped lanes (wire mode; wire.chunkBytes)",
    )
    p.add_argument(
        "--zipf-alpha", type=float, default=1.2,
        help="Zipf exponent for the per-peer size distribution (skew mode)",
    )
    p.add_argument(
        "--quota", type=int, default=0,
        help="slot quota in rows (skew mode); 0 picks the pow2 ceiling of the "
        "mean lane size automatically",
    )
    p.add_argument(
        "--chunks", type=int, default=0,
        help="FAST chunks per destination (ici mode); 0 picks the default "
        "interleave depth (ops/ici_exchange.py DEFAULT_CHUNKS_PER_DEST)",
    )
    p.add_argument(
        "--apps", type=int, default=8,
        help="concurrent synthetic applications (tenants mode)",
    )
    return p.parse_args(argv)


def run_server(args) -> None:
    host, _, port = args.address.rpartition(":")
    size = parse_size(args.block_size)
    conf = TpuShuffleConf(listener_address=(host or "127.0.0.1", int(port)))
    transport = PeerTransport(conf, executor_id=0)
    addr = transport.init()
    rng = np.random.default_rng(0)
    for i in range(args.num_blocks):
        if args.file:
            block = FileBackedBlock(args.file, offset=(i * size), length=size)
        else:
            block = BytesBlock(rng.integers(0, 256, size=size, dtype=np.uint8))
        transport.register(ShuffleBlockId(0, 0, i), block)
    print(f"serving {args.num_blocks} x {size} B blocks on {addr.decode()}", flush=True)
    try:
        while True:
            time.sleep(1)  # server threads do the work (UcxPerfBenchmark.scala:204-207)
    except KeyboardInterrupt:
        transport.close()


def run_client(args) -> None:
    host, _, port = args.address.rpartition(":")
    size = parse_size(args.block_size)
    conf = TpuShuffleConf(max_blocks_per_request=max(args.outstanding, 1))
    results_lock = threading.Lock()
    printed: List[str] = []

    def worker(tid: int) -> None:
        transport = PeerTransport(conf, executor_id=100 + tid)
        transport.add_executor(0, f"{host or '127.0.0.1'}:{port}".encode())
        # -o bounds the blocks (and result buffers) in flight per window —
        # numOutstanding semantics (UcxPerfBenchmark.scala:129-151): issue a
        # window, progress until it drains, issue the next.  For peak
        # localhost throughput run with -o = -n (whole set in flight) so the
        # next request is queued at the server while a reply streams.
        bufs = [MemoryBlock(np.zeros(size, dtype=np.uint8), size=size) for _ in range(args.outstanding)]
        for it in range(args.iterations):
            t0 = time.perf_counter()
            done_bytes = 0
            for base in range(0, args.num_blocks, args.outstanding):
                bids = [
                    ShuffleBlockId(0, 0, (base + k) % args.num_blocks)
                    for k in range(min(args.outstanding, args.num_blocks - base))
                ]
                reqs = transport.fetch_blocks_by_block_ids(
                    0, bids, bufs[: len(bids)], [None] * len(bids)
                )
                while not all(r.completed() for r in reqs):
                    transport.progress()
                    # wakeup park instead of burning the recv thread's GIL
                    transport.wait_for_activity(0.002)
                for r in reqs:
                    res = r.wait(1)
                    assert res.status == OperationStatus.SUCCESS, str(res.error)
                    done_bytes += res.stats.recv_size
            dt = time.perf_counter() - t0
            # Mb/s like the reference print (UcxPerfBenchmark.scala:140-143)
            line = (
                f"[thread {tid}] iter {it}: {done_bytes} bytes in {dt*1e3:.1f} ms "
                f"= {done_bytes * 8 / dt / 1e6:.0f} Mb/s ({done_bytes / dt / 1e9:.2f} GB/s)"
            )
            with results_lock:
                printed.append(line)
                print(line, flush=True)
        transport.close()

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(args.threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def run_superstep(args) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange, make_mesh

    size = parse_size(args.block_size)
    n = args.executors
    rows_per_peer = max(1, size // 512)
    send_rows = n * rows_per_peer
    spec = ExchangeSpec(num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=128)
    if args.slices > 1:
        from sparkucx_tpu.ops.hierarchy import (
            build_hierarchical_exchange,
            make_hierarchical_mesh,
        )

        mesh = make_hierarchical_mesh(args.slices, n // args.slices)
        fn = build_hierarchical_exchange(mesh, spec.resolve_impl())
        sharding = NamedSharding(mesh, P(("dcn", "ici"), None))
    else:
        mesh = make_mesh(n)
        fn = build_exchange(mesh, spec)
        sharding = NamedSharding(mesh, P("ex", None))
    rng = np.random.default_rng(0)
    data = jax.device_put(
        rng.integers(-100, 100, size=(n * send_rows, 128), dtype=np.int32), sharding
    )
    sizes = jax.device_put(
        np.full((n, n), rows_per_peer, dtype=np.int32), sharding
    )
    out, _ = fn(data, sizes)
    jax.block_until_ready(out)
    moved = n * n * rows_per_peer * 512
    for it in range(args.iterations):
        t0 = time.perf_counter()
        cur = out
        for _ in range(args.outstanding):
            cur, _ = fn(cur, sizes)
        jax.block_until_ready(cur)
        dt = time.perf_counter() - t0
        out = cur
        total = moved * args.outstanding
        print(
            f"iter {it}: {total} bytes in {dt*1e3:.1f} ms = {total * 8 / dt / 1e6:.0f} Mb/s "
            f"({total / dt / 1e9:.2f} GB/s) [impl={fn.spec.impl}]",
            flush=True,
        )


def measure_wire(
    streams_list=(1, 2, 4),
    num_blocks: int = 8,
    block_bytes: int = 32 << 20,
    iterations: int = 5,
    chunk_bytes: int = 4 << 20,
    report=None,
) -> dict:
    """Measurement core of the ``wire`` mode — loopback peer-fetch throughput
    at several ``wire.streams`` lane counts (the striped zero-copy wire path).

    One BlockServer-backed PeerTransport registers ``num_blocks`` blocks of
    ``block_bytes``; for each streams value a fresh client fetches the whole
    set per iteration (the whole batch in flight, the -o = -n shape).  Per
    streams value the result carries best GB/s, receive syscalls per MB
    (``recv_into`` calls / MB landed, from ``wire_lane_stats``), and the worst
    lane's p99 frame stall.  ``streams = 1`` is the byte-identical single-lane
    wire, so its row IS the pre-striping baseline.  ``report(streams, it,
    seconds, bytes)`` per iteration.  Shared by the CLI and bench.py."""
    server = PeerTransport(TpuShuffleConf(), executor_id=0)
    addr = server.init()
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8)
    bids = [ShuffleBlockId(0, 0, i) for i in range(num_blocks)]
    for bid in bids:
        server.register(bid, BytesBlock(payload.tobytes()))
    total = num_blocks * block_bytes
    results = {}
    try:
        for streams in streams_list:
            conf = TpuShuffleConf(
                wire_streams=streams,
                wire_chunk_bytes=chunk_bytes,
                max_blocks_per_request=num_blocks,
            )
            client = PeerTransport(conf, executor_id=100 + streams)
            client.add_executor(0, addr)
            bufs = [
                MemoryBlock(np.zeros(block_bytes, dtype=np.uint8), size=block_bytes)
                for _ in range(num_blocks)
            ]

            def fetch_once():
                reqs = client.fetch_blocks_by_block_ids(
                    0, bids, bufs, [None] * num_blocks
                )
                while not all(r.completed() for r in reqs):
                    client.progress()
                    client.wait_for_activity(0.002)
                for r in reqs:
                    res = r.wait(1)
                    assert res.status == OperationStatus.SUCCESS, str(res.error)

            fetch_once()  # warmup: connect (+ stripe handshake), page in
            assert bytes(bufs[0].host_view()[:64].tobytes()) == payload[:64].tobytes()
            best = 0.0
            t_all0 = time.perf_counter()
            for it in range(iterations):
                t0 = time.perf_counter()
                fetch_once()
                dt = time.perf_counter() - t0
                best = max(best, total / dt / 1e9)
                if report is not None:
                    report(streams, it, dt, total)
            wall = time.perf_counter() - t_all0
            lanes = client.wire_lane_stats()
            rx_bytes = sum(s["rx_bytes"] for s in lanes)
            rx_syscalls = sum(s["rx_syscalls"] for s in lanes)
            results[streams] = {
                "gbps": best,
                "mean_gbps": total * iterations / wall / 1e9,
                "syscalls_per_mb": rx_syscalls / max(rx_bytes / 1e6, 1e-9),
                "p99_frame_stall_ms": max(s["rx_stall_p99_ns"] for s in lanes) / 1e6,
                "lanes": len(lanes),
            }
            client.close()
    finally:
        server.close()
    return results


#: ``measure_compress`` payload matrices.  "dictkeys" is the dictionary-heavy
#: shape the tier-(a) codecs target: a low-cardinality u32 key column laid out
#: clustered (map-side combine emits key-grouped rows), so dict sees a
#: 256-entry alphabet (4x) and word-RLE sees the runs.  "noise" is the
#: incompressible floor: every codec must detect it, ship raw, and cost ~0.
def _compress_matrices(block_bytes: int, rng) -> dict:
    words = block_bytes // 4
    alpha = rng.integers(0, 2**32, size=256, dtype=np.uint32)
    dictkeys = np.repeat(alpha, (words + 255) // 256)[:words]
    dictkeys = dictkeys.astype("<u4").tobytes().ljust(block_bytes, b"\0")
    noise = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
    return {"dictkeys": dictkeys, "noise": noise}


def _compress_e2e(
    codec: str, payload: bytes, num_blocks: int, iterations: int, report=None
) -> float:
    """End-to-end shuffle GB/s at one codec: store-staged blocks on executor 1
    streamed back through a credit-gated ``TpuShuffleReader`` on executor 0
    (the CreditGate budgets DECODED bytes, so this leg exercises exactly the
    composition the wire-level fetch loop does not).  Returns best GB/s;
    every pass asserts bit-equality against the staged payload."""
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader

    block_bytes = len(payload)
    conf = TpuShuffleConf(
        wire_compress_codec=codec,
        wire_timeout_ms=10_000,
        staging_capacity_per_executor=num_blocks * block_bytes + (1 << 20),
    )
    ts = [PeerTransport(conf, executor_id=i) for i in (0, 1)]
    addrs = [t.init() for t in ts]
    ts[0].add_executor(1, addrs[1])
    ts[1].add_executor(0, addrs[0])
    total = num_blocks * block_bytes
    try:
        ts[1].store.create_shuffle(0, 1, num_blocks)
        w = ts[1].store.map_writer(0, 0)
        for r in range(num_blocks):
            w.write_partition(r, payload)
        w.commit()
        ts[1].store.seal(0)

        def consume() -> float:
            reader = TpuShuffleReader(
                ts[0],
                executor_id=0,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_blocks,
                num_mappers=1,
                block_sizes=lambda m, r: block_bytes,
                sender_of=lambda m: 1,
                # several windows in flight under the credit budget: credits
                # meter DECODED bytes, so this is the codec x CreditGate
                # composition path, not just the raw fetch loop
                max_blocks_per_request=2,
                credit_bytes=64 << 20,
            )
            t0 = time.perf_counter()
            blocks = []
            for blk in reader.fetch_blocks():
                blocks.append(blk)
            dt = time.perf_counter() - t0
            assert len(blocks) == num_blocks
            for blk in blocks:  # lossless contract: checked OUTSIDE the clock
                assert bytes(blk.data) == payload, f"e2e codec={codec} corrupted"
                blk.release()
            return dt

        consume()  # warmup: connect + populate the server's encode pool
        best = 0.0
        for it in range(iterations):
            dt = consume()
            best = max(best, total / dt / 1e9)
            if report is not None:
                report(f"e2e:{codec}", it, dt, total)
        return best
    finally:
        for t in ts:
            t.close()


def measure_compress(
    codecs=("off", "dict", "rle", "delta"),
    num_blocks: int = 8,
    block_bytes: int = 8 << 20,
    iterations: int = 5,
    chunk_bytes: int = 4 << 20,
    streams: int = 1,
    e2e: bool = True,
    report=None,
) -> dict:
    """Measurement core of the ``compress`` mode — loopback fetch throughput
    with the tier-(a) wire codecs, ratio x GB/s (never ratio alone).

    Per (matrix, codec): a fresh codec-configured server registers
    ``num_blocks`` blocks of the matrix, a fresh client streams the set per
    iteration, and EVERY iteration's buffers are compared byte-for-byte
    against the source (the lossless contract is asserted, not assumed —
    outside the timed region).  The first (warmup) pass also charges the
    server's encoded-chunk pool, so timed passes measure the steady serve
    state: sealed blocks are immutable, each chunk pays the encoder once per
    lifetime, not once per fetch.  Results per cell: best/mean effective GB/s
    (DECODED bytes over the wall clock), compression ratio and wire bytes
    from the server's ``compress_stats``, and pool hit count.  ``e2e`` adds a
    store-staged ``TpuShuffleReader`` pass per codec on the dictionary-heavy
    matrix (credit gate budgets decoded bytes).  ``report(label, it, seconds,
    bytes)`` per iteration.  Shared by the CLI and bench.py."""
    rng = np.random.default_rng(0)
    matrices = _compress_matrices(block_bytes, rng)
    total = num_blocks * block_bytes
    results: dict = {name: {} for name in matrices}
    for name, payload in matrices.items():
        for codec in codecs:
            server = PeerTransport(
                TpuShuffleConf(wire_compress_codec=codec), executor_id=0
            )
            addr = server.init()
            bids = [ShuffleBlockId(0, 0, i) for i in range(num_blocks)]
            for bid in bids:
                server.register(bid, BytesBlock(payload))
            client = PeerTransport(
                TpuShuffleConf(
                    wire_compress_codec=codec,
                    wire_streams=streams,
                    wire_chunk_bytes=chunk_bytes,
                    max_blocks_per_request=num_blocks,
                ),
                executor_id=1,
            )
            client.add_executor(0, addr)
            try:
                bufs = [
                    MemoryBlock(np.zeros(block_bytes, dtype=np.uint8), size=block_bytes)
                    for _ in range(num_blocks)
                ]

                def fetch_once():
                    reqs = client.fetch_blocks_by_block_ids(
                        0, bids, bufs, [None] * num_blocks
                    )
                    while not all(r.completed() for r in reqs):
                        client.progress()
                        client.wait_for_activity(0.002)
                    for r in reqs:
                        res = r.wait(1)
                        assert res.status == OperationStatus.SUCCESS, str(res.error)

                fetch_once()  # warmup: connect + charge the encode pool
                best = 0.0
                t_all0 = time.perf_counter()
                wall = 0.0
                for it in range(iterations):
                    t0 = time.perf_counter()
                    fetch_once()
                    dt = time.perf_counter() - t0
                    wall += dt
                    best = max(best, total / dt / 1e9)
                    if report is not None:
                        report(f"{name}:{codec}", it, dt, total)
                    for b in bufs:  # bit-equality EVERY lossless run
                        got = b.host_view().tobytes()
                        assert got == payload, (
                            f"lossless fetch diverged: matrix={name} codec={codec}"
                        )
                st = server.server.compress_snapshot()
                cell = {
                    "gbps": best,
                    "mean_gbps": total * iterations / max(wall, 1e-9) / 1e9,
                    "ratio": st["raw_bytes"] / max(st["wire_bytes"], 1),
                    "wire_bytes": st["wire_bytes"],
                    "raw_bytes": st["raw_bytes"],
                    "encoded_chunks": st["encoded_chunks"],
                    "raw_chunks": st["raw_chunks"],
                    "pool_hits": st["cache_hits"],
                }
            finally:
                client.close()
                server.close()
            if e2e and name == "dictkeys":
                cell["e2e_gbps"] = _compress_e2e(
                    codec, payload, num_blocks, iterations, report=report
                )
            results[name][codec] = cell
    for name in results:
        base = results[name].get("off", {}).get("gbps")
        if base:
            for codec, cell in results[name].items():
                cell["speedup_vs_off"] = cell["gbps"] / base
    return results


def measure_quantized_ici(
    num_executors: int = 4,
    slot_rows: int = 1024,
    lane: int = 128,
    iterations: int = 5,
    modes=("int8", "blockfloat"),
    report=None,
) -> dict:
    """Tier-(b) leg of the ``compress`` mode — quantized vs f32 ICI exchange.

    Builds the stock f32 exchange (float rows bitcast through the int32 lane)
    and ``build_quantized_exchange`` per mode over the same mesh, feeds both
    identical seeded payloads, asserts the dequantized result within the
    spec's per-block error bound (exact for the row sizes/counts), and times
    chained donated iterations.  Effective GB/s counts the LOGICAL f32 bytes
    delivered, so the quantized rows' win is wire-bytes (reported as
    ``wire_reduction``) showing up as throughput.  Requires >= 2 devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.compress import QuantizeSpec
    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange, make_mesh
    from sparkucx_tpu.ops.ici_exchange import build_quantized_exchange

    avail = jax.device_count()
    n = min(num_executors, avail)
    if n < 2:
        raise RuntimeError(f"quantized ici leg needs >=2 devices (have {avail})")
    slot = slot_rows
    send_rows = n * slot
    spec = ExchangeSpec(
        num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane
    )
    mesh = make_mesh(n)
    sharding = NamedSharding(mesh, P("ex", None))
    stock = build_exchange(mesh, spec)

    rng = np.random.default_rng(11)
    sizes_host = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
    data_f32 = rng.standard_normal((n * send_rows, lane), dtype=np.float32)
    sizes = jax.device_put(sizes_host, sharding)
    remote_bytes = n * (n - 1) * slot * lane * 4

    def time_impl(label, fn, make_data):
        best = 0.0
        for it in range(iterations):
            data = jax.device_put(make_data(), sharding)
            t0 = time.perf_counter()
            cur = data
            for _ in range(4):  # chained: donation recycles the buffer
                cur, _ = fn(cur, sizes)
            jax.block_until_ready(cur)
            dt = time.perf_counter() - t0
            best = max(best, 4 * remote_bytes / dt / 1e9)
            if report is not None:
                report(label, n, it, dt, 4 * remote_bytes)
        return best

    # oracle: the exact f32 rows every mode must approximate
    ref, ref_sizes = stock(
        jax.device_put(data_f32.view(np.int32), sharding), sizes
    )
    ref = np.asarray(ref).view(np.float32)
    ref_sizes = np.asarray(ref_sizes)
    stock_gbps = time_impl(
        "f32", stock, lambda: data_f32.view(np.int32)
    )
    out: dict = {"n": n, "f32_gbps": stock_gbps, "modes": {}}
    for mode in modes:
        q = QuantizeSpec(mode=mode, block_size=128)
        qfn = build_quantized_exchange(mesh, spec, q)
        got, got_sizes = qfn(jax.device_put(data_f32, sharding), sizes)
        got = np.asarray(got)
        assert np.array_equal(np.asarray(got_sizes), ref_sizes), (
            f"quantized exchange sizes diverged ({mode})"
        )
        bound = q.error_bound(float(np.abs(data_f32).max()))
        err = float(np.abs(got - ref).max())
        assert err <= bound + 1e-7, (
            f"dequant error {err} above bound {bound} ({mode})"
        )
        mode_gbps = time_impl(mode, qfn, lambda: data_f32)
        out["modes"][mode] = {
            "gbps": mode_gbps,
            "speedup_vs_f32": mode_gbps / max(stock_gbps, 1e-9),
            "wire_reduction": lane / q.quantized_width(lane),
            "max_err": err,
            "err_bound": bound,
        }
    return out


def measure_failover(
    num_blocks: int = 8,
    block_bytes: int = 4 << 20,
    iterations: int = 3,
    report=None,
) -> dict:
    """Measurement core of the ``failover`` mode — fetch throughput through
    executor loss.

    Three loopback executors with ``replication.factor = 1``: executor 1
    stages ``num_blocks`` blocks of ``block_bytes`` and seals (the background
    replicator pushes every round to ring neighbor 2); executor 0 streams the
    set back with a failover-enabled reader.  Phase one measures steady-state
    GB/s over ``iterations`` passes.  Phase two runs one more pass and kills
    executor 1 after half the blocks have landed — the reader re-resolves the
    rest to the replica holder.  Returns steady vs killed GB/s, recovery time
    (kill -> first replica-served block), failover/retry counts, and the worst
    lane's p99 frame stall.  ``report(phase, it, seconds, bytes)`` per pass.
    Shared by the CLI and bench.py."""
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader
    from sparkucx_tpu.shuffle.resolver import ring_neighbors
    from sparkucx_tpu.testing import faults

    conf = TpuShuffleConf(
        replication_factor=1,
        wire_timeout_ms=10_000,
        staging_capacity_per_executor=num_blocks * block_bytes + (1 << 20),
    )
    executors = [0, 1, 2]
    ts = [PeerTransport(conf, executor_id=i) for i in executors]
    addrs = [t.init() for t in ts]
    for t in ts:
        for j, a in enumerate(addrs):
            if j != t.executor_id:
                t.add_executor(j, a)
    total = num_blocks * block_bytes
    try:
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
        ts[1].store.create_shuffle(0, 1, num_blocks)
        w = ts[1].store.map_writer(0, 0)
        for r in range(num_blocks):
            w.write_partition(r, payload)
        w.commit()
        ts[1].store.seal(0)
        assert ts[1].replication_wait(0, timeout=60.0), "replication did not settle"

        def make_reader():
            return TpuShuffleReader(
                ts[0],
                executor_id=0,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_blocks,
                num_mappers=1,
                block_sizes=lambda m, r: block_bytes,
                max_blocks_per_request=1,  # one window per block: the kill
                sender_of=lambda m: 1,     # lands between windows, mid-stream
                replica_of=lambda p: ring_neighbors(p, executors, 1),
                fetch_retries=3,
                fetch_deadline_ms=2000,
                fetch_backoff_ms=10,
            )

        def consume(reader, kill_at=None):
            """Drain the reader; returns (seconds, kill->next-block seconds)."""
            n = 0
            t_kill = recovery = None
            t0 = time.perf_counter()
            for blk in reader.fetch_blocks():
                blk.release()
                n += 1
                if t_kill is not None and recovery is None:
                    recovery = time.perf_counter() - t_kill
                if n == kill_at:
                    t_kill = time.perf_counter()
                    faults.kill_executor(ts[1])
            assert n == num_blocks
            return time.perf_counter() - t0, recovery

        consume(make_reader())  # warmup: connect (+ stripe handshake), page in
        steady = 0.0
        for it in range(iterations):
            dt, _ = consume(make_reader())
            steady = max(steady, total / dt / 1e9)
            if report is not None:
                report("steady", it, dt, total)
        kill_reader = make_reader()
        dt, recovery = consume(kill_reader, kill_at=max(1, num_blocks // 2))
        if report is not None:
            report("killed", 0, dt, total)
        lanes = ts[0].wire_lane_stats()
        return {
            "steady_gbps": steady,
            "killed_gbps": total / dt / 1e9,
            "recovery_ms": (recovery or 0.0) * 1e3,
            "failovers": kill_reader.metrics.failovers,
            "blocks_retried": kill_reader.metrics.blocks_retried,
            "fetch_timeouts": kill_reader.metrics.fetch_timeouts,
            "rx_stall_p99_ms": max(
                (s["rx_stall_p99_ns"] for s in lanes), default=0
            ) / 1e6,
        }
    finally:
        for t in ts:
            t.close()


def measure_gray(
    num_blocks: int = 8,
    block_bytes: int = 4 << 20,
    iterations: int = 3,
    report=None,
) -> dict:
    """Measurement core of the ``gray`` mode — fetch throughput through a
    gray (degraded-but-alive) primary, hedging off vs on.

    Same 3-executor loopback shape as ``failover`` (executor 1 stages +
    seals, the replicator pushes to ring neighbor 2, executor 0 streams the
    set back) — but instead of killing the primary, every frame it serves is
    stalled so its effective rate is ~10% of the measured healthy rate (the
    gray failure the breaker/deadline machinery can't see: the peer answers,
    just slowly).  Three phases over ``iterations`` passes each:

    1. healthy, hedging off — the baseline GB/s,
    2. primary throttled to ~10%, hedging off — the un-hedged collapse,
    3. primary throttled to ~10%, ``fetch.hedgeMs`` on — hedges fire after
       the delay and the replica holder serves the straggling blocks.

    One extra UNCLOCKED hedged pass asserts every delivered block is
    bit-identical to the staged payload (first-completion-wins must never
    surface replica/primary divergence), so the equality check can't pollute
    the timed numbers.  Returns per-phase GB/s + p99 frame stall, hedge
    counters, and the derived per-frame stall.  ``report(phase, it, seconds,
    bytes)`` per timed pass.  Shared by the CLI and bench.py."""
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader
    from sparkucx_tpu.shuffle.resolver import ring_neighbors
    from sparkucx_tpu.testing import faults

    conf = TpuShuffleConf(
        replication_factor=1,
        wire_timeout_ms=60_000,
        staging_capacity_per_executor=num_blocks * block_bytes + (1 << 20),
    )
    executors = [0, 1, 2]
    ts = [PeerTransport(conf, executor_id=i) for i in executors]
    addrs = [t.init() for t in ts]
    for t in ts:
        for j, a in enumerate(addrs):
            if j != t.executor_id:
                t.add_executor(j, a)
    total = num_blocks * block_bytes
    try:
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
        ts[1].store.create_shuffle(0, 1, num_blocks)
        w = ts[1].store.map_writer(0, 0)
        for r in range(num_blocks):
            w.write_partition(r, payload)
        w.commit()
        ts[1].store.seal(0)
        assert ts[1].replication_wait(0, timeout=60.0), "replication did not settle"

        def make_reader(hedge_ms=0):
            return TpuShuffleReader(
                ts[0],
                executor_id=0,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_blocks,
                num_mappers=1,
                block_sizes=lambda m, r: block_bytes,
                max_blocks_per_request=1,  # one window per block: each frame
                sender_of=lambda m: 1,     # the gray primary serves stalls
                replica_of=lambda p: ring_neighbors(p, executors, 1),
                fetch_retries=3,
                fetch_deadline_ms=30_000,  # gray peers answer — no deadline
                fetch_backoff_ms=10,       # trips, hedges do the rescuing
                fetch_hedge_ms=hedge_ms,
                fetch_hedge_max_ms=hedge_ms,
            )

        def consume(reader, collect=None):
            n = 0
            t0 = time.perf_counter()
            for blk in reader.fetch_blocks():
                if collect is not None:
                    collect.append(bytes(blk.data))
                blk.release()
                n += 1
            assert n == num_blocks
            return time.perf_counter() - t0

        def p99_ms():
            return max(
                (s["rx_stall_p99_ns"] for s in ts[0].wire_lane_stats()), default=0
            ) / 1e6

        consume(make_reader())  # warmup: connect, page in
        out: dict = {}
        healthy = 0.0
        for it in range(iterations):
            dt = consume(make_reader())
            healthy = max(healthy, total / dt / 1e9)
            if report is not None:
                report("healthy", it, dt, total)
        out["healthy_gbps"] = healthy
        out["healthy_p99_ms"] = p99_ms()

        # Throttle the primary to ~10%: each served frame sleeps 9x the
        # healthy per-block time, so primary-served traffic runs at a tenth
        # of the measured healthy rate.  The faults registry is process-
        # global — the executor match key pins the stall to server 1 only.
        stall_s = min(max(9.0 * (total / (healthy * 1e9)) / num_blocks, 0.005), 2.0)
        out["frame_stall_ms"] = stall_s * 1e3
        entry = faults.arm(
            "peer.server.frame", faults.stall(stall_s), match={"executor": 1}
        )
        try:
            degraded = 0.0
            for it in range(iterations):
                dt = consume(make_reader())
                degraded = max(degraded, total / dt / 1e9)
                if report is not None:
                    report("throttled", it, dt, total)
            out["degraded_gbps"] = degraded
            out["degraded_p99_ms"] = p99_ms()

            # hedge delay: a fraction of the injected stall, so hedges fire
            # well before the gray primary answers but never on healthy peers
            hedge_ms = max(1, int(stall_s * 1e3 / 4))
            hedged = 0.0
            hedge_reader = None
            for it in range(iterations):
                hedge_reader = make_reader(hedge_ms=hedge_ms)
                dt = consume(hedge_reader)
                hedged = max(hedged, total / dt / 1e9)
                if report is not None:
                    report("hedged", it, dt, total)
            out["hedged_gbps"] = hedged
            out["hedged_p99_ms"] = p99_ms()
            out["hedge_ms"] = hedge_ms
            m = hedge_reader.metrics
            out["hedges_issued"] = m.hedges_issued
            out["hedge_wins"] = m.hedge_wins
            out["hedge_losses"] = m.hedge_losses
            out["fetch_timeouts"] = m.fetch_timeouts

            # bit-equality OUTSIDE the clock: one unclocked hedged pass, every
            # delivered block compared against the staged payload
            got: List[bytes] = []
            consume(make_reader(hedge_ms=hedge_ms), collect=got)
            assert len(got) == num_blocks and all(b == payload for b in got), (
                "hedged read diverged from the staged payload"
            )
            out["bit_identical"] = True
        finally:
            faults.disarm(entry)
        return out
    finally:
        for t in ts:
            t.close()


def measure_tenants(
    num_apps: int = 8,
    num_blocks: int = 8,
    block_bytes: int = 1 << 20,
    iterations: int = 2,
    server_workers: int = 8,
    report=None,
) -> dict:
    """Measurement core of the ``tenants`` mode — the multi-tenant serving
    plane under concurrent fan-in.

    One tenants-enabled loopback server (the shared-selector reactor plane,
    service/reactor.py, ``server_workers`` pool threads) registers
    ``num_apps`` applications in a TenantRegistry and stages ``num_blocks``
    blocks of ``block_bytes`` per app, each under the app's own shuffle-id
    namespace (tenant-local shuffle id 0, translated server-side).  Every app
    then streams its set back concurrently through its own client transport
    — the ``app_id`` rides the FETCH_BLOCK_REQ extension.  The best-aggregate
    pass reports per-app GB/s; latency percentiles pool every per-block fetch
    gap across all apps and iterations.  Returns aggregate GB/s, per-app
    GB/s, the fairness ratio (min/max per-app GB/s — 1.0 is perfectly fair),
    p50/p99 per-block fetch latency, and the registry's usage snapshot.
    ``report(phase, it, seconds, bytes)`` per concurrent pass.  Shared by the
    CLI and bench.py."""
    from sparkucx_tpu.service.tenants import TenantRegistry
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader

    total_per_app = num_blocks * block_bytes
    conf = TpuShuffleConf(
        tenants_enabled=True,
        server_workers=server_workers,
        wire_timeout_ms=10_000,
        staging_capacity_per_executor=num_apps * total_per_app + (1 << 20),
    )
    registry = TenantRegistry()
    server = PeerTransport(conf, executor_id=1)
    server.store.tenants = registry  # before init(): BlockServer captures it
    addr = server.init()
    apps = [f"app-{i:03d}" for i in range(num_apps)]
    clients: List[PeerTransport] = []
    try:
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
        for app in apps:
            registry.register(app, hbm_quota_bytes=2 * total_per_app)
            gsid = registry.sid_for(app, 0)
            server.store.create_shuffle(gsid, 1, num_blocks, app_id=app)
            w = server.store.map_writer(gsid, 0)
            for r in range(num_blocks):
                w.write_partition(r, payload)
            w.commit()
            server.store.seal(gsid)
        for i, app in enumerate(apps):
            c = PeerTransport(conf, executor_id=100 + i)
            c.app_id = app
            c.init()
            c.add_executor(1, addr)
            clients.append(c)

        def make_reader(c):
            # tenant-LOCAL shuffle id 0: the server translates via the wire ext
            return TpuShuffleReader(
                c,
                executor_id=c.executor_id,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_blocks,
                num_mappers=1,
                block_sizes=lambda m, r: block_bytes,
                max_blocks_per_request=1,  # one window per block: per-block latency
                sender_of=lambda m: 1,
                fetch_retries=2,
                fetch_deadline_ms=10_000,
                fetch_backoff_ms=10,
            )

        def drain(c, lat, elapsed, idx):
            t0 = prev = time.perf_counter()
            n = 0
            for blk in make_reader(c).fetch_blocks():
                blk.release()
                now = time.perf_counter()
                lat.append(now - prev)
                prev = now
                n += 1
            assert n == num_blocks
            elapsed[idx] = time.perf_counter() - t0

        for c in clients:  # warmup: connect (+ stripe handshake), page in
            for blk in make_reader(c).fetch_blocks():
                blk.release()

        latencies: List[float] = []
        best_agg = 0.0
        per_app_gbps: dict = {}
        for it in range(iterations):
            lat = [[] for _ in clients]
            elapsed = [0.0] * len(clients)
            threads = [
                threading.Thread(target=drain, args=(c, lat[i], elapsed, i))
                for i, c in enumerate(clients)
            ]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            agg = num_apps * total_per_app / wall / 1e9
            if agg > best_agg:
                best_agg = agg
                per_app_gbps = {
                    app: total_per_app / max(elapsed[i], 1e-12) / 1e9
                    for i, app in enumerate(apps)
                }
            for per_client in lat:
                latencies.extend(per_client)
            if report is not None:
                report("concurrent", it, wall, num_apps * total_per_app)
        lats = np.sort(np.asarray(latencies))
        p50 = float(lats[len(lats) // 2]) * 1e3
        p99 = float(lats[min(len(lats) - 1, int(0.99 * len(lats)))]) * 1e3
        fairness = min(per_app_gbps.values()) / max(max(per_app_gbps.values()), 1e-12)
        return {
            "apps": num_apps,
            "agg_gbps": best_agg,
            "per_app_gbps": per_app_gbps,
            "fairness": fairness,
            "p50_fetch_ms": p50,
            "p99_fetch_ms": p99,
            "tenant_stats": registry.stats(),
        }
    finally:
        for c in clients:
            c.close()
        server.close()


def measure_fanin(
    num_readers: int = 8,
    block_bytes: int = 256 << 10,
    iterations: int = 3,
    widths=(1, 2, 4),
    fetches_per_reader: int = 4,
    serve_stall_ms: float = 2.0,
    report=None,
) -> dict:
    """Measurement core of the ``fanin`` mode — N-reducer fan-in on ONE hot
    block vs the popularity tier's replica-set width.

    Per width ``w``: a fresh loopback cluster of ``w`` servers (primary +
    ``w - 1`` ring successors at ``replication.factor = w - 1``), each with a
    single-worker reactor (``server.workers = 1``) and every FETCH_BLOCK_REQ
    stalled ``serve_stall_ms`` — a deterministic per-request service-time
    ceiling, so one server saturates and the only way up is MORE HOLDERS.
    A bootstrap storm promotes the block past
    ``serve.hotThresholdFetchesPerSec``; the primary then advertises all
    ``w`` holders over HOT_SET_PULL, and ``num_readers`` concurrent reader
    transports (deterministic per-reader rotation) fan their fetches out
    across the set.  The stall is armed AFTER staging/replication and
    disarmed before the off-clock pass, which asserts the block bit-identical
    from EVERY holder.  Returns per-width aggregate GB/s and pooled p99
    per-fetch latency plus the width-max/width-1 speedup.
    ``report(phase, it, seconds, bytes)`` per pass.  Shared by the CLI and
    bench.py."""
    from sparkucx_tpu.core.definitions import AmId
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader
    from sparkucx_tpu.testing import faults

    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
    per_width: dict = {}
    for w in widths:
        conf = TpuShuffleConf(
            replication_factor=w - 1,
            serve_hot_threshold_fetches_per_sec=1.0,
            serve_hot_replicas=w - 1,
            serve_cache_bytes=4 * block_bytes,
            server_workers=1,
            wire_timeout_ms=10_000,
            staging_capacity_per_executor=block_bytes + (1 << 20),
        )
        servers = [PeerTransport(conf, executor_id=i) for i in range(w)]
        addrs = [t.init() for t in servers]
        for t in servers:
            for j, a in enumerate(addrs):
                if j != t.executor_id:
                    t.add_executor(j, a)
        clients: List[PeerTransport] = []
        try:
            servers[0].store.create_shuffle(0, 1, 1)
            mw = servers[0].store.map_writer(0, 0)
            mw.write_partition(0, payload)
            mw.commit()
            servers[0].store.seal(0)
            assert servers[0].replication_wait(0, timeout=60.0)

            for i in range(num_readers):
                c = PeerTransport(conf, executor_id=100 + i)
                c.init()
                c.add_executor(0, addrs[0])
                for j in range(1, w):
                    c.add_executor(j, addrs[j])
                clients.append(c)

            def fetch_once(c, target):
                buf = MemoryBlock(np.zeros(block_bytes, np.uint8), size=block_bytes)
                req = c.fetch_block(target, 0, 0, 0, buf)
                deadline = time.monotonic() + 10.0
                while not req.completed() and time.monotonic() < deadline:
                    c.progress()
                res = req.wait(1)
                assert res.status == OperationStatus.SUCCESS, str(res.error)
                return buf

            # bootstrap storm: back-to-back fetches promote the block and
            # (w > 1) stand up the widened advertisement
            for _ in range(6):
                fetch_once(clients[0], 0).close()
            assert servers[0].popularity.is_hot(0)
            holders = clients[0].hot_holders(0, 0) or [0]
            assert len(holders) == w, f"width {w}: advertised {holders}"

            def make_reader(c):
                return TpuShuffleReader(
                    c,
                    executor_id=c.executor_id,
                    shuffle_id=0,
                    start_partition=0,
                    end_partition=1,
                    num_mappers=1,
                    block_sizes=lambda m, r: block_bytes,
                    max_blocks_per_request=1,
                    sender_of=lambda m: 0,
                    holders_of=c.hot_holders,
                    fetch_retries=2,
                    fetch_deadline_ms=10_000,
                    fetch_backoff_ms=10,
                )

            def drain(c, lat):
                for _ in range(fetches_per_reader):
                    t0 = time.perf_counter()
                    for blk in make_reader(c).fetch_blocks():
                        blk.release()
                    lat.append(time.perf_counter() - t0)

            for c in clients:  # warmup: connect, learn the hot set
                for blk in make_reader(c).fetch_blocks():
                    blk.release()

            # service-time ceiling, armed only for the timed passes
            entry = faults.arm(
                "peer.server.frame",
                faults.stall(serve_stall_ms / 1e3),
                match={"am_id": int(AmId.FETCH_BLOCK_REQ)},
            )
            total = num_readers * fetches_per_reader * block_bytes
            best = 0.0
            latencies: List[float] = []
            for it in range(iterations):
                lat = [[] for _ in clients]
                threads = [
                    threading.Thread(target=drain, args=(c, lat[i]))
                    for i, c in enumerate(clients)
                ]
                t0 = time.perf_counter()
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                wall = time.perf_counter() - t0
                best = max(best, total / wall / 1e9)
                for per_client in lat:
                    latencies.extend(per_client)
                if report is not None:
                    report(f"width-{w}", it, wall, total)
            faults.disarm(entry)

            # off-clock: the same bytes from EVERY advertised holder
            for holder in holders:
                buf = fetch_once(clients[0], holder)
                assert bytes(buf.host_view()[:block_bytes]) == payload, (
                    f"width {w}: holder {holder} served different bytes"
                )
                buf.close()

            lats = np.sort(np.asarray(latencies))
            per_width[w] = {
                "agg_gbps": best,
                "p99_fetch_ms": float(
                    lats[min(len(lats) - 1, int(0.99 * len(lats)))]
                ) * 1e3,
                "holders": holders,
            }
        finally:
            faults.reset()
            for c in clients:
                c.close()
            for t in servers:
                t.close()
    w_lo, w_hi = min(widths), max(widths)
    return {
        "readers": num_readers,
        "block_bytes": block_bytes,
        "per_width": per_width,
        "speedup": per_width[w_hi]["agg_gbps"]
        / max(per_width[w_lo]["agg_gbps"], 1e-12),
    }


def measure_elastic(
    num_executors: int = 4,
    block_bytes: int = 8 << 10,
    iterations: int = 3,
    report=None,
) -> dict:
    """Measurement core of the ``elastic`` mode — collective-exchange
    throughput through an executor death with degraded-mode recovery.

    A ``num_executors``-wide loopback cluster with ``elastic.enabled`` and
    ``replication.factor = 1`` runs 3n x 2n shuffles whose staging budget
    forces multiple collective rounds.  Phase one measures steady-state
    full-mesh exchange GB/s over ``iterations`` fresh shuffles.  Phase two
    stages one more shuffle and kills an executor mid-superstep (the
    ``exchange.submit`` chaos hook): the cluster shrinks to the surviving
    pow2 bucket, restages the dead executor's rounds from its ring
    successor's replicas, and re-runs in degraded waves — output asserted
    byte-identical to the staged payloads.  Returns steady vs shrink-recover
    GB/s plus the recovery telemetry from ``TpuShuffleCluster.elastic_stats``.
    ``report(phase, it, seconds, bytes)`` per pass.  Shared by the CLI and
    bench.py."""
    from sparkucx_tpu.testing import faults
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    n = num_executors
    M, R = 3 * n, 2 * n
    align = 512
    padded = -(-block_bytes // align) * align
    total = M * R * block_bytes

    def mk_cluster():
        conf = TpuShuffleConf(
            num_executors=n,
            elastic=True,
            replication_factor=1,
            block_alignment=align,
            # ~2 maps per staging round: the shuffle spans several collective
            # rounds, so the kill lands mid-superstep with rounds left both
            # to restage from replicas and to re-run on the shrunk mesh
            staging_capacity_per_executor=2 * R * padded,
        )
        return TpuShuffleCluster(conf, num_executors=n)

    def run_once(cluster, shuffle_id, kill=None, verify=False):
        meta = cluster.create_shuffle(shuffle_id, M, R)
        rng = np.random.default_rng(shuffle_id)
        oracle = {}
        for m in range(M):
            t = cluster.transport(meta.map_owner[m])
            w = t.store.map_writer(shuffle_id, m)
            for r in range(R):
                payload = rng.integers(
                    0, 256, size=block_bytes, dtype=np.uint8
                ).tobytes()
                if verify:
                    oracle[(m, r)] = payload
                w.write_partition(r, payload)
            t.commit_block(w.commit().pack())
        if kill is not None:
            def die(**_ctx):
                faults.kill_executor(cluster.transport(kill))

            faults.arm("exchange.submit", die, times=1, match={"round": 1})
        try:
            t0 = time.perf_counter()
            cluster.run_exchange(shuffle_id)
            dt = time.perf_counter() - t0
        finally:
            faults.reset()
        for (m, r), want in oracle.items():
            consumer = meta.owner_of_reduce(r)
            view, length = cluster.locate_received_block(consumer, shuffle_id, m, r)
            assert bytes(view[:length]) == want, "recovered block diverged"
        return dt

    steady = 0.0
    cluster = mk_cluster()
    try:
        run_once(cluster, 0)  # warmup: compile the full-mesh exchange
        for it in range(iterations):
            dt = run_once(cluster, it + 1)
            steady = max(steady, total / dt / 1e9)
            if report is not None:
                report("steady", it, dt, total)
    finally:
        for t in cluster.transports:
            t.close()
    cluster = mk_cluster()
    try:
        # kill the highest executor id: the survivors are the contiguous pow2
        # prefix, the common shrink shape (any id recovers identically)
        dt = run_once(cluster, 0, kill=n - 1, verify=True)
        if report is not None:
            report("shrink", 0, dt, total)
        stats = dict(cluster.elastic_stats)
    finally:
        for t in cluster.transports:
            t.close()
    m_deg, phys = stats["degraded_mesh"] or (0, ())
    return {
        "steady_gbps": steady,
        "degraded_gbps": total / dt / 1e9,
        "recovery_ms": stats["last_recovery_ms"],
        "recoveries": stats["recoveries"],
        "epoch": stats["last_epoch"],
        "degraded_mesh": m_deg,
        "survivors": tuple(phys),
    }


def measure_obs(
    num_blocks: int = 8,
    block_bytes: int = 4 << 20,
    iterations: int = 3,
    report=None,
) -> dict:
    """Measurement core of the ``obs`` mode — telemetry-plane overhead.

    Two loopback executors; executor 1 stages ``num_blocks`` blocks and
    executor 0 streams them back, with ``obs.traceContext`` compiled in but
    the process tracer flipped per leg:

    * ``off``     — tracing AND recording disabled (the always-on flight
      recorder switched off; nothing rides the wire, ``span()`` returns the
      shared no-op singleton);
    * ``ring``    — recording only: the flight recorder's steady-state
      default.  Spans land in the bounded ring, nothing rides the wire.
      The always-on contract is ``ring`` overhead < 1% — asserted here
      against the ACCOUNTED cost (events recorded per pass x measured
      ns/record, over the pass wall time), because a loopback socket's
      run-to-run throughput jitter is itself several percent and would
      swamp a wall-clock delta of microseconds;
    * ``full``    — tracing enabled: span contexts ride FetchBlockReq as the
      trailing ext, the server re-parents serve spans, and afterwards the
      buffers are pulled over TracePull and merged into one event list
      (export timed separately, not inside the fetch loop).

    Also times the disabled-``span()`` fast path (ns/call).  Returns GB/s per
    leg, overhead percentages, the fast-path cost, and the merged-export
    stats.  ``report(leg, it, seconds, bytes)`` per pass.  Shared by the CLI
    and bench.py."""
    from sparkucx_tpu.shuffle.reader import TpuShuffleReader
    from sparkucx_tpu.utils.trace import TRACER, merge_events, span

    conf = TpuShuffleConf(
        obs_trace_context=True,
        staging_capacity_per_executor=num_blocks * block_bytes + (1 << 20),
    )
    executors = [0, 1]
    ts = [PeerTransport(conf, executor_id=i) for i in executors]
    addrs = [t.init() for t in ts]
    for t in ts:
        for j, a in enumerate(addrs):
            if j != t.executor_id:
                t.add_executor(j, a)
    total = num_blocks * block_bytes
    saved = (TRACER.enabled, TRACER.recording)
    try:
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes()
        ts[1].store.create_shuffle(0, 1, num_blocks)
        w = ts[1].store.map_writer(0, 0)
        for r in range(num_blocks):
            w.write_partition(r, payload)
        w.commit()
        ts[1].store.seal(0)

        def make_reader():
            return TpuShuffleReader(
                ts[0],
                executor_id=0,
                shuffle_id=0,
                start_partition=0,
                end_partition=num_blocks,
                num_mappers=1,
                block_sizes=lambda m, r: block_bytes,
                max_blocks_per_request=1,  # one window per block: every block
                sender_of=lambda m: 1,     # fetch is its own read.window span
            )

        def consume():
            n = 0
            t0 = time.perf_counter()
            for blk in make_reader().fetch_blocks():
                blk.release()
                n += 1
            assert n == num_blocks
            return time.perf_counter() - t0

        # disabled-span fast path: one attribute check + the shared singleton
        TRACER.enabled = False
        TRACER.recording = False
        calls = 200_000
        t0 = time.perf_counter()
        for _ in range(calls):
            with span("bench.noop"):
                pass
        span_disabled_ns = (time.perf_counter() - t0) / calls * 1e9

        consume()  # warmup: connect, page in

        def leg(name, enabled, recording):
            TRACER.clear()
            TRACER.enabled = enabled
            TRACER.recording = recording
            # both transports share ``conf``: the ext rides only on the full
            # leg, so ``ring`` measures exactly the always-on default
            conf.obs_trace_context = enabled
            best_dt = float("inf")
            for it in range(iterations):
                dt = consume()
                best_dt = min(best_dt, dt)
                if report is not None:
                    report(name, it, dt, total)
            return best_dt, len(TRACER.events)

        off_dt, _ = leg("off", False, False)
        ring_dt, ring_events = leg("ring", False, True)
        full_dt, _ = leg("full", True, True)
        off = total / off_dt / 1e9
        ring = total / ring_dt / 1e9
        full = total / full_dt / 1e9

        # the full leg's export (while its events are still in the ring):
        # pull the server's buffer over the TracePull AM and merge with the
        # local ring — ONE event list, two pids
        t0 = time.perf_counter()
        remote = ts[0].pull_trace(1)
        merged = merge_events([TRACER.events, remote["events"]])
        export_ms = (time.perf_counter() - t0) * 1e3

        # record-path cost: time actual ring appends while recording
        TRACER.clear()
        TRACER.enabled = False
        TRACER.recording = True
        calls = 50_000
        t0 = time.perf_counter()
        for _ in range(calls):
            with span("bench.record"):
                pass
        span_record_ns = (time.perf_counter() - t0) / calls * 1e9

        # the always-on contract: the recorder's accounted steady-state cost
        # (events it records per pass x the measured cost of recording one)
        # must be < 1% of the pass — the wall-clock ring-vs-off delta is also
        # reported but NOT asserted on, since loopback jitter exceeds 1%
        events_per_pass = ring_events / max(iterations, 1)
        ring_overhead = events_per_pass * span_record_ns / (ring_dt * 1e9)
        assert ring_overhead < 0.01, (
            f"always-on recorder overhead {ring_overhead * 100:.3f}% >= 1% "
            f"({events_per_pass:.0f} events/pass x {span_record_ns:.0f} ns "
            f"over {ring_dt * 1e3:.1f} ms)"
        )

        return {
            "off_gbps": off,
            "ring_gbps": ring,
            "full_gbps": full,
            "ring_overhead_pct": ring_overhead * 100.0,
            "ring_wall_delta_pct": (1.0 - ring / max(off, 1e-9)) * 100.0,
            "full_wall_delta_pct": (1.0 - full / max(off, 1e-9)) * 100.0,
            "events_per_pass": events_per_pass,
            "span_record_ns": span_record_ns,
            "span_disabled_ns": span_disabled_ns,
            "export_ms": export_ms,
            "merged_events": len(merged),
            "merged_pids": len({e.get("pid") for e in merged}),
        }
    finally:
        TRACER.enabled, TRACER.recording = saved
        TRACER.clear()
        for t in ts:
            t.close()


def measure_pipeline(
    executors: int, round_bytes: int, rounds: int, iterations: int,
    depths=(1, 2, 3), report=None,
) -> dict:
    """Measurement core of the ``pipeline`` mode — multi-round (spilled)
    shuffle throughput WITH host staging in the loop, at several pipeline
    depths.  Unlike ``superstep`` (HBM-resident payloads chained K deep),
    every round here pays the full H2D -> collective -> D2H path the spill
    engine drives; depth d overlaps round k's collective with round k+1's
    staging and round k-1's drain (transport/pipeline.py — the tentpole
    overlap).  Returns ``{depth: best GB/s of payload moved}``;
    ``report(depth, it, seconds, bytes)`` is called per iteration when given.
    Shared by the CLI and bench.py."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import (
        ExchangeSpec, bucket_send_rows, build_exchange, make_mesh,
    )
    from sparkucx_tpu.transport.pipeline import RoundPipeline

    n = executors
    rows_per_peer = max(1, round_bytes // (512 * n))
    send_rows = bucket_send_rows(n * rows_per_peer, n)
    spec = ExchangeSpec(
        num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=128
    )
    mesh = make_mesh(n)
    fn = build_exchange(mesh, spec)
    sharding = NamedSharding(mesh, P("ex", None))
    rng = np.random.default_rng(0)
    host_rounds = [
        rng.integers(-100, 100, size=(n * send_rows, 128), dtype=np.int32)
        for _ in range(rounds)
    ]
    sizes = np.full((n, n), rows_per_peer, dtype=np.int32)
    moved_per_round = n * n * rows_per_peer * 512
    results = {}
    for depth in depths:
        size_mat = jax.device_put(sizes, sharding)  # never donated: hoist

        def submit(rnd):
            data = jax.device_put(host_rounds[rnd], sharding)  # H2D (async)
            recv, _ = fn(data, size_mat)                       # collective
            shards = [s.data for s in recv.addressable_shards]
            for a in shards:
                a.copy_to_host_async()                         # D2H kick-off
            return shards

        def drain(rnd, shards):
            for a in shards:
                np.asarray(a)  # observe completion: materialize host-side
            return None

        pipe = RoundPipeline(depth, submit, drain, name=f"bench.pipeline.d{depth}")
        pipe.run(rounds)  # warmup: compile + first H2D/D2H
        best = 0.0
        for it in range(iterations):
            t0 = time.perf_counter()
            pipe.run(rounds)
            dt = time.perf_counter() - t0
            tot = moved_per_round * rounds
            best = max(best, tot / dt / 1e9)
            if report is not None:
                report(depth, it, dt, tot)
        results[depth] = best
    return results


def measure_gather(
    num_blocks: int,
    block_bytes: int,
    iterations: int,
    outstanding: int,
    impl: str | None = None,
    report=None,
) -> float:
    """Measurement core of the ``gather`` mode — device-side ragged block gather
    (the reply-packing hot path, UcxWorkerWrapper.scala:397-448 analogue).
    Returns best GB/s across iterations; ``report(it, seconds, bytes, impl)`` is
    called per iteration when given.  Shared by the CLI and bench.py."""
    import jax

    from sparkucx_tpu.ops.pallas_kernels import build_block_gather, pack_plan

    row = 512
    rows_each = max(1, block_bytes // row)
    b = num_blocks
    # blocks scattered at 2x stride through the source (every other slot used)
    src_rows = 2 * b * rows_each
    rng = np.random.default_rng(0)
    src = jax.device_put(
        rng.integers(-100, 100, size=(src_rows, row // 4), dtype=np.int32)
    )
    plan = [(2 * i * rows_each * row, rows_each * row) for i in range(b)]
    starts, counts, outs, total = pack_plan(plan, row)
    fn = build_block_gather(b, total, impl=impl)
    dev = src.device
    sargs = tuple(jax.device_put(a, dev) for a in (starts, counts, outs))
    out = jax.block_until_ready(fn(*sargs, src))  # compile
    assert np.array_equal(np.asarray(out[:rows_each]), np.asarray(src[:rows_each]))
    moved = total * row
    best = 0.0
    for it in range(iterations):
        t0 = time.perf_counter()
        for _ in range(outstanding):
            out = fn(*sargs, src)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        tot = moved * outstanding
        best = max(best, tot / dt / 1e9)
        if report is not None:
            report(it, dt, tot, fn.impl)
    return best


def run_wire(args) -> None:
    size = parse_size(args.block_size)
    streams_list = tuple(int(s) for s in args.streams.split(","))

    def report(streams, it, dt, tot):
        print(
            f"streams {streams} iter {it}: {args.num_blocks} x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    results = measure_wire(
        streams_list, args.num_blocks, size, args.iterations,
        chunk_bytes=parse_size(args.chunk_bytes), report=report,
    )
    base = results.get(1, {}).get("gbps")
    for streams, r in sorted(results.items()):
        speedup = (
            f" ({r['gbps'] / base:.2f}x vs streams=1)"
            if base and streams != 1
            else ""
        )
        print(
            f"wire streams {streams}: {r['gbps']:.2f} GB/s, "
            f"{r['syscalls_per_mb']:.1f} syscalls/MB, "
            f"p99 frame stall {r['p99_frame_stall_ms']:.2f} ms{speedup}",
            flush=True,
        )


def run_compress(args) -> None:
    size = parse_size(args.block_size)

    def report(label, it, dt, tot):
        print(
            f"{label} iter {it}: {tot} B in {dt*1e3:.1f} ms = "
            f"{tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    results = measure_compress(
        num_blocks=args.num_blocks,
        block_bytes=size,
        iterations=args.iterations,
        chunk_bytes=parse_size(args.chunk_bytes),
        streams=int(args.streams.split(",")[0]),
        report=report,
    )
    for name, row in results.items():
        for codec, r in row.items():
            speed = (
                f" ({r['speedup_vs_off']:.2f}x vs off)"
                if codec != "off" and "speedup_vs_off" in r
                else ""
            )
            e2e = f", e2e {r['e2e_gbps']:.2f} GB/s" if "e2e_gbps" in r else ""
            print(
                f"compress {name:9s} codec={codec:5s}: {r['gbps']:.2f} GB/s"
                f"{speed}, ratio {r['ratio']:.2f}x "
                f"({r['encoded_chunks']} enc / {r['raw_chunks']} raw chunks, "
                f"{r['pool_hits']} pool hits){e2e}",
                flush=True,
            )
    try:
        q = measure_quantized_ici(
            num_executors=args.executors if args.executors > 1 else 4,
            iterations=args.iterations,
        )
    except RuntimeError as e:
        print(f"quantized ici leg skipped: {e}", flush=True)
        return
    print(f"quantized ici n={q['n']}: f32 {q['f32_gbps']:.2f} GB/s", flush=True)
    for mode, m in q["modes"].items():
        print(
            f"quantized ici {mode}: {m['gbps']:.2f} GB/s "
            f"({m['speedup_vs_f32']:.2f}x vs f32), "
            f"wire bytes {m['wire_reduction']:.2f}x fewer, "
            f"max err {m['max_err']:.3g} <= bound {m['err_bound']:.3g}",
            flush=True,
        )


def run_failover(args) -> None:
    size = parse_size(args.block_size)

    def report(phase, it, dt, tot):
        print(
            f"{phase} iter {it}: {args.num_blocks} x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_failover(args.num_blocks, size, args.iterations, report=report)
    ratio = r["killed_gbps"] / max(r["steady_gbps"], 1e-9)
    print(
        f"failover: steady {r['steady_gbps']:.2f} GB/s, "
        f"primary killed at t=50% {r['killed_gbps']:.2f} GB/s ({ratio:.2f}x), "
        f"recovery {r['recovery_ms']:.1f} ms, "
        f"{r['failovers']} failovers / {r['blocks_retried']} retried / "
        f"{r['fetch_timeouts']} timeouts, "
        f"p99 frame stall {r['rx_stall_p99_ms']:.2f} ms",
        flush=True,
    )


def run_gray(args) -> None:
    size = parse_size(args.block_size)

    def report(phase, it, dt, tot):
        print(
            f"{phase} iter {it}: {args.num_blocks} x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_gray(args.num_blocks, size, args.iterations, report=report)
    collapse = r["degraded_gbps"] / max(r["healthy_gbps"], 1e-9)
    rescue = r["hedged_gbps"] / max(r["healthy_gbps"], 1e-9)
    print(
        f"gray: healthy {r['healthy_gbps']:.2f} GB/s (p99 stall "
        f"{r['healthy_p99_ms']:.2f} ms); primary throttled to ~10% "
        f"({r['frame_stall_ms']:.1f} ms/frame): hedging off "
        f"{r['degraded_gbps']:.2f} GB/s ({collapse:.2f}x, p99 "
        f"{r['degraded_p99_ms']:.2f} ms), hedging on ({r['hedge_ms']} ms) "
        f"{r['hedged_gbps']:.2f} GB/s ({rescue:.2f}x, p99 "
        f"{r['hedged_p99_ms']:.2f} ms), "
        f"{r['hedges_issued']} hedges / {r['hedge_wins']} wins / "
        f"{r['hedge_losses']} losses / {r['fetch_timeouts']} timeouts, "
        f"bit-identical {r['bit_identical']}",
        flush=True,
    )


def run_tenants(args) -> None:
    size = parse_size(args.block_size)

    def report(phase, it, dt, tot):
        print(
            f"{phase} iter {it}: {args.apps} apps x {args.num_blocks} x {size} B "
            f"in {dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_tenants(
        num_apps=args.apps,
        num_blocks=args.num_blocks,
        block_bytes=size,
        iterations=args.iterations,
        report=report,
    )
    print(
        f"tenants: {r['apps']} apps, aggregate {r['agg_gbps']:.2f} GB/s, "
        f"fairness {r['fairness']:.2f} (min/max per-app GB/s), "
        f"p50 fetch {r['p50_fetch_ms']:.2f} ms, "
        f"p99 fetch {r['p99_fetch_ms']:.2f} ms",
        flush=True,
    )
    for app, gbps in sorted(r["per_app_gbps"].items()):
        used = r["tenant_stats"].get(app, {}).get("used_bytes", 0)
        print(f"tenants   {app}: {gbps:.3f} GB/s, hbm used {used} B", flush=True)


def measure_queries(
    num_apps: int = 4,
    queries_per_app: int = 5,
    rows_per_query: int = 2000,
    keys: int = 64,
    report=None,
) -> dict:
    """Measurement core of the ``queries`` mode — M concurrent tenant DAGs
    with repeated sub-DAGs through the query runner (sparkucx_tpu/query).

    Each of ``num_apps`` tenants drives ``queries_per_app`` repetitions of a
    GroupByTest-shaped DAG (scan -> hash exchange -> grouped aggregate) over
    its own input, one thread per tenant, twice: a COLD pass on a cache-less
    manager (every exchange executes — the baseline a cache-less runner
    pays) and a CACHED pass with ``query.cacheEnabled`` on a shared
    LineageCache, where every repeat after the first serves the sealed
    shuffle straight from the store tiers and skips the exchange entirely.
    Asserts every cached-hit result bit-identical to the cold pass off the
    clock.  Returns cold/warm queries-per-second, the measured hit rate,
    p50/p99 per-stage latency for both passes, and the tenant usage
    snapshot.  ``report(phase, app_idx, seconds, queries)`` per tenant
    drain.  Shared by the CLI and bench.py."""
    import jax

    from sparkucx_tpu.query import LineageCache, QueryRunner, Stage, StageDag
    from sparkucx_tpu.service.tenants import TenantRegistry
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    num_executors = max(1, min(4, jax.device_count()))
    dag = StageDag(
        [
            Stage.make("src", "scan"),
            Stage.make("ex", "exchange", ["src"]),
            Stage.make("agg", "aggregate", ["ex"]),
        ]
    )
    apps = [f"app-{i:03d}" for i in range(num_apps)]
    rng = np.random.default_rng(7)
    inputs = {
        app: [
            (int(k), int(v))
            for k, v in zip(
                rng.integers(0, keys, rows_per_query),
                rng.integers(0, 1 << 20, rows_per_query),
            )
        ]
        for app in apps
    }

    def _conf(cache_on: bool) -> TpuShuffleConf:
        return TpuShuffleConf(
            staging_capacity_per_executor=8 << 20,
            num_executors=num_executors,
            query_cache_enabled=cache_on,
        )

    def _pass(cache_on: bool, phase: str):
        mgr = TpuShuffleManager(_conf(cache_on), num_executors=num_executors)
        registry = TenantRegistry()
        cache = LineageCache() if cache_on else None
        try:
            stage_ms: List[float] = []
            stage_lock = threading.Lock()
            results: dict = {}
            runners = {}
            for app in apps:
                r = QueryRunner(mgr, app, tenants=registry, cache=cache)

                def observe(name, op, ms):
                    with stage_lock:
                        stage_ms.append(ms)

                r.on_stage = observe
                runners[app] = r
            # warmup: compile the exchange path once, off the clock
            runners[apps[0]].run(dag, {"src": inputs[apps[0]]})

            def drain(app):
                t0 = time.perf_counter()
                outs = [
                    runners[app].run(dag, {"src": inputs[app]})
                    for _ in range(queries_per_app)
                ]
                dt = time.perf_counter() - t0
                results[app] = (outs, dt)
                if report is not None:
                    report(phase, app, dt, queries_per_app)

            threads = [threading.Thread(target=drain, args=(app,)) for app in apps]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            qps = num_apps * queries_per_app / wall
            lat = np.sort(np.asarray(stage_ms))
            p50 = float(lat[len(lat) // 2])
            p99 = float(lat[min(len(lat) - 1, int(0.99 * len(lat)))])
            hits = misses = 0
            if cache is not None:
                snap = cache.snapshot()
                hits, misses = snap["cache_hits"], snap["cache_misses"]
            return {
                "qps": qps,
                "p50_stage_ms": p50,
                "p99_stage_ms": p99,
                "hits": hits,
                "misses": misses,
                "results": {app: results[app][0] for app in apps},
                "tenant_stats": registry.stats(),
            }
        finally:
            mgr.stop()

    cold = _pass(False, "cold")
    warm = _pass(True, "cached")
    for app in apps:
        # every cached-hit result bit-identical to cold execution
        assert warm["results"][app] == cold["results"][app], f"{app} result drift"
    total = warm["hits"] + warm["misses"]
    return {
        "apps": num_apps,
        "queries_per_app": queries_per_app,
        "executors": num_executors,
        "cold_qps": cold["qps"],
        "warm_qps": warm["qps"],
        "speedup": warm["qps"] / max(cold["qps"], 1e-12),
        "hit_rate": warm["hits"] / max(total, 1),
        "cold_p99_stage_ms": cold["p99_stage_ms"],
        "p50_stage_ms": warm["p50_stage_ms"],
        "p99_stage_ms": warm["p99_stage_ms"],
        "tenant_stats": warm["tenant_stats"],
        "bit_identical": True,
    }


def run_queries(args) -> None:
    def report(phase, app, dt, n):
        print(
            f"{phase} {app}: {n} queries in {dt*1e3:.1f} ms "
            f"= {n / dt:.1f} q/s",
            flush=True,
        )

    r = measure_queries(
        num_apps=args.apps,
        queries_per_app=args.iterations,
        rows_per_query=args.keys * 32,
        keys=args.keys,
        report=report,
    )
    print(
        f"queries: {r['apps']} apps x {r['queries_per_app']} queries, "
        f"cold {r['cold_qps']:.1f} q/s -> cached {r['warm_qps']:.1f} q/s "
        f"({r['speedup']:.2f}x at {r['hit_rate']:.0%} hit rate), "
        f"p99 stage {r['cold_p99_stage_ms']:.2f} -> {r['p99_stage_ms']:.2f} ms, "
        f"hit results bit-identical",
        flush=True,
    )
    for app, st in sorted(r["tenant_stats"].items()):
        print(
            f"queries   {app}: hbm charged {st['used_bytes']} B "
            f"(cached rounds stay on the tenant's quota)",
            flush=True,
        )


def run_fanin(args) -> None:
    size = parse_size(args.block_size)
    readers = args.threads if args.threads > 1 else 8

    def report(phase, it, dt, tot):
        print(
            f"{phase} iter {it}: {readers} readers x 1 hot block x {size} B "
            f"in {dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_fanin(
        num_readers=readers,
        block_bytes=size,
        iterations=args.iterations,
        report=report,
    )
    for w, m in sorted(r["per_width"].items()):
        print(
            f"fanin width {w}: {m['agg_gbps']:.2f} GB/s aggregate, "
            f"p99 fetch {m['p99_fetch_ms']:.2f} ms, holders {m['holders']}",
            flush=True,
        )
    print(
        f"fanin: width-{max(r['per_width'])} / width-{min(r['per_width'])} "
        f"speedup {r['speedup']:.2f}x, bit-identical from every holder",
        flush=True,
    )


def run_elastic(args) -> None:
    size = parse_size(args.block_size)
    n = args.executors if args.executors > 1 else 4

    def report(phase, it, dt, tot):
        print(
            f"{phase} iter {it}: {3*n}x{2*n} x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_elastic(n, size, args.iterations, report=report)
    ratio = r["degraded_gbps"] / max(r["steady_gbps"], 1e-9)
    print(
        f"elastic: steady {r['steady_gbps']:.2f} GB/s, "
        f"killed mid-superstep {r['degraded_gbps']:.2f} GB/s ({ratio:.2f}x), "
        f"recovery {r['recovery_ms']:.1f} ms "
        f"(epoch {r['epoch']}, mesh {n} -> {r['degraded_mesh']} "
        f"on {list(r['survivors'])}), "
        f"{r['recoveries']} recoveries, bit-identical asserted",
        flush=True,
    )


def run_obs(args) -> None:
    size = parse_size(args.block_size)

    def report(leg, it, dt, tot):
        print(
            f"{leg} iter {it}: {args.num_blocks} x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_obs(args.num_blocks, size, args.iterations, report=report)
    print(
        f"obs: off {r['off_gbps']:.2f} GB/s, "
        f"ring-only {r['ring_gbps']:.2f} GB/s, "
        f"full export {r['full_gbps']:.2f} GB/s; "
        f"always-on recorder {r['events_per_pass']:.0f} events/pass x "
        f"{r['span_record_ns']:.0f} ns = {r['ring_overhead_pct']:.3f}% "
        f"accounted overhead (<1% asserted; wall delta "
        f"{r['ring_wall_delta_pct']:+.1f}% ring / "
        f"{r['full_wall_delta_pct']:+.1f}% full, loopback jitter included), "
        f"disabled span() {r['span_disabled_ns']:.0f} ns/call, "
        f"TracePull merge {r['merged_events']} events from "
        f"{r['merged_pids']} executors in {r['export_ms']:.1f} ms",
        flush=True,
    )


def run_pipeline(args) -> None:
    size = parse_size(args.block_size)
    depths = tuple(int(d) for d in args.depths.split(","))

    def report(depth, it, dt, tot):
        print(
            f"depth {depth} iter {it}: {args.num_blocks} rounds x {size} B in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    results = measure_pipeline(
        args.executors, size, args.num_blocks, args.iterations,
        depths=depths, report=report,
    )
    base = results.get(1)
    for depth, gbps in sorted(results.items()):
        speedup = f" ({gbps / base:.2f}x vs serial)" if base and depth != 1 else ""
        print(f"pipeline depth {depth}: {gbps:.2f} GB/s{speedup}", flush=True)


def run_gather(args) -> None:
    size = parse_size(args.block_size)
    rows_each = max(1, size // 512)

    def report(it, dt, tot, impl):
        print(
            f"iter {it}: {args.num_blocks} blocks x {rows_each * 512} B packed "
            f"{args.outstanding}x: {tot} bytes in {dt*1e3:.1f} ms = "
            f"{tot / dt / 1e9:.2f} GB/s [impl={impl}]",
            flush=True,
        )

    measure_gather(
        args.num_blocks,
        size,
        args.iterations,
        args.outstanding,
        impl=None if args.impl == "auto" else args.impl,
        report=report,
    )


def measure_write(
    num_blocks: int,
    block_bytes: int,
    iterations: int,
    impls=("host", "device"),
    report=None,
) -> dict:
    """Measurement core of the ``write`` mode — map-output staging throughput,
    host byte path vs device staging path (ISSUE 2's tentpole comparison).

    ``host``: ``MapWriter.write_partition`` copies bytes into host staging and
    ``seal`` uploads the whole buffer H2D — the reference-faithful shape
    (NvkvHandler.scala:213-242 pinned-buffer staging).  ``device``:
    ``write_partition_device`` keeps the blocks device-resident and ``seal``
    places them with the block-scatter kernel, returning the HBM payload with
    no host round trip.  One map task writes ``num_blocks`` partitions of
    ``block_bytes`` each into a fresh shuffle per iteration; the clock covers
    write -> seal -> payload ready.  Returns ``{impl: best GB/s}``;
    ``report(impl, it, seconds, bytes)`` per iteration.  Shared by the CLI and
    bench.py."""
    import jax

    from sparkucx_tpu.store.hbm_store import HbmBlockStore

    row = 512
    rows_each = max(1, block_bytes // row)
    total = num_blocks * rows_each * row
    conf = TpuShuffleConf(
        device_staging=True,
        staging_capacity_per_executor=max(2 * total, 1 << 20),
        spill_to_disk=False,
    )
    device = jax.devices()[0]
    rng = np.random.default_rng(0)
    host_blocks = [
        rng.integers(0, 256, size=rows_each * row, dtype=np.uint8).tobytes()
        for _ in range(num_blocks)
    ]
    dev_blocks = [
        jax.device_put(
            np.frombuffer(b, np.uint8).view(np.int32).reshape(rows_each, row // 4),
            device,
        )
        for b in host_blocks
    ]
    jax.block_until_ready(dev_blocks)
    results = {}
    for impl in impls:
        if impl not in ("host", "device"):
            raise ValueError(f"unknown write impl {impl!r} (host|device)")
        store = HbmBlockStore(conf, device=device)
        best = 0.0
        for it in range(iterations + 1):  # iteration 0 = warmup (compiles)
            sid = it
            store.create_shuffle(sid, 1, num_blocks)
            t0 = time.perf_counter()
            w = store.map_writer(sid, 0)
            for r in range(num_blocks):
                if impl == "host":
                    w.write_partition(r, host_blocks[r])
                else:
                    w.write_partition_device(r, dev_blocks[r])
            w.commit()
            payload = store.seal(sid)[-1][0]
            jax.block_until_ready(payload)
            dt = time.perf_counter() - t0
            store.remove_shuffle(sid)
            if it == 0:
                continue
            best = max(best, total / dt / 1e9)
            if report is not None:
                report(impl, it - 1, dt, total)
        results[impl] = best
    return results


def zipf_size_matrix(executors: int, max_peer_rows: int, alpha: float) -> np.ndarray:
    """A deterministic Zipf-skewed exchange size matrix: ``sizes[i, j]`` rows
    from sender i to destination j follow ``(rank + 1) ** -alpha`` scaled so
    each sender's hottest lane is ``max_peer_rows`` (min 1 row), with the rank
    order permuted per sender (seeded) so the hot destination varies — the
    shape real shuffle workloads take (ISSUE: TPC-DS/TPC-H are Zipf-skewed)."""
    n = executors
    rng = np.random.default_rng(0)
    weights = (np.arange(1, n + 1, dtype=np.float64)) ** (-alpha)
    base = np.maximum(1, np.round(max_peer_rows * weights / weights[0])).astype(np.int64)
    sizes = np.empty((n, n), dtype=np.int32)
    for i in range(n):
        sizes[i] = base[rng.permutation(n)]
    return sizes


def measure_skew(
    executors: int, max_peer_rows: int, iterations: int,
    zipf_alpha: float = 1.2, quota_rows: int = 0, report=None,
) -> dict:
    """Measurement core of the ``skew`` mode — the quota-capped plan
    (ops/skew.py) vs the max-sized single-shot plan on a Zipf-skewed shuffle.

    The max plan stages every peer slot at the hottest lane's pow2 bucket (the
    ``bucket_send_rows`` behavior the quota exists to cap): one exchange, most
    of it padding.  The quota plan caps the slot at ``quota_rows`` (0 = the
    pow2 ceiling of the mean lane size) and chunks hot lanes across sub-round
    exchanges.  Both produce bit-identical receive bytes (asserted); the
    returned dict carries effective GB/s (useful bytes / wall time), staged
    rows, dense-lowering wire bytes, and padding fraction per plan — the
    measured table in docs/PERF.md.  ``report(plan, it, seconds, bytes)`` per
    iteration.  Shared by the CLI and bench.py."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import (
        ExchangeSpec, bucket_send_rows, build_exchange, make_mesh,
    )
    from sparkucx_tpu.ops.skew import (
        chunk_size_rows, plan_exchange, quota_slot_rows, reassemble_round,
        slice_subround,
    )

    n = executors
    row_bytes = 512
    lane = row_bytes // 4
    sizes = zipf_size_matrix(n, max_peer_rows, zipf_alpha)
    slot = bucket_send_rows(int(sizes.max()) * n, n) // n  # the max plan's slot
    if quota_rows <= 0:
        quota_rows = int(quota_slot_rows(slot, int(np.ceil(sizes.mean()))))
    plan = plan_exchange([int(sizes.max())], slot, quota_rows)
    q = plan.slot_rows

    mesh = make_mesh(n)
    sharding = NamedSharding(mesh, P("ex", None))
    rng = np.random.default_rng(1)
    # slot-layout staging payload per sender, hot lanes filled to their size
    payloads = []
    for i in range(n):
        p = np.zeros((n * slot, lane), dtype=np.int32)
        for j in range(n):
            p[j * slot : j * slot + sizes[i, j]] = rng.integers(
                -100, 100, size=(int(sizes[i, j]), lane), dtype=np.int32
            )
        payloads.append(p)
    used_rows = int(sizes.sum())
    useful_bytes = used_rows * row_bytes

    def run_max():
        spec = ExchangeSpec(
            num_executors=n, send_rows=n * slot, recv_rows=n * slot, lane=lane
        )
        fn = build_exchange(mesh, spec)
        size_mat = jax.device_put(sizes, sharding)
        data_host = np.concatenate(payloads)

        def shot():
            data = jax.device_put(data_host, sharding)
            recv, rs = fn(data, size_mat)
            jax.block_until_ready(recv)
            return recv, rs

        recv, rs = shot()  # warmup/compile + the oracle output
        rs_host = np.asarray(rs)
        devices = list(mesh.devices.reshape(-1))
        by_device = {s.device: s.data for s in recv.addressable_shards}
        shards = [
            np.asarray(by_device[devices[j]]).reshape(-1).view(np.uint8)[
                : int(rs_host[j].sum()) * row_bytes
            ]
            for j in range(n)
        ]
        best = 0.0
        for it in range(iterations):
            t0 = time.perf_counter()
            shot()
            dt = time.perf_counter() - t0
            best = max(best, useful_bytes / dt / 1e9)
            if report is not None:
                report("max", it, dt, useful_bytes)
        staged = n * n * slot
        return shards, best, staged

    def run_quota():
        spec = ExchangeSpec(
            num_executors=n, send_rows=n * q, recv_rows=n * q, lane=lane
        )
        fn = build_exchange(mesh, spec)
        nchunks = plan.chunks_per_round[0]
        sub_size_mats = [
            np.stack([chunk_size_rows(sizes[i], c, q) for i in range(n)])
            for c in range(nchunks)
        ]
        size_mats = [jax.device_put(m, sharding) for m in sub_size_mats]

        def shot():
            outs = []
            for c in range(nchunks):
                data = jax.device_put(
                    np.concatenate(
                        [slice_subround(p, n, c, q) for p in payloads]
                    ),
                    sharding,
                )
                recv, _ = fn(data, size_mats[c])
                outs.append(recv)
            jax.block_until_ready(outs[-1])
            return outs

        outs = shot()  # warmup/compile + the compared output
        devices = list(mesh.devices.reshape(-1))
        shards = []
        for j in range(n):
            # consumer j reassembles from column j (rows j received per sender)
            sub_sizes = [m[:, j] for m in sub_size_mats]
            sub_shards = [
                np.asarray(
                    next(s.data for s in o.addressable_shards if s.device == devices[j])
                ).reshape(-1).view(np.uint8)
                for o in outs
            ]
            shards.append(reassemble_round(sub_shards, sub_sizes, row_bytes))
        best = 0.0
        for it in range(iterations):
            t0 = time.perf_counter()
            shot()
            dt = time.perf_counter() - t0
            best = max(best, useful_bytes / dt / 1e9)
            if report is not None:
                report("quota", it, dt, useful_bytes)
        return shards, best, plan.staged_rows(n)

    max_shards, max_gbps, max_staged = run_max()
    quota_shards, quota_gbps, quota_staged = run_quota()
    for j in range(n):
        assert bytes(quota_shards[j]) == bytes(max_shards[j]), (
            f"quota plan diverged from single-shot on consumer {j}"
        )
    return {
        "executors": n,
        "zipf_alpha": zipf_alpha,
        "max_peer_rows": int(sizes.max()),
        "quota_slot": q,
        "subrounds": plan.num_subrounds,
        "used_rows": used_rows,
        "bit_identical": True,
        "max": {
            "gbps": max_gbps,
            "staged_rows": max_staged,
            "wire_bytes": max_staged * row_bytes,
            "padding_fraction": 1.0 - used_rows / max_staged,
        },
        "quota": {
            "gbps": quota_gbps,
            "staged_rows": quota_staged,
            "wire_bytes": quota_staged * row_bytes,
            "padding_fraction": 1.0 - used_rows / quota_staged,
        },
    }


def run_skew(args) -> None:
    size = parse_size(args.block_size)
    max_peer_rows = max(1, size // 512)

    def report(plan, it, dt, tot):
        print(
            f"{plan} iter {it}: {tot} useful bytes in {dt*1e3:.1f} ms = "
            f"{tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_skew(
        args.executors, max_peer_rows, args.iterations,
        zipf_alpha=args.zipf_alpha, quota_rows=args.quota, report=report,
    )
    print(
        f"zipf(alpha={r['zipf_alpha']}) over {r['executors']} executors: "
        f"hottest lane {r['max_peer_rows']} rows, quota slot {r['quota_slot']} "
        f"rows, {r['subrounds']} sub-rounds",
        flush=True,
    )
    for plan in ("max", "quota"):
        p = r[plan]
        print(
            f"{plan:5} plan: {p['gbps']:.2f} GB/s effective, "
            f"{p['staged_rows']} staged rows, {p['wire_bytes']} wire bytes "
            f"(dense), padding {p['padding_fraction']:.1%}",
            flush=True,
        )
    staged_cut = r["max"]["staged_rows"] / max(r["quota"]["staged_rows"], 1)
    print(
        f"quota plan stages {staged_cut:.2f}x fewer rows; outputs bit-identical",
        flush=True,
    )


def measure_adaptive(
    executors: int = 8, max_peer_rows: int = 2048, iterations: int = 2,
    link_gbps: float = 1.0, stall_ms: float = 40.0, report=None,
) -> dict:
    """Measurement core of the ``adaptive`` mode — the telemetry-fed
    AdaptivePlanner (ops/planner.py) against every static configuration on a
    skew x payload-entropy x fault cell matrix.

    Per cell the EXCHANGE leg is measured (the same machinery as
    ``measure_skew``: compiled collective over the loopback mesh, best-of-N
    wall time, bit-equality of every chunked schedule's reassembled shards
    against the single-shot reference), while the SERVE-plane legs are
    modeled from measured inputs, because loopback has no real wire: codec
    cost = measured ``encode_chunk`` time + shipped bytes / ``link_gbps``
    (encoded bytes measured per cell payload), and the fault cell charges a
    gray straggler of ``5 x stall_ms`` to any config that does not hedge,
    vs ``hedge_ms + one peer-shard refetch`` for one that does (the
    docs/PERF.md hedged-fetch measurements are the grounding for that shape).

    Static candidates: quota arms {single-shot, the adaptive quota formula's
    pick, 2x it} x codec {off, rle}, all with hedging off — the legacy knob
    grid an operator would sweep by hand.  The adaptive arm builds real
    ``PlanSignals`` per cell (observed compression ratio from the sample
    encode; the fault cell's stall tail and degraded peer health) and
    executes whatever plan ``AdaptivePlanner`` returns.  Reported per cell:
    every arm's effective GB/s, the static oracle (best arm), the adaptive
    arm's distance from it, and the plan fields it chose; aggregate = mean
    GB/s over cells, adaptive vs each static config held fixed across the
    matrix.  Shared by the CLI and bench.py."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.ops.compress import CompressSpec, encode_chunk
    from sparkucx_tpu.ops.exchange import (
        ExchangeSpec, bucket_send_rows, build_exchange, make_mesh,
    )
    from sparkucx_tpu.ops.planner import AdaptivePlanner, PlanContext, PlanSignals
    from sparkucx_tpu.ops.skew import (
        chunk_size_rows, plan_exchange, reassemble_round, slice_subround,
    )

    n = executors
    row_bytes = 512
    lane = row_bytes // 4
    mesh = make_mesh(n)
    sharding = NamedSharding(mesh, P("ex", None))
    fns: dict = {}

    def exchange_fn(rows):
        fn = fns.get(rows)
        if fn is None:
            fn = fns[rows] = build_exchange(
                mesh,
                ExchangeSpec(num_executors=n, send_rows=rows, recv_rows=rows, lane=lane),
            )
        return fn

    def prepare_arm(payloads, sizes, slot, quota):
        """Build one quota arm's exchange leg: compiled schedule, warmed up,
        reassembled tight shards for the bit-equality gate.  Returns a dict
        with the replayable ``shot`` thunk (timed later, INTERLEAVED across
        arms — back-to-back per-arm loops pick up correlated scheduler noise
        on the loopback CPU mesh).  quota == 0 is the single-shot arm (one
        chunk at the full slot)."""
        plan = plan_exchange([int(sizes.max())], slot, quota)
        q, nchunks = plan.slot_rows, plan.chunks_per_round[0]
        fn = exchange_fn(n * q)
        sub_size_mats = [
            np.stack([chunk_size_rows(sizes[i], c, q) for i in range(n)])
            for c in range(nchunks)
        ]
        size_mats = [jax.device_put(m, sharding) for m in sub_size_mats]
        sub_payloads = [
            np.concatenate([slice_subround(p, n, c, q) for p in payloads])
            for c in range(nchunks)
        ]

        def shot():
            outs = []
            for c in range(nchunks):
                recv, _ = fn(jax.device_put(sub_payloads[c], sharding), size_mats[c])
                outs.append(recv)
            jax.block_until_ready(outs[-1])
            return outs

        outs = shot()  # warmup/compile + the compared output
        devices = list(mesh.devices.reshape(-1))
        shards = []
        for j in range(n):
            sub_shards = [
                np.asarray(
                    next(s.data for s in o.addressable_shards if s.device == devices[j])
                ).reshape(-1).view(np.uint8)
                for o in outs
            ]
            shards.append(
                bytes(reassemble_round(sub_shards, [m[:, j] for m in sub_size_mats], row_bytes))
            )
        return {
            "shot": shot,
            "shards": shards,
            "staged": plan.staged_rows(n),
            "best": float("inf"),
        }

    rle = CompressSpec(codec="rle", min_chunk_bytes=0)
    straggler_s = 5.0 * stall_ms / 1e3  # gray tail: well past the p99 signal

    def serve_time(raw_bytes, enc_bytes, enc_s, codec, hedge_ms, fault):
        ship = enc_bytes if codec != "off" else raw_bytes
        t = ship / (link_gbps * 1e9) + (enc_s if codec != "off" else 0.0)
        if fault == "degraded":
            if hedge_ms <= 0:
                t += straggler_s
            else:
                t += min(straggler_s, hedge_ms / 1e3) + (
                    raw_bytes / n / (link_gbps * 1e9)
                )
        return t

    cells = []
    rng = np.random.default_rng(3)
    base = 512  # pow2 floor of the requested hottest lane, min 512
    while base * 2 <= max_peer_rows:
        base *= 2
    for alpha in (0.0, 1.8):
        # balanced cells stage padding-free at a pow2 hottest lane; skewed
        # cells put the hottest lane just past the pow2 boundary — the
        # geometry where chunking beats the single-shot round-up (the same
        # regime the docs/PERF.md skew table pins)
        hot = base if alpha == 0.0 else base * 5 // 4
        sizes = zipf_size_matrix(n, hot, alpha)
        slot = bucket_send_rows(int(sizes.max()) * n, n) // n
        used_rows = int(sizes.sum())
        useful = used_rows * row_bytes
        # static quota candidates keep only DISTINCT footprints: a quota whose
        # chunked schedule stages exactly the single-shot row count moves the
        # same bytes in more launches — same config class, and its loopback
        # delta is dispatch granularity (CPU cache effects), not plan quality
        single_staged = plan_exchange([int(sizes.max())], slot, 0).staged_rows(n)
        quotas = sorted(
            q
            for q in {0, max(256, slot // 4), max(256, slot // 2)}
            if q == 0
            or plan_exchange([int(sizes.max())], slot, q).staged_rows(n) < single_staged
        )
        for entropy in ("low", "high"):
            # slot-layout staging payloads: zeros (RLE-collapsible) vs
            # full-range random rows (incompressible — RLE ships raw)
            payloads = []
            for i in range(n):
                p = np.zeros((n * slot, lane), dtype=np.int32)
                if entropy == "high":
                    for j in range(n):
                        p[j * slot : j * slot + sizes[i, j]] = rng.integers(
                            -(2**30), 2**30, size=(int(sizes[i, j]), lane), dtype=np.int32
                        )
                payloads.append(p)
            # arms cached by REALIZED schedule (slot, chunks): distinct conf
            # quotas that lower to the same sub-round schedule share one
            # measurement, so identical schedules can't diverge by CPU noise
            arm_cache: dict = {}

            def arm(quota):
                p = plan_exchange([int(sizes.max())], slot, quota)
                key = (p.slot_rows, p.chunks_per_round[0])
                if key not in arm_cache:
                    arm_cache[key] = prepare_arm(payloads, sizes, slot, quota)
                return arm_cache[key]

            conf = TpuShuffleConf(
                planner_mode="adaptive",
                wire_compress_codec="rle",
                fetch_hedge_ms=1,
                fetch_hedge_max_ms=int(stall_ms * 4),
            )

            def plan_ctx(signals):
                return PlanContext(
                    num_executors=n,
                    staging_slot_rows=slot,
                    round_max_rows=(int(sizes.max()),),
                    used_rows_total=used_rows,
                    row_bytes=row_bytes,
                    platform="cpu",
                    signals=signals,
                )

            # the adaptive quota is geometry-only (SPMD lockstep discipline),
            # so it is known before any fault cell: prepare its arm alongside
            # the static candidates, then bit-equality-gate every schedule
            neutral = AdaptivePlanner(conf).plan(plan_ctx(PlanSignals()))
            ad_q = 0 if neutral.single_shot else neutral.slot_rows
            ref = arm(0)["shards"]  # single-shot reference shards
            for q in sorted(set(quotas) | {ad_q}):
                shards = arm(q)["shards"]
                for j in range(n):
                    assert shards[j] == ref[j], (
                        f"quota {q} diverged from single-shot on consumer {j}"
                    )
            # interleaved best-of timing: one pass times every arm once, so
            # slow-drift scheduler noise hits all arms alike
            for _ in range(max(2, iterations)):
                for a in arm_cache.values():
                    t0 = time.perf_counter()
                    a["shot"]()
                    a["best"] = min(a["best"], time.perf_counter() - t0)
            # measured codec leg on the reference shards (what the serve
            # plane would ship): encoded bytes + encode seconds
            enc_bytes, t0 = 0, time.perf_counter()
            for shard in ref:
                _, enc = encode_chunk(rle, shard)
                enc_bytes += len(enc) if enc is not None else len(shard)
            enc_s = time.perf_counter() - t0
            for fault in ("none", "degraded"):
                statics = {}
                for q in quotas:
                    ex_s = arm(q)["best"]
                    for codec in ("off", "rle"):
                        name = f"{'single' if q == 0 else f'q{q}'}/{codec}"
                        t = ex_s + serve_time(useful, enc_bytes, enc_s, codec, 0, fault)
                        statics[name] = useful / t / 1e9
                signals = PlanSignals(
                    rx_stall_p99_ns=int(stall_ms * 1e6) if fault == "degraded" else 0,
                    worst_peer_health=0.3 if fault == "degraded" else 1.0,
                    compression_ratio=useful / max(enc_bytes, 1),
                )
                plan = AdaptivePlanner(conf).plan(plan_ctx(signals))
                assert (0 if plan.single_shot else plan.slot_rows) == ad_q
                ad_ex_s = arm(ad_q)["best"]
                hedge = plan.hedge_ms if fault == "degraded" else 0
                ad_t = ad_ex_s + serve_time(
                    useful, enc_bytes, enc_s, plan.codec, hedge, fault
                )
                ad_gbps = useful / ad_t / 1e9
                oracle_name, oracle_gbps = max(statics.items(), key=lambda kv: kv[1])
                cell = {
                    "alpha": alpha,
                    "entropy": entropy,
                    "fault": fault,
                    "static_gbps": {k: round(v, 4) for k, v in statics.items()},
                    "oracle": oracle_name,
                    "oracle_gbps": round(oracle_gbps, 4),
                    "adaptive_gbps": round(ad_gbps, 4),
                    "distance_from_oracle": round(1.0 - ad_gbps / oracle_gbps, 4),
                    "adaptive_choice": {
                        "quota": ad_q,
                        "codec": plan.codec,
                        "hedge_ms": plan.hedge_ms,
                        "subrounds": plan.num_subrounds,
                    },
                    "bit_identical": True,
                }
                cells.append(cell)
                if report is not None:
                    report(cell)
    # aggregate: each static config held fixed across the whole matrix vs
    # the adaptive planner re-planning per cell
    static_names = sorted({k for c in cells for k in c["static_gbps"]})
    agg_static = {
        name: sum(c["static_gbps"].get(name, 0.0) for c in cells) / len(cells)
        for name in static_names
    }
    agg_adaptive = sum(c["adaptive_gbps"] for c in cells) / len(cells)
    best_static = max(agg_static.items(), key=lambda kv: kv[1])
    return {
        "executors": n,
        "max_peer_rows": max_peer_rows,
        "link_gbps_model": link_gbps,
        "stall_ms_model": stall_ms,
        "cells": cells,
        "aggregate_static_gbps": {k: round(v, 4) for k, v in agg_static.items()},
        "aggregate_adaptive_gbps": round(agg_adaptive, 4),
        "best_static": best_static[0],
        "best_static_gbps": round(best_static[1], 4),
        "adaptive_beats_every_static": agg_adaptive >= best_static[1],
        "worst_cell_distance": round(
            max(c["distance_from_oracle"] for c in cells), 4
        ),
    }


def run_adaptive(args) -> None:
    size = parse_size(args.block_size)
    max_peer_rows = max(512, size // 512)

    def report(cell):
        print(
            f"cell alpha={cell['alpha']} entropy={cell['entropy']} "
            f"fault={cell['fault']}: adaptive {cell['adaptive_gbps']:.3f} GB/s "
            f"(chose quota={cell['adaptive_choice']['quota']} "
            f"codec={cell['adaptive_choice']['codec']} "
            f"hedge={cell['adaptive_choice']['hedge_ms']}ms) vs oracle "
            f"{cell['oracle']} {cell['oracle_gbps']:.3f} GB/s "
            f"(distance {cell['distance_from_oracle']:+.1%})",
            flush=True,
        )

    r = measure_adaptive(
        args.executors, max_peer_rows, args.iterations, report=report
    )
    print(
        f"aggregate over {len(r['cells'])} cells: adaptive "
        f"{r['aggregate_adaptive_gbps']:.3f} GB/s vs best static "
        f"{r['best_static']} {r['best_static_gbps']:.3f} GB/s "
        f"(beats every static: {r['adaptive_beats_every_static']}); "
        f"worst cell distance {r['worst_cell_distance']:+.1%}; "
        f"outputs bit-identical",
        flush=True,
    )


def measure_ici(
    executors_list=(2, 4, 8), slot_rows: int = 1024, lane: int = 128,
    chunks_per_dest: int = 0, iterations: int = 5, report=None, stats=None,
) -> dict:
    """Measurement core of the ``ici`` mode — the FAST-scheduled ring exchange
    (ops/ici_exchange.py) head-to-head against the stock collective
    (ops/exchange.py) at each mesh width in ``executors_list`` (clamped to the
    devices actually present).

    Per width: both impls are compiled over the same mesh, fed identical
    seeded slot-layout payloads with ragged per-peer sizes, asserted
    bit-identical (recv bytes AND recv_sizes), then timed over chained
    donated iterations.  Bandwidth is reported two ways: aggregate GB/s
    (remote bytes / wall) and per-link GB/s (a width-n bidirectional ring has
    2n directed ICI links, so per-link = aggregate / 2n — the number that maps
    onto a chip's per-direction ICI bandwidth).  Per-superstep span and link
    occupancy land in ``stats`` (utils/stats.py StatsAggregator,
    ``record_counters`` under kind ``ici_n{n}``): supersteps per exchange,
    busy/idle directed-link slots from ``step_occupancy``, and the measured
    mean span per superstep.  The fused send side
    (build_fused_ici_exchange: block scatter + exchange, ONE launch) is
    checked at the widest mesh against the two-launch scatter-then-exchange
    reference — bit-equality asserted, staging-launch elimination recorded.
    ``report(impl, n, it, seconds, bytes)`` per iteration.  Shared by the CLI
    and bench.py."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, build_exchange, make_mesh
    from sparkucx_tpu.ops.ici_exchange import (
        DEFAULT_CHUNKS_PER_DEST,
        build_fused_ici_exchange,
        build_ici_exchange,
        schedule_chunks,
        step_occupancy,
    )

    if chunks_per_dest <= 0:
        chunks_per_dest = DEFAULT_CHUNKS_PER_DEST
    avail = jax.device_count()
    widths = sorted({n for n in executors_list if 2 <= n <= avail})
    if not widths:
        raise RuntimeError(
            f"ici mode needs >=2 devices (have {avail}); widths {executors_list}"
        )
    row_bytes = lane * 4
    per_n: dict = {}
    for n in widths:
        slot = max(chunks_per_dest, slot_rows)
        chunks = schedule_chunks(slot, chunks_per_dest)
        send_rows = n * slot
        spec = ExchangeSpec(
            num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane
        )
        mesh = make_mesh(n)
        sharding = NamedSharding(mesh, P("ex", None))
        stock = build_exchange(mesh, spec)
        pallas = build_ici_exchange(mesh, spec, chunks_per_dest=chunks_per_dest)
        sched = pallas.schedule

        rng = np.random.default_rng(7)
        sizes_host = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
        data_host = rng.integers(
            -100, 100, size=(n * send_rows, lane), dtype=np.int32
        )
        sizes = jax.device_put(sizes_host, sharding)

        def shot(fn):
            data = jax.device_put(data_host, sharding)
            recv, rs = fn(data, sizes)
            jax.block_until_ready(recv)
            return np.asarray(recv), np.asarray(rs)

        recv_s, rs_s = shot(stock)  # warmup/compile + oracle
        recv_p, rs_p = shot(pallas)
        assert np.array_equal(rs_s, rs_p), f"recv_sizes diverged at n={n}"
        assert recv_s.tobytes() == recv_p.tobytes(), (
            f"scheduled exchange diverged from stock at n={n}"
        )
        # every device ships (n-1) remote slots per exchange; local slot is
        # a same-chip copy, not ICI traffic
        remote_bytes = n * (n - 1) * slot * row_bytes

        def time_impl(name, fn):
            best = 0.0
            for it in range(iterations):
                data = jax.device_put(data_host, sharding)
                t0 = time.perf_counter()
                cur = data
                for _ in range(4):  # chained: donation recycles the buffer
                    cur, _ = fn(cur, sizes)
                jax.block_until_ready(cur)
                dt = time.perf_counter() - t0
                best = max(best, 4 * remote_bytes / dt / 1e9)
                if report is not None:
                    report(name, n, it, dt, 4 * remote_bytes)
            return best

        stock_gbps = time_impl("stock", stock)
        pallas_gbps = time_impl("pallas", pallas)
        occ = step_occupancy(sched)
        if stats is not None:
            span_ns = int(remote_bytes / max(pallas_gbps, 1e-9) / sched.num_steps)
            stats.record_counters(
                f"ici_n{n}",
                supersteps=sched.num_steps,
                busy_link_slots=sum(b for b, _ in occ),
                idle_link_slots=sum(i for _, i in occ),
                superstep_span_ns=span_ns,
            )
            used = int(sizes_host.sum())
            stats.record_rows(f"ici_n{n}", used, n * n * slot - used)
        per_n[n] = {
            "stock_gbps": stock_gbps,
            "pallas_gbps": pallas_gbps,
            "pallas_per_link_gbps": pallas_gbps / (2 * n),
            "stock_per_link_gbps": stock_gbps / (2 * n),
            "supersteps": sched.num_steps,
            "chunks": sched.chunks,
            "lowering": pallas.lowering,
            "bit_identical": True,
        }

    # Fused send side at the widest mesh: scatter + exchange in one launch
    # vs the two-launch reference (host-built staged layout -> stock fn).
    n = widths[-1]
    slot = max(chunks_per_dest, slot_rows)
    send_rows = n * slot
    spec = ExchangeSpec(
        num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane
    )
    mesh = make_mesh(n)
    sharding = NamedSharding(mesh, P("ex", None))
    rng = np.random.default_rng(11)
    sizes_host = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
    # one block per destination: packed rows consecutive per sender, scattered
    # to the head of each destination slot (build_block_scatter plan triple)
    starts = np.zeros((n, n), dtype=np.int32)
    counts = np.zeros((n, n), dtype=np.int32)
    outs = np.zeros((n, n), dtype=np.int32)
    packed_host = np.zeros((n * send_rows, lane), dtype=np.int32)
    staged_ref = np.zeros((n * send_rows, lane), dtype=np.int32)
    for i in range(n):
        off = 0
        for j in range(n):
            c = int(sizes_host[i, j])
            rows = rng.integers(-100, 100, size=(c, lane), dtype=np.int32)
            packed_host[i * send_rows + off : i * send_rows + off + c] = rows
            staged_ref[i * send_rows + j * slot : i * send_rows + j * slot + c] = rows
            starts[i, j], counts[i, j], outs[i, j] = j * slot, c, off
            off += c
    fused = build_fused_ici_exchange(
        mesh, spec, n, chunks_per_dest=chunks_per_dest, max_block_rows=slot
    )
    stock = build_exchange(mesh, spec)
    sizes = jax.device_put(sizes_host, sharding)
    recv_ref, rs_ref = stock(jax.device_put(staged_ref, sharding), sizes)
    recv_f, rs_f = fused(
        jax.device_put(starts, sharding),
        jax.device_put(counts, sharding),
        jax.device_put(outs, sharding),
        jax.device_put(packed_host, sharding),
        jax.device_put(np.zeros((n * send_rows, lane), dtype=np.int32), sharding),
        sizes,
    )
    assert np.array_equal(np.asarray(rs_ref), np.asarray(rs_f)), (
        "fused recv_sizes diverged"
    )
    assert np.asarray(recv_ref).tobytes() == np.asarray(recv_f).tobytes(), (
        "fused scatter+exchange diverged from scatter-then-exchange"
    )
    return {
        "slot_rows": max(chunks_per_dest, slot_rows),
        "chunks_per_dest": chunks_per_dest,
        "per_n": per_n,
        "fused": {
            "executors": n,
            "bit_identical": True,
            # one jitted launch covers scatter AND exchange; the reference
            # needs a separate staging launch before its exchange
            "launches": 1,
            "reference_launches": 2,
        },
    }


def run_ici(args) -> None:
    from sparkucx_tpu.utils.stats import StatsAggregator

    size = parse_size(args.block_size)
    slot_rows = max(1, size // 512)
    stats = StatsAggregator()

    def report(impl, n, it, dt, tot):
        print(
            f"n={n} {impl:6} iter {it}: {tot} remote bytes in {dt*1e3:.1f} ms "
            f"= {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    widths = (2, 4, 8) if args.executors <= 1 else (args.executors,)
    r = measure_ici(
        widths, slot_rows, 128, chunks_per_dest=args.chunks,
        iterations=args.iterations, report=report, stats=stats,
    )
    print(
        f"slot {r['slot_rows']} rows, {r['chunks_per_dest']} chunks/dest "
        f"requested",
        flush=True,
    )
    for n, p in sorted(r["per_n"].items()):
        print(
            f"n={n}: stock {p['stock_gbps']:.2f} GB/s, pallas "
            f"{p['pallas_gbps']:.2f} GB/s ({p['pallas_per_link_gbps']:.3f} "
            f"GB/s/link over {2*n} links), {p['supersteps']} supersteps x "
            f"{p['chunks']} chunks [{p['lowering']}]; bit-identical",
            flush=True,
        )
    f = r["fused"]
    print(
        f"fused send side (n={f['executors']}): scatter+exchange in "
        f"{f['launches']} launch vs {f['reference_launches']} "
        f"(separate staging launch eliminated); bit-identical",
        flush=True,
    )
    print(stats.report(), flush=True)


def measure_combine(
    executors: int = 8, slot_rows: int = 1024, num_groups: int = 128,
    iterations: int = 5, chunks_per_dest: int = 0, report=None,
) -> dict:
    """Measurement core of the ``combine`` mode — the receive-side fused
    combine (ops/ici_exchange.build_combine_exchange) against the unfused
    reference: the same FAST-scheduled exchange followed by a SEPARATE fold
    launch over the landed O(rows) grid.

    Both sides are fed identical seeded partial-aggregate rows (``[key |
    sum/min/max/avg lanes | count]``, keys in ``[0, num_groups)``) with
    ragged per-peer sizes; the fused accumulator is asserted BIT-IDENTICAL
    to the reference fold off the clock (int32 folds are order-exact), then
    both are timed over chained donated iterations.  The two headline
    numbers of the compute-in-exchange argument land in the result dict:

    * ``drain``: the reference drains the landed grid — ``n * slot_rows *
      lane * 4`` B per device, O(rows) — where the fused side drains only
      the accumulator (``CombineSpec.acc_bytes``, O(groups));
    * ``launches``: the fused exchange+fold is ONE jitted launch (one
      Pallas kernel under the DMA lowering) vs the reference's exchange
      launch plus fold launch, with one dispatch per schedule item inside
      the scheduled-XLA walk.

    ``report(impl, it, seconds, bytes)`` per iteration.  Shared by the CLI
    and bench.py."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.combine import CombineSpec, acc_init, combine_window
    from sparkucx_tpu.ops.exchange import ExchangeSpec, make_mesh
    from sparkucx_tpu.ops.ici_exchange import (
        DEFAULT_CHUNKS_PER_DEST,
        build_combine_exchange,
        build_ici_exchange,
    )

    if chunks_per_dest <= 0:
        chunks_per_dest = DEFAULT_CHUNKS_PER_DEST
    avail = jax.device_count()
    n = min(executors, avail)
    if n < 2:
        raise RuntimeError(f"combine mode needs >=2 devices (have {avail})")
    cspec = CombineSpec(num_groups=num_groups, aggs=("sum", "min", "max", "avg"))
    lane = cspec.row_width
    slot = max(chunks_per_dest, slot_rows)
    send_rows = n * slot
    spec = ExchangeSpec(
        num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane
    )
    mesh = make_mesh(n)
    pspec = P("ex", None)
    sharding = NamedSharding(mesh, pspec)
    fused = build_combine_exchange(mesh, spec, cspec, chunks_per_dest=chunks_per_dest)
    ref_ex = build_ici_exchange(mesh, spec, chunks_per_dest=chunks_per_dest)

    # the reference's post-exchange fold: a second launch over the landed
    # grid (int32 folds are order-insensitive, so one whole-grid window
    # reproduces the fused canonical order bit-exactly)
    def _fold(grid):
        return combine_window(cspec, grid, *acc_init(cspec))

    fold = jax.jit(
        shard_map(
            _fold, mesh=mesh, in_specs=(pspec,), out_specs=(pspec, pspec),
            check_vma=False,
        ),
        in_shardings=(sharding,),
        out_shardings=(sharding, sharding),
    )

    # seeded partial rows: every staged row is a real partial (count >= 1)
    # up to its ragged per-peer size; padding rows stay all-zero (count 0)
    rng = np.random.default_rng(23)
    sizes_host = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
    data_host = np.zeros((n * send_rows, lane), dtype=np.int32)
    for i in range(n):
        for j in range(n):
            c = int(sizes_host[i, j])
            base = i * send_rows + j * slot
            data_host[base : base + c, 0] = rng.integers(0, num_groups, size=c)
            data_host[base : base + c, 1:-1] = rng.integers(
                -100, 100, size=(c, cspec.width)
            )
            data_host[base : base + c, -1] = rng.integers(1, 5, size=c)
    av0, ac0 = acc_init(cspec)
    av_host = np.tile(np.asarray(av0), (n, 1))
    ac_host = np.tile(np.asarray(ac0), (n, 1))
    sizes = jax.device_put(sizes_host, sharding)
    data = jax.device_put(data_host, sharding)

    # warmup/compile + off-clock bit-equality: fused fold vs exchange-then-fold
    recv, rs_ref = ref_ex(jax.device_put(data_host, sharding), sizes)
    rv_ref, rc_ref = fold(recv)
    fv, fc, rs_f = fused(
        data, sizes,
        jax.device_put(av_host, sharding), jax.device_put(ac_host, sharding),
    )
    assert np.array_equal(np.asarray(rs_ref), np.asarray(rs_f)), (
        "fused recv_sizes diverged from the scheduled exchange"
    )
    assert np.asarray(rv_ref).tobytes() == np.asarray(fv).tobytes(), (
        "fused accumulator values diverged from exchange-then-fold"
    )
    assert np.asarray(rc_ref).tobytes() == np.asarray(fc).tobytes(), (
        "fused accumulator counts diverged from exchange-then-fold"
    )

    remote_bytes = n * (n - 1) * slot * lane * 4

    def time_fused():
        best = 0.0
        for it in range(iterations):
            av = jax.device_put(av_host, sharding)
            ac = jax.device_put(ac_host, sharding)
            t0 = time.perf_counter()
            for _ in range(4):  # chained: the donated accumulator recycles
                av, ac, _ = fused(data, sizes, av, ac)
            jax.block_until_ready(av)
            dt = time.perf_counter() - t0
            best = max(best, 4 * remote_bytes / dt / 1e9)
            if report is not None:
                report("fused", it, dt, 4 * remote_bytes)
        return best

    def time_reference():
        best = 0.0
        for it in range(iterations):
            cur = jax.device_put(data_host, sharding)
            t0 = time.perf_counter()
            for _ in range(4):  # chained: exchange donates, then the fold
                cur, _ = ref_ex(cur, sizes)
                accs = fold(cur)
            jax.block_until_ready(accs)
            dt = time.perf_counter() - t0
            best = max(best, 4 * remote_bytes / dt / 1e9)
            if report is not None:
                report("unfused", it, dt, 4 * remote_bytes)
        return best

    fused_gbps = time_fused()
    ref_gbps = time_reference()
    sched = fused.schedule
    ref_drain = n * slot * lane * 4  # the landed grid, per device — O(rows)
    return {
        "executors": n,
        "slot_rows": slot,
        "groups": num_groups,
        "lane": lane,
        "lowering": fused.lowering,
        "supersteps": sched.num_steps,
        "chunks": sched.chunks,
        "fused_gbps": fused_gbps,
        "unfused_gbps": ref_gbps,
        "bit_identical": True,
        "drain": {
            "reference_bytes": ref_drain,
            "fused_bytes": cspec.acc_bytes,
            "ratio": ref_drain / cspec.acc_bytes,
        },
        # one jitted launch folds windows as they land (one Pallas kernel
        # under the DMA lowering); the reference needs its exchange launch
        # plus a separate fold launch, with one dispatch per schedule item
        # inside the scheduled-XLA walk
        "launches": 1,
        "reference_launches": 2,
        "reference_dispatches": len(sched.items()) + 1,
    }


def run_combine(args) -> None:
    size = parse_size(args.block_size)
    n = args.executors if args.executors > 1 else 8

    def report(impl, it, dt, tot):
        print(
            f"{impl:7} iter {it}: {tot} remote bytes in {dt*1e3:.1f} ms "
            f"= {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    r = measure_combine(
        n, max(1, size // 512), max(2, args.keys),
        iterations=args.iterations, chunks_per_dest=args.chunks, report=report,
    )
    d = r["drain"]
    print(
        f"n={r['executors']}: fused {r['fused_gbps']:.2f} GB/s vs unfused "
        f"{r['unfused_gbps']:.2f} GB/s, {r['supersteps']} supersteps x "
        f"{r['chunks']} chunks [{r['lowering']}]; bit-identical",
        flush=True,
    )
    print(
        f"drain per device: {d['reference_bytes']} B landed grid (O(rows)) -> "
        f"{d['fused_bytes']} B accumulator (O(groups)), {d['ratio']:.1f}x less",
        flush=True,
    )
    print(
        f"launches: exchange+fold in {r['launches']} vs "
        f"{r['reference_launches']} (separate fold launch eliminated; "
        f"{r['reference_dispatches']} scheduled dispatches collapse under "
        f"the DMA lowering)",
        flush=True,
    )


def run_write(args) -> None:
    size = parse_size(args.block_size)
    impls = (
        ("host", "device")
        if args.impl == "auto"
        else tuple(s.strip() for s in args.impl.split(",") if s.strip())
    )

    def report(impl, it, dt, tot):
        print(
            f"iter {it}: staged {args.num_blocks} x {size} B via {impl} path in "
            f"{dt*1e3:.1f} ms = {tot / dt / 1e9:.2f} GB/s",
            flush=True,
        )

    results = measure_write(
        args.num_blocks, size, args.iterations, impls=impls, report=report
    )
    host = results.get("host")
    for impl in impls:
        gbps = results[impl]
        speedup = f" ({gbps / host:.2f}x vs host)" if host and impl == "device" else ""
        print(f"write {impl}: {gbps:.2f} GB/s{speedup}", flush=True)


def measure_sort(
    executors: int, total_rows: int, iterations: int, report=None,
    outstanding: int = 8, sort_impl: str = "auto",
) -> float:
    """Measurement core of the ``sort`` mode — device-resident TeraSort step
    (100 B rows: uint32 key + 24 int32 lanes; BASELINE.json configs[1]).
    Returns best M rows/s; ``report(it, seconds, rows, impl)`` per iteration.
    Shared by the CLI and bench.py.  ``outstanding`` independent steps are
    chained per sync like the other modes (UcxPerfBenchmark.scala:129-151's
    outstanding window)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.sort import SortSpec, build_distributed_sort

    n = executors
    cap = -(-total_rows // n)
    # skew headroom only matters when splitters can misjudge a range; one
    # executor owns the whole range, so n=1 needs none (and the 'single'
    # lowering then skips the output pad copy entirely)
    spec = SortSpec(
        num_executors=n, capacity=cap, recv_capacity=2 * cap if n > 1 else cap,
        width=24, impl=sort_impl,
    )
    mesh = make_mesh(n)
    fn = build_distributed_sort(mesh, spec)
    rng = np.random.default_rng(0)
    keys = jax.device_put(
        rng.integers(0, 1 << 32, size=n * cap, dtype=np.uint32),
        NamedSharding(mesh, P("ex")),
    )
    payload = jax.device_put(
        np.zeros((n * cap, 24), np.int32), NamedSharding(mesh, P("ex", None))
    )
    nv = jax.device_put(
        np.full(n, cap, np.int32), NamedSharding(mesh, P("ex"))
    )
    out = jax.block_until_ready(fn(keys, payload, nv))  # compile
    assert int(np.asarray(out[2]).sum()) == n * cap, "sort dropped rows"
    best = 0.0
    for it in range(iterations):
        t0 = time.perf_counter()
        for _ in range(outstanding):
            out = fn(keys, payload, nv)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rows = outstanding * n * cap
        best = max(best, rows / dt / 1e6)
        if report is not None:
            report(it, dt, rows, fn.spec.impl)
    return best


def measure_columnar(
    executors: int, total_rows: int, width: int, iterations: int,
    outstanding: int = 8, report=None,
) -> float:
    """Measurement core of the ``columnar`` mode — the device-resident columnar
    shuffle (the GpuColumnarExchange analogue, ops/columnar.py): rows already
    in HBM are repartitioned by a random owner vector, no host round-trip.
    Returns best GB/s of rows moved; ``report(it, seconds, bytes, impl)`` per
    iteration."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.columnar import ColumnarSpec, build_columnar_shuffle
    from sparkucx_tpu.ops.exchange import make_mesh

    n = executors
    cap = -(-total_rows // n)
    # worst-case skew headroom: all rows could land on one executor only when
    # n == 1; for n > 1 use 2x balanced (random owners stay well inside it)
    spec = ColumnarSpec(
        num_executors=n, capacity=cap,
        recv_capacity=cap if n == 1 else 2 * cap, width=width,
    )
    mesh = make_mesh(n)
    fn = build_columnar_shuffle(mesh, spec)
    rng = np.random.default_rng(0)
    rows = jax.device_put(
        rng.normal(size=(n * cap, width)).astype(np.float32),
        NamedSharding(mesh, P("ex", None)),
    )
    owners = jax.device_put(
        rng.integers(0, n, size=n * cap).astype(np.int32),
        NamedSharding(mesh, P("ex")),
    )
    recv, counts = fn(rows, owners)
    jax.block_until_ready(recv)  # compile
    assert int(np.asarray(counts).sum()) == n * cap, "columnar shuffle dropped rows"
    moved = n * cap * width * 4
    best = 0.0
    for it in range(iterations):
        t0 = time.perf_counter()
        for _ in range(outstanding):
            recv, counts = fn(rows, owners)
        jax.block_until_ready(recv)
        dt = time.perf_counter() - t0
        tot = moved * outstanding
        best = max(best, tot / dt / 1e9)
        if report is not None:
            report(it, dt, tot, fn.spec.impl)
    return best


def measure_groupby(
    executors: int, total_rows: int, iterations: int,
    outstanding: int = 8, num_keys: int = 100, report=None,
    partial: bool = False, wire_rows=None,
) -> float:
    """Measurement core of the ``groupby`` mode — the device-resident GROUP BY
    (100 B rows: uint32 key + 24 summed int32 lanes; the GroupByTest workload
    shape, BASELINE.json configs[0]).  Returns best M input rows/s;
    ``report(it, seconds, rows, impl)`` per iteration.  Shared by the CLI and
    bench.py like measure_sort.  ``partial`` enables map-side partial
    aggregation below the exchange (conf ``partialAggregation``);
    ``wire_rows``, if a list, receives the TRUE exchanged row count — the
    before/after traffic comparison is ``total_rows`` vs that number."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.relational import (
        AggregateSpec, build_grouped_aggregate, hash_owners_host,
    )

    n = executors
    cap = -(-total_rows // n)
    rng = np.random.default_rng(0)
    host_keys = rng.integers(0, num_keys, size=n * cap).astype(np.uint32)
    # Size receive buffers from the ACTUAL hash placement (like measure_join):
    # per-shard key granularity concentrates rows far past any fixed headroom
    # when num_keys is small relative to n.  The overflow assert below then
    # guards host/device placement agreement, not luck.  With partial
    # aggregation each sender exchanges at most one row per local distinct
    # key, so the placement twin counts per-sender distinct keys instead.
    if partial:
        per_owner = np.zeros(n, np.int64)
        for s in range(n):
            uk = np.unique(host_keys[s * cap : (s + 1) * cap])
            np.add.at(per_owner, hash_owners_host(uk, n), 1)
        recv = int(per_owner.max())
    else:
        recv = int(np.bincount(hash_owners_host(host_keys, n), minlength=n).max())
    spec = AggregateSpec(
        num_executors=n, capacity=cap, recv_capacity=recv,
        aggs=("sum",) * 24, partial=partial,
    )
    mesh = make_mesh(n)
    fn = build_grouped_aggregate(mesh, spec)
    keys = jax.device_put(host_keys, NamedSharding(mesh, P("ex")))
    # zeros like measure_sort's payload: the aggregation cost is value-
    # independent (the keys, which steer the exchange, stay random)
    values = jax.device_put(
        np.zeros((n * cap, 24), np.int32), NamedSharding(mesh, P("ex", None))
    )
    nv = jax.device_put(np.full(n, cap, np.int32), NamedSharding(mesh, P("ex")))
    out = jax.block_until_ready(fn(keys, values, nv))  # compile
    # overflow guard first (measure_sort's "dropped rows" check): hash skew
    # past the 2x headroom truncates shards — and can drop whole keys, which
    # would otherwise fire the group-count assert with a misleading message
    recv_totals = np.asarray(out[4])
    assert (recv_totals <= spec.recv_capacity).all(), (
        f"hash skew overflowed recv_capacity ({recv_totals.max()} > "
        f"{spec.recv_capacity}): use more --keys or fewer executors"
    )
    if wire_rows is not None:
        wire_rows.append(int(recv_totals.sum()))
    rows_aggregated = int(np.asarray(out[2]).sum())
    assert rows_aggregated == n * cap, (
        f"groupby dropped rows ({rows_aggregated} != {n * cap})"
    )
    got_groups = int(np.asarray(out[3]).sum())
    want_groups = len(np.unique(host_keys))
    assert got_groups == want_groups, (
        f"groupby produced {got_groups} groups, expected {want_groups}"
    )
    best = 0.0
    for it in range(iterations):
        t0 = time.perf_counter()
        for _ in range(outstanding):
            out = fn(keys, values, nv)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rows = outstanding * n * cap
        best = max(best, rows / dt / 1e6)
        if report is not None:
            report(it, dt, rows, fn.spec.impl)
    return best


def run_groupby(args) -> None:
    def report(it, dt, rows, impl):
        print(
            f"iter {it}: grouped {rows} x 100 B rows in {dt*1e3:.1f} ms = "
            f"{rows / dt / 1e6:.2f} M rows/s ({rows * 100 / dt / 1e9:.2f} GB/s) "
            f"[impl={impl}]",
            flush=True,
        )

    wire = []
    measure_groupby(
        args.executors, args.num_blocks, args.iterations,
        outstanding=args.outstanding, num_keys=args.keys, report=report,
        partial=args.partial, wire_rows=wire,
    )
    mode = "partial (map-side agg below the exchange)" if args.partial else "raw rows"
    print(
        f"exchange traffic [{mode}]: {wire[0]} rows on the wire for "
        f"{args.num_blocks} input rows ({args.num_blocks / max(wire[0], 1):.0f}x reduction)"
        if args.partial
        else f"exchange traffic [{mode}]: {wire[0]} rows on the wire",
        flush=True,
    )


def measure_join(
    executors: int, probe_rows: int, build_rows: int, iterations: int,
    outstanding: int = 8, report=None, join_type: str = "inner",
) -> float:
    """Measurement core of the ``join`` mode — the device-resident PK-FK hash
    join (TPC-H's plan shape, BASELINE.json configs[2]): ``build_rows``
    dimension rows with globally unique keys, ``probe_rows`` fact rows each
    referencing a key in [0, 2*build_rows) — half the probes hit, so every
    ``join_type`` arm (inner/left_outer/left_semi/left_anti/right_outer/
    full_outer) has real work on both its matched and unmatched branches.
    The expected output count is computed with numpy set logic and asserted.
    Returns best M probe rows/s; ``report(it, seconds, rows, impl)``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.relational import (
        JoinSpec,
        build_hash_join,
        plan_join_capacities,
    )

    n = executors
    build_rows = build_rows or probe_rows // 4  # the CLI's documented default
    pcap = -(-probe_rows // n)
    bcap = -(-max(build_rows, n) // n)
    rng = np.random.default_rng(0)
    nb = n * bcap
    bkeys_h = rng.permutation(nb).astype(np.uint32)  # unique PKs, shuffled
    # FK keyspace = [0, 2*nb): ~half the probe rows match a PK, half miss
    pkeys_h = rng.integers(0, 2 * nb, size=n * pcap, dtype=np.uint64).astype(np.uint32)
    # Exact per-shard receive/output capacities from the host twin of the
    # device placement hash (plan_join_capacities) — the asserts below then
    # guard host/device placement agreement, not skew luck.
    brecv, precv, out_cap = plan_join_capacities(
        bkeys_h, pkeys_h, n, join_type=join_type
    )
    probe_hits = int(np.isin(pkeys_h, bkeys_h).sum())
    build_missed = int((~np.isin(bkeys_h, pkeys_h)).sum())
    expect = {
        "inner": probe_hits,
        "left_outer": n * pcap,                       # misses null-extend
        "left_semi": probe_hits,                      # unique PKs: 1 emit/hit
        "left_anti": n * pcap - probe_hits,
        "right_outer": probe_hits + build_missed,
        "full_outer": n * pcap + build_missed,
    }[join_type]
    spec = JoinSpec(
        num_executors=n,
        build_capacity=bcap, build_recv_capacity=brecv, build_width=8,
        probe_capacity=pcap, probe_recv_capacity=precv, probe_width=16,
        out_capacity=out_cap, join_type=join_type,
    )
    mesh = make_mesh(n)
    fn = build_hash_join(mesh, spec)
    key_sh = NamedSharding(mesh, P("ex"))
    row_sh = NamedSharding(mesh, P("ex", None))
    bkeys = jax.device_put(bkeys_h, key_sh)
    bvals = jax.device_put(np.zeros((nb, 8), np.int32), row_sh)
    bnum = jax.device_put(np.full(n, bcap, np.int32), key_sh)
    pkeys = jax.device_put(pkeys_h, key_sh)
    pvals = jax.device_put(np.zeros((n * pcap, 16), np.int32), row_sh)
    pnum = jax.device_put(np.full(n, pcap, np.int32), key_sh)
    out = jax.block_until_ready(fn(bkeys, bvals, bnum, pkeys, pvals, pnum))
    recv_totals = np.asarray(out[4])  # (n, 2) true (build, probe) per shard
    assert (recv_totals[:, 0] <= spec.build_recv_capacity).all() and (
        recv_totals[:, 1] <= spec.probe_recv_capacity
    ).all(), (
        f"hash skew overflowed a receive buffer (max build "
        f"{recv_totals[:, 0].max()}/{spec.build_recv_capacity}, probe "
        f"{recv_totals[:, 1].max()}/{spec.probe_recv_capacity})"
    )
    counts = np.asarray(out[3])
    assert (counts <= spec.out_capacity).all(), (
        f"join output overflowed out_capacity ({counts.max()} > {spec.out_capacity})"
    )
    matches = int(counts.sum())
    assert matches == expect, (
        f"{join_type} join emitted {matches} rows, expected {expect}"
    )
    best = 0.0
    for it in range(iterations):
        t0 = time.perf_counter()
        for _ in range(outstanding):
            out = fn(bkeys, bvals, bnum, pkeys, pvals, pnum)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rows = outstanding * n * pcap
        best = max(best, rows / dt / 1e6)
        if report is not None:
            report(it, dt, rows, fn.spec.impl)
    return best


def run_join(args) -> None:
    def report(it, dt, rows, impl):
        print(
            f"iter {it}: joined {rows} probe rows in {dt*1e3:.1f} ms = "
            f"{rows / dt / 1e6:.2f} M rows/s [impl={impl}]",
            flush=True,
        )

    measure_join(
        args.executors, args.num_blocks, args.build_rows, args.iterations,
        outstanding=args.outstanding, report=report, join_type=args.join_type,
    )


def run_columnar(args) -> None:
    width = max(1, parse_size(args.block_size) // 4)  # -s = row bytes

    def report(it, dt, tot, impl):
        print(
            f"iter {it}: {tot} bytes of {width * 4} B rows in {dt*1e3:.1f} ms = "
            f"{tot / dt / 1e9:.2f} GB/s [impl={impl}]",
            flush=True,
        )

    measure_columnar(
        args.executors, args.num_blocks, width, args.iterations,
        outstanding=args.outstanding, report=report,
    )


def run_sort(args) -> None:
    def report(it, dt, rows, impl):
        print(
            f"iter {it}: sorted {rows} x 100 B rows in {dt*1e3:.1f} ms = "
            f"{rows / dt / 1e6:.2f} M rows/s ({rows * 100 / dt / 1e9:.2f} GB/s) "
            f"[impl={impl}]",
            flush=True,
        )

    if args.sort_impl in ("radix", "single") and args.executors != 1:
        raise SystemExit(
            f"--sort-impl {args.sort_impl} needs --executors 1 (it is an n=1 "
            "local-sort lowering)"
        )
    if args.batches > 1:
        run_sort_external(args)
        return
    measure_sort(
        args.executors, args.num_blocks, args.iterations,
        report=report, outstanding=args.outstanding, sort_impl=args.sort_impl,
    )


def run_sort_external(args) -> None:
    """The --batches > 1 arm of the sort mode: out-of-core TeraSort through
    run_external_sort (device batches + stable host run-merge), timed
    end-to-end per iteration — one number covering device sorts, transfers,
    and the host merge, since that composite IS the out-of-core story."""
    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_external_sort

    n = args.executors
    total = args.num_blocks
    cap = -(-total // (args.batches * n))
    spec = SortSpec(
        num_executors=n, capacity=cap, recv_capacity=2 * cap if n > 1 else cap,
        width=24, impl=args.sort_impl,
    )
    mesh = make_mesh(n)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint32)
    payload = np.zeros((total, 24), np.int32)
    actual_batches = -(-total // (n * cap))  # the driver's real batch count
    fns = {}  # compiled-sort cache shared across iterations: time data, not JIT
    sk, _ = run_external_sort(mesh, spec, keys, payload, fns=fns)  # warmup
    ok, _ = oracle_sort(keys, payload)
    assert np.array_equal(sk, ok), "external sort diverged from oracle"
    for it in range(args.iterations):
        t0 = time.perf_counter()
        run_external_sort(mesh, spec, keys, payload, fns=fns)
        dt = time.perf_counter() - t0
        print(
            f"iter {it}: external-sorted {total} x 100 B rows "
            f"({actual_batches} device batches) in {dt:.2f} s = "
            f"{total / dt / 1e6:.2f} M rows/s", flush=True,
        )


def main(argv=None) -> None:
    from sparkucx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    if args.mode == "server":
        run_server(args)
    elif args.mode == "client":
        run_client(args)
    elif args.mode == "wire":
        run_wire(args)
    elif args.mode == "compress":
        run_compress(args)
    elif args.mode == "failover":
        run_failover(args)
    elif args.mode == "tenants":
        run_tenants(args)
    elif args.mode == "fanin":
        run_fanin(args)
    elif args.mode == "queries":
        run_queries(args)
    elif args.mode == "elastic":
        run_elastic(args)
    elif args.mode == "obs":
        run_obs(args)
    elif args.mode == "pipeline":
        run_pipeline(args)
    elif args.mode == "gather":
        run_gather(args)
    elif args.mode == "write":
        run_write(args)
    elif args.mode == "gray":
        run_gray(args)
    elif args.mode == "skew":
        run_skew(args)
    elif args.mode == "adaptive":
        run_adaptive(args)
    elif args.mode == "combine":
        run_combine(args)
    elif args.mode == "ici":
        run_ici(args)
    elif args.mode == "sort":
        run_sort(args)
    elif args.mode == "columnar":
        run_columnar(args)
    elif args.mode == "groupby":
        run_groupby(args)
    elif args.mode == "join":
        run_join(args)
    else:
        run_superstep(args)


if __name__ == "__main__":
    main()

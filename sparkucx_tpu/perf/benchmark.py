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
"""

from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf, parse_size
from sparkucx_tpu.core.block import BytesBlock, FileBackedBlock, MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus
from sparkucx_tpu.transport.peer import PeerTransport


def _parse_args(argv):
    p = argparse.ArgumentParser(prog="sparkucx-tpu-perf", description=__doc__.split("\n")[0])
    p.add_argument("mode", choices=["server", "client"])
    p.add_argument("-a", "--address", default="127.0.0.1:13337", help="server host:port")
    p.add_argument("-f", "--file", default=None, help="file to serve blocks from (server)")
    p.add_argument("-n", "--num-blocks", type=int, default=8)
    p.add_argument("-s", "--block-size", default="4m")
    p.add_argument("-i", "--iterations", type=int, default=5)
    p.add_argument("-o", "--outstanding", type=int, default=8)
    p.add_argument("-r", "--reports", type=int, default=1, help="batches per bandwidth print")
    p.add_argument("-t", "--threads", type=int, default=1)
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
    print_lock = threading.Lock()

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
            with print_lock:
                print(line, flush=True)
        transport.close()

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(args.threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()



def main(argv=None) -> None:
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    if args.mode == "server":
        run_server(args)
    else:
        run_client(args)


if __name__ == "__main__":
    main()

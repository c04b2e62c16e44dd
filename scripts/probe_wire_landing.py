#!/usr/bin/env python
"""What does the body of a ``fetch_blocks`` reply cost on its way from the
daemon to a client on the same host, by the way it travels?  The probe of
PR 60: its ratio on the chip's host decided that the reply's body goes through
a shared mapping (it ran there first of all this PR ran on the chip; the
change, written meanwhile on this sandbox's ratio because no machine was to be
had, would have come out again at a ratio over 0.6).

Two processes and nothing of the package: a *sender* that stands for the
daemon — it holds a pool of bytes as the received shards are held, and answers
a request for a task's blocks with the real reply's two headers (fixed frame
header, tag + count + sizes) and the blocks — and a *receiver*, this process,
that stands for the client: it asks for one task at a time, as the benchmark's
loop and a Spark reduce task do, and touches every byte it was handed once
(``numpy`` sums the reply as ``uint64``: the cheapest pass that pulls every
line of it into the reader's cache).  Neither touches a device.

A *shape* is ``blocks x bytes`` of one reply: ``large`` 13 x 625,475 (a reduce
task of the 25k gate job at one chip's share, 8.1 MB) and ``small`` 63 x 1,600
(the 1k gate job, 100 KB).  The sender takes a task's blocks from consecutive
places of its pool, which is far larger than the caches, so it reads cold
lines every task, as the daemon does out of a 1.6 GB shard.

For every shape the tasks are run three ways:

``socket_whole``  today's shape: the headers and the blocks in one vectored
                  ``sendmsg`` over TCP loopback (``TCP_NODELAY``, the kernel's
                  default buffers), the body received whole into a kept
                  ``bytearray`` (``recv_into``), then the pass.
``socket_block``  the same bytes on the wire, received a block at a time:
                  ``recv_into`` of one block, its pass (and its decode
                  stand-in), then the next block's ``recv_into`` — the only
                  overlap to be had inside one reply without a helper thread.
``mapped``        the receiver creates a file under ``/dev/shm`` (exclusive,
                  mode 0600), maps it, names it to the sender, which maps it
                  too, and unlinks it; a reply is then the sender's copy of
                  each block into the mapping (``numpy`` slice assignment, off
                  the interpreter lock) and the two headers on the socket with
                  a body length of 0; the pass runs over the mapping.

and each of them with and without a *decode stand-in*: busy work of
``--decode-us`` a block on the receiver (it holds the interpreter, as
``default_deserializer`` does), 150 us at 625 KB and 3 us at 1.6 KB — what
``daemon_fetch_client_turn_p50_us`` ÷ the blocks of a reply reads in the two
daemon cells.  A row gives the median of ``--tasks`` tasks on the receiver's
clock (request sent → last byte passed over) and on the sender's (request read
→ reply handed to the socket), and ``equal``: the first task of every row is
compared byte for byte with the pool, off the clock.

``ratio`` in the report is ``mapped`` ÷ ``socket_whole``, task p50 without the
stand-in, a shape: the issue's rule was to go on only if it is at most 0.6 at
8.1 MB on the chip's host.

What it read on the chip's host (TPU v5 lite, one chip, 13 cores; PR 60, my
chip run; ``PERF.md`` section 6 has every row): at 13 x 625 KB a task is
4,046 us received whole, 4,532 a block at a time and **2,349 through the
mapping — 0.581 of the socket's, so the change went on** (the sender 2,728 →
1,390 us); with the stand-in 5,492 / 7,008 / 4,113 (0.749).  At 63 x 1.6 KB
656 / 1,616 / 383 us (0.583) and 674 / 1,870 / 625 with the stand-in.
Received a block at a time, a reply is slower at both sizes (x1.28 and x2.78
with the stand-in): an overlap inside one reply has nothing to give.

Run on the chip's host:  ``python scripts/probe_wire_landing.py``; the table
goes to stdout and ``chiprun_out/probe_wire_landing.json``.  ``--large`` /
``--small`` (``blocks x bytes``), ``--pool-mb`` and ``--tasks`` shrink it to
prove here that the script works; a time from this sandbox says nothing about
the chip's host.
"""

import argparse
import json
import mmap
import os
import secrets
import socket
import statistics
import struct
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODES = ("socket_whole", "socket_block", "mapped")
#: a request: what to do, the reply's shape and which task of the pool
_REQ = struct.Struct("<IIQQ")
_FRAME = struct.Struct("<IQQ")
_TAG_COUNT = struct.Struct("<QI")
OP_FETCH_SOCKET, OP_FETCH_MAPPED, OP_OFFER, OP_TIMES = range(4)
SHM_DIR = "/dev/shm"


def make_pool(seed: int, nbytes: int) -> np.ndarray:
    """The bytes the sender serves, the same in both processes."""
    return np.random.default_rng(seed).integers(0, 256, size=nbytes, dtype=np.uint8)


def task_offsets(task: int, blocks: int, nbytes: int, pool_bytes: int) -> list:
    """Where the blocks of a task lie in the pool: consecutive places, round
    and round, so a task reads what no task near it has read."""
    places = pool_bytes // nbytes
    return [((task * blocks + j) % places) * nbytes for j in range(blocks)]


def parse_shape(text: str) -> dict:
    blocks, nbytes = (int(x) for x in text.split("x"))
    return {"blocks": blocks, "bytes": nbytes}


def recv_exact(sock: socket.socket, view: memoryview) -> None:
    got, n = 0, len(view)
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            raise ConnectionError(f"peer closed with {got}/{n} B received")
        got += r


def sendmsg_all(sock: socket.socket, parts: list) -> None:
    bufs = [memoryview(p) for p in parts if len(p)]
    i = 0
    while i < len(bufs):
        sent = sock.sendmsg(bufs[i : i + 1024])
        while sent > 0:
            if sent >= bufs[i].nbytes:
                sent -= bufs[i].nbytes
                i += 1
            else:
                bufs[i] = bufs[i][sent:]
                sent = 0


# -- the sender: a process of its own, the daemon's part ---------------------


def sender_main(spec: dict) -> int:
    pool = make_pool(spec["seed"], spec["pool_bytes"])
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    print(json.dumps({"port": srv.getsockname()[1]}), flush=True)
    conn, _ = srv.accept()
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    landing = None  # the mapping the receiver offered, as an array
    times = []
    req = memoryview(bytearray(_REQ.size))
    try:
        while True:
            try:
                recv_exact(conn, req)
            except ConnectionError:
                return 0
            t0 = time.perf_counter_ns()
            op, blocks, nbytes, task = _REQ.unpack(req)
            if op == OP_OFFER:  # ``blocks`` is the name's length, ``nbytes`` the capacity
                name = bytearray(blocks)
                recv_exact(conn, memoryview(name))
                fd = os.open(os.path.join(SHM_DIR, name.decode()), os.O_RDWR | os.O_NOFOLLOW)
                try:
                    landing = np.frombuffer(mmap.mmap(fd, nbytes), dtype=np.uint8)
                finally:
                    os.close(fd)
                conn.sendall(b"\x01")
                continue
            if op == OP_TIMES:
                body = json.dumps(times).encode()
                conn.sendall(struct.pack("<Q", len(body)) + body)
                times = []
                continue
            views = [pool[o : o + nbytes] for o in task_offsets(task, blocks, nbytes, len(pool))]
            header = _TAG_COUNT.pack(task, blocks) + struct.pack(f"<{blocks}q", *([nbytes] * blocks))
            if op == OP_FETCH_MAPPED:
                pos = 0
                for v in views:
                    landing[pos : pos + nbytes] = v
                    pos += nbytes
                conn.sendall(_FRAME.pack(4, len(header), 0) + header)
            else:
                sendmsg_all(conn, [_FRAME.pack(4, len(header), blocks * nbytes) + header] + views)
            times.append(time.perf_counter_ns() - t0)
    finally:
        conn.close()
        srv.close()


# -- the receiver: the client's part, the clock and the comparison -----------


def busy(us: float) -> None:
    """The decode's stand-in: hold the interpreter for ``us``."""
    end = time.perf_counter_ns() + int(us * 1e3)
    while time.perf_counter_ns() < end:
        pass


def touch(view) -> int:
    """One pass over the bytes (the tail under eight bytes is left out)."""
    n = len(view) & ~7
    return int(np.frombuffer(view[:n], dtype=np.uint64).sum()) if n else 0


class Receiver:
    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        self.buf = bytearray()  # the kept landing of the socket's replies
        self.mapping = None
        self._hdr = memoryview(bytearray(_FRAME.size))

    def offer(self, capacity: int) -> None:
        """A mapping of ``capacity`` both processes hold, its name gone."""
        name = "probe-landing-" + secrets.token_hex(16)
        path = os.path.join(SHM_DIR, name)
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR | os.O_NOFOLLOW, 0o600)
        try:
            os.ftruncate(fd, capacity)
            self.mapping = mmap.mmap(fd, capacity)
            raw = name.encode()
            self.sock.sendall(_REQ.pack(OP_OFFER, len(raw), capacity, 0) + raw)
            ack = bytearray(1)
            recv_exact(self.sock, memoryview(ack))
        finally:
            os.close(fd)
            os.unlink(path)

    def _reply_header(self):
        recv_exact(self.sock, self._hdr)
        _, hlen, blen = _FRAME.unpack(self._hdr)
        header = bytearray(hlen)
        recv_exact(self.sock, memoryview(header))
        _, count = _TAG_COUNT.unpack_from(header)
        return struct.unpack_from(f"<{count}q", header, _TAG_COUNT.size), blen

    def task(self, mode: str, shape: dict, task: int, decode_us: float, keep: bool = False):
        """One task; with ``keep`` its bytes come back (the comparison)."""
        blocks, nbytes = shape["blocks"], shape["bytes"]
        sock = self.sock
        op = OP_FETCH_MAPPED if mode == "mapped" else OP_FETCH_SOCKET
        sock.sendall(_REQ.pack(op, blocks, nbytes, task))
        sizes, blen = self._reply_header()
        if mode == "mapped":
            view = memoryview(self.mapping)[: sum(sizes)]
        else:
            if len(self.buf) < blen:
                self.buf = bytearray(blen)
            view = memoryview(self.buf)[:blen]
        if mode == "socket_block":
            pos = 0
            for s in sizes:
                recv_exact(sock, view[pos : pos + s])
                touch(view[pos : pos + s])
                if decode_us:
                    busy(decode_us)
                pos += s
        else:
            if mode == "socket_whole":
                recv_exact(sock, view)
            touch(view)
            if decode_us:
                for _ in sizes:
                    busy(decode_us)
        return bytes(view) if keep else None

    def sender_times(self) -> list:
        self.sock.sendall(_REQ.pack(OP_TIMES, 0, 0, 0))
        n = bytearray(8)
        recv_exact(self.sock, memoryview(n))
        body = bytearray(struct.unpack("<Q", n)[0])
        recv_exact(self.sock, memoryview(body))
        return json.loads(body)


def run_shape(rx: Receiver, pool: np.ndarray, name: str, shape: dict, tasks: int, decode_us: float) -> list:
    blocks, nbytes = shape["blocks"], shape["bytes"]
    page = mmap.PAGESIZE
    rx.offer(-(-blocks * nbytes // page) * page)
    rows = []
    next_task = 0
    for mode in MODES:
        for stand_in in (0.0, decode_us):
            got = rx.task(mode, shape, next_task, stand_in, keep=True)  # also the row's warm-up
            want = b"".join(pool[o : o + nbytes].tobytes() for o in task_offsets(next_task, blocks, nbytes, len(pool)))
            equal = got == want
            next_task += 1
            rx.sender_times()  # the warm-up's is dropped
            took = []
            for _ in range(tasks):
                t0 = time.perf_counter_ns()
                rx.task(mode, shape, next_task, stand_in)
                took.append(time.perf_counter_ns() - t0)
                next_task += 1
            sent = rx.sender_times()
            row = {
                "shape": name, "blocks": blocks, "bytes": nbytes, "mode": mode, "decode_us": stand_in,
                "tasks": tasks, "task_p50_us": statistics.median(took) / 1e3,
                "task_p95_us": sorted(took)[int(0.95 * (len(took) - 1))] / 1e3,
                "sender_p50_us": statistics.median(sent) / 1e3, "equal": equal,
            }
            rows.append(row)
            print(
                f"{name:>5} {blocks:3d} x {nbytes:7d} B  {mode:>12}  decode {stand_in:5.0f} us a block: "
                f"task p50 {row['task_p50_us']:9.1f} us  p95 {row['task_p95_us']:9.1f}  "
                f"sender p50 {row['sender_p50_us']:9.1f} us  equal {equal}",
                flush=True,
            )
    return rows


def ratios(rows: list) -> dict:
    """``mapped`` ÷ ``socket_whole`` by shape: the transfer, and with the decode."""
    out = {}
    for shape in dict.fromkeys(r["shape"] for r in rows):
        p50 = {(r["mode"], bool(r["decode_us"])): r["task_p50_us"] for r in rows if r["shape"] == shape}
        out[shape] = {
            "transfer": p50["mapped", False] / p50["socket_whole", False],
            "with_decode": p50["mapped", True] / p50["socket_whole", True],
            "block_at_a_time_with_decode": p50["socket_block", True] / p50["socket_whole", True],
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sender", help=argparse.SUPPRESS)
    ap.add_argument("--large", default="13x625475")
    ap.add_argument("--small", default="63x1600")
    ap.add_argument("--decode-us", default="150,3", help="the stand-in a block: large,small")
    ap.add_argument("--pool-mb", type=int, default=768)
    ap.add_argument("--tasks", type=int, default=150)
    ap.add_argument("--seed", type=int, default=60)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "probe_wire_landing.json"))
    args = ap.parse_args(argv)
    if args.sender:
        return sender_main(json.loads(args.sender))

    pool_bytes = args.pool_mb << 20
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--sender",
         json.dumps({"seed": args.seed, "pool_bytes": pool_bytes})],
        stdout=subprocess.PIPE, text=True,
    )
    report = {"cpu_count": os.cpu_count(), "pool_bytes": pool_bytes, "rows": []}
    try:
        pool = make_pool(args.seed, pool_bytes)
        port = json.loads(child.stdout.readline())["port"]
        sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        rx = Receiver(sock)
        try:
            decode = [float(x) for x in args.decode_us.split(",")]
            for name, us in zip(("large", "small"), decode):
                report["rows"] += run_shape(rx, pool, name, parse_shape(getattr(args, name)), args.tasks, us)
        finally:
            sock.close()
    finally:
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()
        child.stdout.close()
    report["ratio"] = ratios(report["rows"])
    for shape, r in report["ratio"].items():
        print(
            f"{shape:>5}: mapped / socket_whole = {r['transfer']:.3f} (transfer)  {r['with_decode']:.3f} (with the decode); "
            f"socket_block / socket_whole = {r['block_at_a_time_with_decode']:.3f} (with the decode)",
            flush=True,
        )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0 if all(row["equal"] for row in report["rows"]) else 1


if __name__ == "__main__":
    sys.exit(main())

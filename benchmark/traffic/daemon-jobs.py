"""Traffic driver: whole jobs from one client process over the ``ShuffleDaemon``
socket with ``DaemonClient`` (what the JVM shim speaks), one synchronous
JSON-acked frame a block.

The harness holds the chip and the daemon.  The client is this same file run
as a program: it never touches JAX's devices, makes the records from the seed,
runs, times and checks the jobs over the socket, and reports over a pipe.
Every line on the pipe is one JSON message from the client (a control event of
``run_window``, ``ready`` or ``result``), answered by one JSON line.  The
interface a driver offers is set out in ``manager-jobs.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

T_PROCESS = time.perf_counter()
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.cells import load_cell, load_module
from benchmark.jobs import WindowResult, run_window


class Entry:
    """``DaemonClient`` over the socket."""

    def __init__(self, address) -> None:
        from sparkucx_tpu.core.block import ShuffleBlockId
        from sparkucx_tpu.shuffle.daemon import DaemonClient
        from sparkucx_tpu.shuffle.reader import default_deserializer

        self._block_id = ShuffleBlockId
        self._decode = default_deserializer
        self.client = DaemonClient(tuple(address))
        #: client-side nanoseconds of every ``write_partition`` call, when
        #: ``run_window`` sets a list here (traced runs)
        self.frame_ns = None

    def create(self, shuffle_id: int, mappers: int, reducers: int) -> None:
        self.client.create_shuffle(shuffle_id, mappers, reducers)

    def write_map(self, shuffle_id: int, map_id: int, parts) -> None:
        client, frame_ns = self.client, self.frame_ns
        writer = client.open_map_writer(shuffle_id, map_id)
        for reduce_id, payload in parts:
            if frame_ns is None:
                client.write_partition(writer, reduce_id, payload)
            else:
                t0 = time.perf_counter_ns()
                client.write_partition(writer, reduce_id, payload)
                frame_ns.append(time.perf_counter_ns() - t0)
        lengths = client.commit_map(writer)
        if int(lengths.sum()) != sum(len(p) for _, p in parts):
            raise AssertionError(f"map {map_id} committed {int(lengths.sum())} bytes")

    def exchange(self, shuffle_id: int) -> None:
        self.client.run_exchange(shuffle_id)

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        bids = [self._block_id(shuffle_id, m, reduce_id) for m in mappers]
        for bid, payload in zip(bids, self.client.fetch_blocks(bids)):
            if payload is None:
                raise AssertionError(f"daemon could not serve {bid}")
            for key, value in self._decode(payload):
                consume(key, value)
        return 0

    def remove(self, shuffle_id: int) -> None:
        self.client.remove_shuffle(shuffle_id)

    def close(self) -> None:
        self.client.close()


class Traffic:
    def __init__(self, cell, args) -> None:
        self.cell = cell
        self.daemon = None
        spec = {"workload": cell.name, "rehearse": args.rehearse, "seed": args.seed,
                "seconds": args.seconds, "trace": bool(args.trace)}
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # the chip is the harness's
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        )

    def start(self, conf, parts: dict):
        from sparkucx_tpu.shuffle.daemon import ShuffleDaemon

        t0 = time.perf_counter()
        self.daemon = ShuffleDaemon(conf, num_executors=self.cell.chips, port=0)
        parts["manager"] = time.perf_counter() - t0
        return self.daemon.manager

    def run(self, control, parts: dict):
        """Answer the client until it reports its window."""
        child = self.child
        for line in child.stdout:
            msg = json.loads(line)
            event = msg.pop("event")
            if event == "result":
                parts.update(msg["parts"])
                return WindowResult.from_json(msg["window"])
            reply = {"address": list(self.daemon.address)} if event == "ready" else control(event, **msg)
            child.stdin.write(json.dumps(reply) + "\n")
            child.stdin.flush()
        raise RuntimeError(f"the client process ended without a result (exit {child.wait()})")

    def close(self) -> None:
        if self.daemon is not None:
            self.daemon.close()
        child = self.child
        child.stdin.close()
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()
        child.stdout.close()


def client_main(spec: dict) -> int:
    """The client process.  Its stdout is the pipe and carries the protocol
    and nothing else; what it prints otherwise goes to stderr."""
    pipe = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    sys.stdout = sys.stderr

    def control(event: str, **fields) -> dict:
        pipe.write(json.dumps({"event": event, **fields}) + "\n")
        pipe.flush()
        reply = sys.stdin.readline()
        if not reply:
            raise ConnectionError("the harness closed the pipe")
        return json.loads(reply)

    config = load_cell(spec["workload"], spec["rehearse"]).config
    parts = {"client_imports": time.perf_counter() - T_PROCESS}
    t0 = time.perf_counter()
    records = load_module("references", config["reference"]).make_records(config, spec["seed"])
    parts["records"] = time.perf_counter() - t0
    entry = Entry(control("ready")["address"])
    try:
        window = run_window(entry, records, spec["seconds"], spec["trace"], control)
    finally:
        entry.close()
    pipe.write(json.dumps({"event": "result", "window": window.to_json(), "parts": parts}) + "\n")
    pipe.flush()
    return 0


if __name__ == "__main__":
    sys.exit(client_main(json.loads(sys.argv[1])))

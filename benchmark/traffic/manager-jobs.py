"""Traffic driver: whole jobs from one client thread in the harness's own
process, on ``TpuShuffleManager`` (what a Python engine calls).

A driver is the part of a traffic mix that is code: how the system under test
is stood up in the process that holds the chip, and how a client reaches its
entry point.  ``run.py`` finds it by the ``driver`` of the cell's traffic file
and knows nothing else about it:

``Traffic(cell, args)``
    made before the harness imports JAX (a driver with client processes starts
    them here, so that they set up side by side with it);
``start(conf, parts) -> manager``
    stands the system up with that ``TpuShuffleConf`` and returns the
    ``TpuShuffleManager`` whose cluster the harness reads counters from;
    seconds of its set-up steps go into ``parts``;
``run(control, parts) -> WindowResult``
    the warm-up job and the window (``benchmark.jobs.run_window``);
``close()``
    stops what ``start`` and the constructor started, and waits for it.

Its ``Entry`` is what ``run_job`` calls: ``create``, ``write_map``,
``exchange``, ``read``, ``remove``.
"""

from __future__ import annotations

import time
from typing import List

from benchmark.cells import load_module
from benchmark.jobs import run_window

#: counters of ``ShuffleReadMetrics`` that stay zero on a healthy host
FAULT_COUNTERS = ("blocks_retried", "failovers", "fetch_timeouts")


class Entry:
    """``TpuShuffleManager`` in the client's own process."""

    def __init__(self, manager) -> None:
        self.manager = manager

    def create(self, shuffle_id: int, mappers: int, reducers: int) -> None:
        self.manager.register_shuffle(shuffle_id, mappers, reducers)

    def write_map(self, shuffle_id: int, map_id: int, parts) -> None:
        writer = self.manager.get_writer(shuffle_id, map_id)
        for reduce_id, payload in parts:
            with writer.get_partition_writer(reduce_id).open_stream() as stream:
                stream.write(payload)
        lengths = writer.commit_all_partitions()
        if int(sum(lengths)) != sum(len(p) for _, p in parts):
            raise AssertionError(f"map {map_id} committed {int(sum(lengths))} bytes")

    def exchange(self, shuffle_id: int) -> None:
        self.manager.run_exchange(shuffle_id)

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        """Drain one reduce task into ``consume(key, value)``; returns the
        fetches that were retried, failed over or timed out."""
        reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1)
        for key, value in reader.read():
            consume(key, value)
        return sum(getattr(reader.metrics, name) for name in FAULT_COUNTERS)

    def remove(self, shuffle_id: int) -> None:
        self.manager.unregister_shuffle(shuffle_id)


class Traffic:
    def __init__(self, cell, args) -> None:
        self.cell, self.args = cell, args
        self.manager = None

    def start(self, conf, parts: dict):
        from sparkucx_tpu.shuffle.manager import TpuShuffleManager

        t0 = time.perf_counter()
        config = self.cell.config
        self.records = load_module("references", config["reference"]).make_records(config, self.args.seed)
        parts["records"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.manager = TpuShuffleManager(conf, num_executors=self.cell.chips)
        parts["manager"] = time.perf_counter() - t0
        return self.manager

    def run(self, control, parts: dict):
        return run_window(Entry(self.manager), self.records, self.args.seconds,
                          bool(self.args.trace), control)

    def close(self) -> None:
        if self.manager is not None:
            self.manager.stop()

"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, with a reduce side that runs on the device.

The map side is ``manager-jobs``'s (host bytes through ``get_writer``, every
partition stream written once; its ``Entry`` is reused for ``create`` /
``write_map`` / ``exchange`` / ``remove``).  A reduce task is
``get_reader(sid, r, r + 1).read_device()`` — the task's blocks gathered into
one packed buffer on the owning executor's chip — handed whole to the
reference's consumer, which reads every record's key and the first bytes of
its value on the chip; the task ends when its four numbers are on the host.
200 tasks a job, in reducer order, one in flight, every block read once.

Beyond what ``run.py`` decides ``correct`` on, a run is unsound here when a
packed buffer left its executor's device (the task fails) or a block gather of
another lowering than the platform's own ran (``dma`` on the chip, ``xla`` on
the CPU).  A program whose reader has no ``read_device`` is refused in
``start``, before any record is made.  The line ``devread:`` gives the gather
lowerings and the device's ``bytes_in_use`` after every removed job.
"""

from __future__ import annotations

import json
from typing import List

from benchmark.cells import load_module
from benchmark.jobs import run_window

shipped = load_module("traffic", "manager-jobs")


def require_device_read(reader_class) -> None:
    """Exit at once on a program that cannot run this traffic."""
    if not callable(getattr(reader_class, "read_device", None)):
        raise SystemExit(
            f"benchmark: traffic manager-devread needs {reader_class.__name__}.read_device(); "
            "this program has none"
        )


class Entry(shipped.Entry):
    """``TpuShuffleManager`` in the client's own process; reduce tasks read on
    the device."""

    def __init__(self, manager) -> None:
        super().__init__(manager)
        #: the fullest device's ``bytes_in_use`` after each removed job
        self.bytes_in_use: List[int] = []

    def read(self, shuffle_id: int, reduce_id: int, mappers: List[int], consume) -> int:
        """One reduce task: its blocks packed on the chip, handed whole to
        ``consume(packed, table)``; returns the fetches that were retried,
        failed over or timed out."""
        reader = self.manager.get_reader(shuffle_id, reduce_id, reduce_id + 1)
        got = reader.read_device()
        device = self.manager.cluster.transport(reader.executor_id).device
        if got.packed.devices() != {device}:
            raise AssertionError(f"the packed buffer is on {got.packed.devices()}, not on {device}")
        read = [b.map_id for b in got.block_ids]
        if read != sorted(mappers):
            raise AssertionError(f"blocks of mappers {read}, not {sorted(mappers)}")
        consume(got.packed, got.table)
        return sum(getattr(reader.metrics, name) for name in shipped.FAULT_COUNTERS)

    def remove(self, shuffle_id: int) -> None:
        super().remove(shuffle_id)
        devices = self.manager.cluster.mesh.devices.reshape(-1)
        self.bytes_in_use.append(max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices))


class Traffic(shipped.Traffic):
    def start(self, conf, parts: dict):
        from sparkucx_tpu.shuffle.reader import TpuShuffleReader

        require_device_read(TpuShuffleReader)
        return super().start(conf, parts)

    def run(self, control, parts: dict):
        entry = Entry(self.manager)
        window = run_window(entry, self.records, self.args.seconds, bool(self.args.trace), control)
        cluster = self.manager.cluster
        platform = cluster.mesh.devices.reshape(-1)[0].platform
        want = "dma" if platform == "tpu" else "xla"
        ran = sorted(set(cluster.executed_lowerings()["gather"]))
        print("devread: " + json.dumps({
            "gather": ran, "expected": want, "bytes_in_use_after_job": entry.bytes_in_use,
        }), flush=True)
        if ran != [want]:
            window.warmup.failed += 1  # the one way a driver has to say: not this run
        return window

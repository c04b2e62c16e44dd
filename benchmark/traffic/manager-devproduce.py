"""Traffic driver: whole jobs from one client thread in the harness's own
process on ``TpuShuffleManager``, with a map side whose output is born on the
device and a reduce side that runs there.

Set-up puts each map task's output on its executor's chip once, as ONE packed
int32 buffer (the reference's ``map_output``: the task's non-empty blocks back
to back in reducer order, each from a fresh row; capacity a multiple of the
traffic file's ``pack_rows``).  A map task of a job hands that buffer, its
reducer ids and its byte lengths to ``get_writer(sid, m)`` in one call and
commits; no host byte of the job is written.  The reduce side is
``manager-devread``'s (its ``Entry.read`` is reused): ``read_device()`` per
task, the reference's check on the chip.

Beyond what ``run.py`` decides ``correct`` on, a run is unsound here when

* a block scatter or gather of another lowering than the platform's own ran
  (``dma`` on the chip, ``xla`` on the CPU);
* the stores' ``device_staged_bytes`` over the window is not jobs x the job's
  bytes, or differs from ``staged_bytes`` (which ``commit`` counts on both
  paths), or ``copy_ns`` rose (only the host path's copies add to it): a host
  byte moved;
* a removed job was not device-staged (its sealed round then is not the
  device array the scatters filled), or host staging had been allocated for it;
* the device's ``bytes_in_use`` after a removed job exceeds the producers'
  arrays by more than 64 MiB: staging or received rows were not released.

A program whose writer has no packed device write, or whose reader has no
``read_device``, is refused in ``start``, before any record is made.  The line
``devproduce:`` gives the lowerings, each store's ``write_stats()`` delta over
the window and ``bytes_in_use`` after every removed job.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List

from benchmark.cells import load_module
from benchmark.jobs import run_window

devread = load_module("traffic", "manager-devread")

#: what may lie on the device beside the producers' arrays once a job is removed
RELEASE_SLACK_BYTES = 64 << 20
#: counters of ``write_stats()`` the line reports over the window
COUNTERS = ("staged_blocks", "staged_bytes", "device_staged_blocks", "device_staged_bytes",
            "scatter_dispatches", "device_stage_ns", "copy_ns", "rollovers", "spilled_bytes",
            "released_device_bytes")


def require_device_write(writer_class) -> None:
    """Exit at once on a program that cannot run this traffic."""
    if not callable(getattr(writer_class, "write_partitions_device", None)):
        raise SystemExit(
            f"benchmark: traffic manager-devproduce needs {writer_class.__name__}"
            ".write_partitions_device(); this program has none"
        )


class Entry(devread.Entry):
    """``TpuShuffleManager`` in the client's own process; map tasks hand over
    packed device buffers, reduce tasks read on the device."""

    def __init__(self, manager, outputs, owners) -> None:
        super().__init__(manager)
        #: per mapper: (packed device array, reducer ids, byte lengths)
        self.outputs = outputs
        #: per mapper: the executor whose chip holds its output, so runs its task
        self.owners = owners
        #: removed jobs that were not device-staged, or that had host staging
        self.host_rounds: List[int] = []

    def create(self, shuffle_id: int, mappers: int, reducers: int) -> None:
        self.manager.register_shuffle(shuffle_id, mappers, reducers, map_owner=self.owners)

    def write_map(self, shuffle_id: int, map_id: int, parts) -> None:
        packed, reduce_ids, lengths = self.outputs[map_id]
        writer = self.manager.get_writer(shuffle_id, map_id)
        writer.write_partitions_device(packed, reduce_ids, lengths)
        committed = writer.commit_all_partitions()
        if int(sum(committed)) != sum(len(p) for _, p in parts):
            raise AssertionError(f"map {map_id} committed {int(sum(committed))} bytes")

    def remove(self, shuffle_id: int) -> None:
        # off the job's clock: a device-staged shuffle that never had host
        # staging sealed the device array its scatters filled, nothing else
        for t in self.manager.cluster.transports:
            stats = t.store.stats(shuffle_id)
            if stats["device_mode"] is not True or stats["host_staging_allocated"]:
                self.host_rounds.append(shuffle_id)
        super().remove(shuffle_id)


def unsound(report: dict, jobs: int, job_bytes: int) -> List[str]:
    """Why the run is not this cell's, by the ``devproduce:`` line."""
    why = []
    want = report["expected"]
    for kind in ("scatter", "gather"):
        if report[kind] != [want]:
            why.append(f"{kind} lowering {report[kind]}, not [{want!r}]")
    staged = sum(d["staged_bytes"] for d in report["stores"])
    on_device = sum(d["device_staged_bytes"] for d in report["stores"])
    if not (on_device == staged == jobs * job_bytes):
        why.append(f"device_staged_bytes {on_device}, staged_bytes {staged}, jobs x bytes {jobs * job_bytes}")
    if any(d["copy_ns"] for d in report["stores"]):
        why.append("copy_ns rose: a host byte was copied")
    if report["host_rounds"]:
        why.append(f"not device-staged, or host staging allocated, in jobs {report['host_rounds']}")
    limit = report["producer_bytes"] + RELEASE_SLACK_BYTES
    if any(b > limit for b in report["bytes_in_use_after_job"]):
        why.append(f"bytes_in_use after a removed job over {limit}")
    return why


class Traffic(devread.Traffic):
    def start(self, conf, parts: dict):
        from sparkucx_tpu.shuffle.writer import TpuShuffleMapOutputWriter

        require_device_write(TpuShuffleMapOutputWriter)
        manager = super().start(conf, parts)  # refuses a reader without read_device first
        t0 = time.perf_counter()
        reference = load_module("references", self.cell.config["reference"])
        cluster = manager.cluster
        # a map task runs where its output lies: round-robin over the executors
        self.owners = [m % cluster.num_executors for m in range(self.records.num_mappers)]
        pack_rows = int(self.cell.traffic.get("pack_rows", 1))
        self.outputs = []
        for m, owner in enumerate(self.owners):
            packed, reduce_ids, lengths = reference.map_output(self.records, m, cluster.row_bytes)
            device = cluster.transport(owner).device
            self.outputs.append((reference.on_device(packed, device, pack_rows), reduce_ids, lengths))
        for packed, _, _ in self.outputs:
            packed.block_until_ready()
        parts["device_output"] = time.perf_counter() - t0
        return manager

    def run(self, control, parts: dict):
        entry = Entry(self.manager, self.outputs, self.owners)
        cluster = self.manager.cluster
        marks: Dict[str, List[dict]] = {}

        def marking(event: str, **fields):
            if event in ("window_start", "window_end"):
                marks[event] = [t.store.write_stats() for t in cluster.transports]
            return control(event, **fields)

        window = run_window(entry, self.records, self.args.seconds, bool(self.args.trace), marking)
        platform = cluster.mesh.devices.reshape(-1)[0].platform
        lowerings = cluster.executed_lowerings()
        report = {
            "scatter": sorted(set(lowerings.get("scatter", ()))),
            "gather": sorted(set(lowerings["gather"])),
            "expected": "dma" if platform == "tpu" else "xla",
            "stores": [
                {"executor": after["executor"], **{k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}}
                for before, after in zip(marks["window_start"], marks["window_end"])
            ],
            "producer_bytes": sum(int(packed.nbytes) for packed, _, _ in self.outputs),
            "bytes_in_use_after_job": entry.bytes_in_use,
            "host_rounds": entry.host_rounds,
        }
        report["unsound"] = unsound(report, len(window.jobs), window.job_bytes)
        print("devproduce: " + json.dumps(report), flush=True)
        if report["unsound"]:
            window.warmup.failed += 1  # the one way a driver has to say: not this run
        return window

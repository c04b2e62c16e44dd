#!/usr/bin/env python
"""Chip smoke: the served shuffle path, once, at GroupByTest width, on every
chip ``jax.devices()`` offers (one executor per chip, one process).

The quickest proof that the system still starts on the chip.  Workload: the
upstream gate job ``org.apache.spark.examples.GroupByTest 200 5000 25000 200``
(buildlib/test.sh:169-173) — every mapper emits 5,000 (random int key,
25,000-byte value) pairs, hash-partitioned over 200 reducers, all kept.  Scale
is cut to a v5e-16 chip's share (13 of the 200 mappers per chip present); the
record shape is not.  Records come from ``--seed``; what comes back is compared
with a plain GroupBy over the same records (group count, and a crc32 of every
value under its key) that never touches the code under test.

Phases, through the entry points a user calls:

  main    TpuShuffleManager at the DEFAULT conf: register_shuffle -> get_writer
          ... commit_all_partitions -> run_exchange -> get_reader(...).read()
          (64 MiB staging, so the share spills into many pipelined rounds)
  reuse   a second, smaller shuffle on the same manager: must compile nothing
  device  received shards kept in HBM (keep_device_recv, host_recv_mode=
          'device'), staged in ONE round, then fetch_blocks_device over each
          executor's whole share (the Pallas DMA gather on the chip)
  daemon  the same records through ShuffleDaemon + DaemonClient — the
          Spark-facing wire; the client side speaks sockets only

The run fails (exit 1, the phase named) if a phase raises, the oracle
disagrees, a lowering other than the platform's own executed (TPU: 'local' at
n=1, 'ragged' at n>1, gather 'dma'), any fetch was retried / failed over /
timed out, the native arena failed to build, or — on several chips — a
device's peak HBM stayed zero.  With no accelerator (or in a directory
without the package) it exits 4 and prints no result.

``--cpu-tiny`` is the tier-1 form: it pins ``JAX_PLATFORMS=cpu`` itself, cuts
the scale to a few MB, prints ``platform: cpu`` and is the only way this
script runs without a chip.  Seconds printed here are a smoke's, not a
benchmark's: nothing derives a rate from them.

The ``summary:`` line carries source, cuts, seed, mesh and per-device peak
HBM.  Last stdout line: one JSON object with exactly ``ok`` and ``device``,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

import argparse
import json
import os
import sys
import time
import traceback
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional

SOURCE = (
    "org.apache.spark.examples.GroupByTest 200 5000 25000 200 "
    "(upstream gate job, buildlib/test.sh:169-173)"
)
SOURCE_MAPPERS = 200
SOURCE_PAIRS = 5000
VALUE_BYTES = 25000
NUM_REDUCERS = 200
DEPLOYMENT_CHIPS = 16  # the stated deployment: one v5e-16 slice
#: device kinds this smoke is sized for; another kind is an error, not a default
KNOWN_KINDS = ("TPU v5 lite", "TPU v5e")
#: exit code for "no chip / cannot start" (1 = a phase failed; 2 and 3 are
#: left to the chip tool, which uses them for refused and lost calls)
NO_CHIP = 4


@dataclass(frozen=True)
class Shape:
    mappers_per_chip: int
    pairs_per_mapper: int
    #: None = the conf default (64 MiB); the tiny form shrinks it so the
    #: multi-round engine still runs
    staging_capacity: Optional[int]


FULL = Shape(-(-SOURCE_MAPPERS // DEPLOYMENT_CHIPS), SOURCE_PAIRS, None)
TINY = Shape(2, 400, 8 << 20)


class CompileCounter:
    """Counts from JAX's own monitoring events: executables built in this
    process, and how many of those came out of the persistent cache."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = self.requests = self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw) -> None:
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def _on_duration(self, event, secs, **_kw) -> None:
        if event == self.BUILD:
            self.compiles += 1
            self.seconds += secs

    def snapshot(self):
        return (self.compiles, self.requests, self.hits, self.seconds)

    def since(self, snap) -> Dict[str, float]:
        c, r, h, s = (a - b for a, b in zip(self.snapshot(), snap))
        return {
            "compiles": c,
            "cache_hits": h,
            "cache_misses": r - h,
            "setup.compile_seconds": round(s, 3),
        }


def make_records(seed: int, num_mappers: int, pairs: int):
    """``blocks[m][r]`` = the serialized (key, value) records mapper ``m``
    emits for reducer ``r``; ``per_mapper[m]`` = the plain GroupBy over that
    mapper's records, ``{key: [crc32(value), ...]}``, taken straight from the
    generated arrays."""
    import numpy as np

    from sparkucx_tpu.shuffle.reader import serialize_records

    blocks: List[Dict[int, bytes]] = []
    per_mapper: List[Dict[int, List[int]]] = []
    for m in range(num_mappers):
        rng = np.random.default_rng([seed, m])
        keys = rng.integers(0, 2**31 - 1, size=pairs, dtype=np.int64).tolist()
        values = rng.integers(0, 256, size=(pairs, VALUE_BYTES), dtype=np.uint8)
        parts: Dict[int, list] = {}
        groups: Dict[int, List[int]] = {}
        for i, key in enumerate(keys):
            value = memoryview(values[i])
            groups.setdefault(key, []).append(zlib.crc32(value))
            # Spark's HashPartitioner: nonNegativeMod(key.hashCode, numReducers)
            parts.setdefault(key % NUM_REDUCERS, []).append((key, value))
        blocks.append({r: serialize_records(recs) for r, recs in parts.items()})
        per_mapper.append(groups)
    return blocks, per_mapper


def plain_groupby(per_mapper) -> Dict[int, List[int]]:
    """The reference for a shuffle over these mappers: union of their groups."""
    reference: Dict[int, List[int]] = {}
    for groups in per_mapper:
        for key, crcs in groups.items():
            reference.setdefault(key, []).extend(crcs)
    return reference


class GroupByCheck:
    """Groups what a phase read back and compares it with the reference."""

    def __init__(self) -> None:
        self.groups: Dict[int, List[int]] = {}
        self.records = 0

    def add(self, reduce_id: int, key: int, value) -> None:
        if key % NUM_REDUCERS != reduce_id:
            raise AssertionError(f"key {key} surfaced in partition {reduce_id}")
        self.groups.setdefault(key, []).append(zlib.crc32(value))
        self.records += 1

    def add_block(self, reduce_id: int, payload) -> None:
        from sparkucx_tpu.shuffle.reader import default_deserializer

        for key, value in default_deserializer(payload):
            self.add(reduce_id, key, value)

    def assert_matches(self, reference: Dict[int, List[int]]) -> Dict[str, int]:
        if len(self.groups) != len(reference):
            raise AssertionError(
                f"group count {len(self.groups)} != reference {len(reference)}"
            )
        for key, crcs in reference.items():
            if sorted(self.groups.get(key, ())) != sorted(crcs):
                raise AssertionError(f"values under key {key} differ from the reference")
        return {"groups": len(self.groups), "records": self.records}


def block_counts(blocks) -> Dict[str, int]:
    return {
        "mappers": len(blocks),
        "reducers": NUM_REDUCERS,
        "blocks": sum(len(p) for p in blocks),
        "bytes": sum(len(b) for p in blocks for b in p.values()),
    }


def exchange_mark(cluster):
    """(collective dispatches, staged rows used, staged rows of padding) so
    far — the cluster's own StatsAggregator, not a second bookkeeping."""
    submit = cluster.stats.summary("exchange.pipeline.submit")
    drain = cluster.stats.summary("exchange.pipeline.drain")
    return (submit.ops, drain.used_rows, drain.padded_rows)


def exchange_counts(cluster, before) -> Dict[str, int]:
    names = ("collective_dispatches", "staged_rows_used", "staged_rows_padding")
    return {k: a - b for k, a, b in zip(names, exchange_mark(cluster), before)}


def write_maps(mgr, shuffle_id: int, blocks) -> None:
    mgr.register_shuffle(shuffle_id, len(blocks), NUM_REDUCERS)
    for m, parts in enumerate(blocks):
        writer = mgr.get_writer(shuffle_id, m)
        for r in sorted(parts):
            with writer.get_partition_writer(r).open_stream() as stream:
                stream.write(parts[r])
        writer.commit_all_partitions()


def check_lowerings(cluster, platform: str, n: int) -> Dict[str, List[str]]:
    """What executed, asserted against the platform's own lowering: on the
    chip never 'dense', 'xla', 'tiled' or the interpreter."""
    ran = cluster.executed_lowerings()
    if platform == "tpu":
        want = {"exchange": "local" if n == 1 else "ragged", "gather": "dma"}
    else:
        want = {"exchange": "dense", "gather": "xla"}
    for kind, impls in ran.items():
        if set(impls) - {want[kind]}:
            raise AssertionError(
                f"{kind} lowering {sorted(set(impls))} executed on {platform} "
                f"x{n}; expected only {want[kind]!r}"
            )
    if not ran["exchange"]:
        raise AssertionError("no exchange executable was built")
    return {k: sorted(set(v)) for k, v in ran.items()}


def manager_shuffle(mgr, shuffle_id: int, blocks, reference, platform: str) -> dict:
    """write -> exchange -> read one shuffle through the manager SPI."""
    cluster = mgr.cluster
    mark = exchange_mark(cluster)
    t0 = time.perf_counter()
    write_maps(mgr, shuffle_id, blocks)
    t1 = time.perf_counter()
    mgr.run_exchange(shuffle_id)
    t2 = time.perf_counter()
    check = GroupByCheck()
    faults = {"blocks_retried": 0, "failovers": 0, "fetch_timeouts": 0}
    for r in range(NUM_REDUCERS):
        reader = mgr.get_reader(shuffle_id, r, r + 1)
        for key, value in reader.read():
            check.add(r, key, value)
        for name in faults:
            faults[name] += getattr(reader.metrics, name)
    t3 = time.perf_counter()
    out = block_counts(blocks)
    out["rounds"] = len(cluster.meta(shuffle_id).recv_sizes)
    out.update(exchange_counts(cluster, mark))
    out["oracle"] = check.assert_matches(reference)
    out.update(faults)
    if any(faults.values()):
        raise AssertionError(f"fetch path degraded on a healthy host: {faults}")
    out["lowering"] = check_lowerings(cluster, platform, mgr.num_executors)
    out["seconds"] = {
        "run.write": round(t1 - t0, 3),
        "run.exchange": round(t2 - t1, 3),
        "run.read": round(t3 - t2, 3),
    }
    mgr.unregister_shuffle(shuffle_id)
    return out


def phase_main(ctx) -> dict:
    return manager_shuffle(ctx.manager, 0, ctx.blocks, ctx.reference, ctx.platform)


def phase_reuse(ctx) -> dict:
    before = ctx.compiles.snapshot()
    # one mapper per chip
    blocks, ref = ctx.blocks[: ctx.n], plain_groupby(ctx.per_mapper[: ctx.n])
    out = manager_shuffle(ctx.manager, 1, blocks, ref, ctx.platform)
    built = ctx.compiles.since(before)["compiles"]
    if built:
        raise AssertionError(f"second shuffle on a warm manager built {built} executable(s)")
    return out


def one_round_capacity(blocks, n: int, alignment: int) -> int:
    """Smallest power-of-two staging capacity that holds every executor's
    share in ONE round (map m lives on executor m % n; reducers are owned in
    contiguous ranges), so the device phase's gather source is the whole
    share."""
    from sparkucx_tpu.store.hbm_store import default_peer_ranges

    ranges = default_peer_ranges(NUM_REDUCERS, n)
    region = 0
    for e in range(n):
        for start, end in ranges:
            used = 0
            for parts in blocks[e::n]:
                for r, payload in parts.items():
                    if start <= r < end:
                        used += -(-len(payload) // alignment) * alignment
            region = max(region, used)
    cap = 1
    while cap < n * region:
        cap <<= 1
    return cap


def phase_device(ctx) -> dict:
    import jax
    import numpy as np

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.core.block import ShuffleBlockId
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    blocks, n = ctx.blocks, ctx.n
    conf = TpuShuffleConf(keep_device_recv=True, host_recv_mode="device")
    conf.staging_capacity_per_executor = one_round_capacity(blocks, n, conf.block_alignment)
    out = block_counts(blocks)
    out["staging_capacity_per_executor"] = conf.staging_capacity_per_executor
    with TpuShuffleManager(conf, num_executors=n) as mgr:
        cluster = mgr.cluster
        mark = exchange_mark(cluster)
        t0 = time.perf_counter()
        write_maps(mgr, 0, blocks)
        t1 = time.perf_counter()
        mgr.run_exchange(0)
        t2 = time.perf_counter()
        meta = cluster.meta(0)
        out["rounds"] = len(meta.recv_sizes)
        check = GroupByCheck()
        gathered_blocks = 0
        t_gather = 0.0
        for e in range(n):
            transport = cluster.transport(e)
            for rnd in meta.recv_device:
                if rnd[e].devices() != {transport.device}:
                    raise AssertionError(
                        f"executor {e}'s received shard is on {rnd[e].devices()}, "
                        f"not its own device {transport.device}"
                    )
            start, end = meta.peer_ranges[e]
            bids = [
                ShuffleBlockId(0, m, r)
                for r in range(start, end)
                for m in range(len(blocks))
                if r in blocks[m]
            ]
            t = time.perf_counter()
            packed, entries = transport.fetch_blocks_device(bids)
            jax.block_until_ready(packed)
            t_gather += time.perf_counter() - t
            if packed.devices() != {transport.device}:
                raise AssertionError(f"executor {e}'s packed fetch left its device")
            gathered_blocks += len(bids)
            # D2H for the comparison only — the fetch itself ends in HBM
            host = np.asarray(packed).reshape(-1).view(np.uint8)
            for (row, length), bid in zip(entries.tolist(), bids):
                at = row * cluster.row_bytes
                check.add_block(bid.reduce_id, memoryview(host[at : at + length]))
            del packed, host
        out["gathered_blocks"] = gathered_blocks
        out.update(exchange_counts(cluster, mark))
        out["oracle"] = check.assert_matches(ctx.reference)
        out["lowering"] = check_lowerings(cluster, ctx.platform, n)
        if not out["lowering"]["gather"]:
            raise AssertionError("no block gather executable was built")
        out["seconds"] = {
            "run.write": round(t1 - t0, 3),
            "run.exchange": round(t2 - t1, 3),
            "run.device_fetch": round(t_gather, 3),
        }
        mgr.unregister_shuffle(0)
    return out


def phase_daemon(ctx) -> dict:
    from sparkucx_tpu.core.block import ShuffleBlockId
    from sparkucx_tpu.shuffle.daemon import DaemonClient, ShuffleDaemon

    blocks = ctx.blocks
    daemon = ShuffleDaemon(ctx.default_conf(), num_executors=ctx.n, port=0)
    try:
        cluster = daemon.manager.cluster
        mark = exchange_mark(cluster)
        # everything below speaks the socket protocol only
        client = DaemonClient(daemon.address)
        t0 = time.perf_counter()
        client.create_shuffle(0, len(blocks), NUM_REDUCERS)
        for m, parts in enumerate(blocks):
            writer = client.open_map_writer(0, m)
            for r in sorted(parts):
                client.write_partition(writer, r, parts[r])
            lengths = client.commit_map(writer)
            if int(lengths.sum()) != sum(len(b) for b in parts.values()):
                raise AssertionError(f"map {m} committed {int(lengths.sum())} bytes")
        t1 = time.perf_counter()
        client.run_exchange(0)
        t2 = time.perf_counter()
        check = GroupByCheck()
        for r in range(NUM_REDUCERS):
            bids = [ShuffleBlockId(0, m, r) for m in range(len(blocks))]
            for bid, payload in zip(bids, client.fetch_blocks(bids)):
                if payload is None:
                    raise AssertionError(f"daemon could not serve {bid}")
                check.add_block(r, payload)
        t3 = time.perf_counter()
        out = block_counts(blocks)
        out["rounds"] = len(cluster.meta(0).recv_sizes)
        out.update(exchange_counts(cluster, mark))
        out["oracle"] = check.assert_matches(ctx.reference)
        out["lowering"] = check_lowerings(cluster, ctx.platform, ctx.n)
        out["seconds"] = {
            "run.write": round(t1 - t0, 3),
            "run.exchange": round(t2 - t1, 3),
            "run.read": round(t3 - t2, 3),
        }
        client.remove_shuffle(0)
        client.close()
    finally:
        daemon.close()
    return out


PHASES = [
    ("main", phase_main),
    ("reuse", phase_reuse),
    ("device", phase_device),
    ("daemon", phase_daemon),
]


@dataclass
class Context:
    platform: str
    n: int
    shape: Shape
    blocks: list
    per_mapper: list
    reference: dict
    compiles: CompileCounter
    manager: object = None

    def default_conf(self):
        """The conf a user gets by default; only the tiny form touches it."""
        from sparkucx_tpu.config import TpuShuffleConf

        conf = TpuShuffleConf()
        if self.shape.staging_capacity is not None:
            conf.staging_capacity_per_executor = self.shape.staging_capacity
        return conf


def say(label: str, payload: dict) -> None:
    print(f"{label}: {json.dumps(payload)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--cpu-tiny", action="store_true",
        help="tier-1 form: JAX_PLATFORMS=cpu, a few MB, no chip needed",
    )
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported

    t_start = time.perf_counter()
    try:
        import jax

        from sparkucx_tpu import native
        from sparkucx_tpu.shuffle.manager import TpuShuffleManager
        from sparkucx_tpu.utils.compile_cache import enable_compile_cache

        devices = jax.devices()
    except (ImportError, RuntimeError) as e:
        print(f"chip_smoke: cannot start: {type(e).__name__}: {e}", file=sys.stderr)
        return NO_CHIP
    platform, kind, n = devices[0].platform, devices[0].device_kind, len(devices)
    if args.cpu_tiny:
        shape = TINY
    elif platform != "tpu" or kind not in KNOWN_KINDS:
        print(
            f"chip_smoke: no chip found — JAX offers {n} x {platform} ({kind!r}); "
            f"this smoke runs on {KNOWN_KINDS} (or, for tier-1, with --cpu-tiny)",
            file=sys.stderr,
        )
        return NO_CHIP
    else:
        shape = FULL

    import importlib.metadata as md

    def version(dist):
        try:
            return md.version(dist)
        except md.PackageNotFoundError:
            return None

    cache_dir = enable_compile_cache()
    device = {"platform": platform, "kind": kind, "count": n}
    header = {
        "device": device,
        "versions": {d: version(d) for d in ("jax", "jaxlib", "libtpu")},
        "native": {
            "available": native.native_available(),
            "built_in_this_run": native.built_here(),
            "build_error": native.build_error(),
        },
        "compile_cache": {
            "dir": cache_dir,
            "entries_at_start": len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        },
    }
    say("chip_smoke", header)

    compiles = CompileCounter()
    num_mappers = shape.mappers_per_chip * n
    t0 = time.perf_counter()
    blocks, per_mapper = make_records(args.seed, num_mappers, shape.pairs_per_mapper)
    reference = plain_groupby(per_mapper)
    if shape is TINY:
        cut = (
            f"tier-1 CPU form: {shape.mappers_per_chip} mappers per device ({num_mappers}), "
            f"{shape.pairs_per_mapper} pairs per mapper, staging capacity "
            f"{shape.staging_capacity} B instead of the default"
        )
    else:
        cut = (
            f"mappers {SOURCE_MAPPERS} -> {num_mappers} ({shape.mappers_per_chip} per chip = a "
            f"v5e-{DEPLOYMENT_CHIPS} chip's share, rounded up; {n} of {DEPLOYMENT_CHIPS} "
            "chips present)"
        )
    reduced = [
        cut,
        f"map output {SOURCE_MAPPERS * SOURCE_PAIRS * VALUE_BYTES / 1e9:.1f} GB -> "
        f"{block_counts(blocks)['bytes'] / 1e9:.3f} GB",
        f"kept: {NUM_REDUCERS} reducers, {VALUE_BYTES}-byte values, random int keys, "
        "hash partitioning",
    ]
    say("records", {
        **block_counts(blocks),
        "groups": len(reference),
        "seconds": {"setup.records": round(time.perf_counter() - t0, 3)},
    })

    ctx = Context(platform, n, shape, blocks, per_mapper, reference, compiles)
    results: Dict[str, dict] = {}
    failed = None
    if native.build_error() is not None:
        failed = "native"
        print(f"FAIL native: {native.build_error()}", flush=True)
    else:
        ctx.manager = TpuShuffleManager(ctx.default_conf(), num_executors=n)
        try:
            for name, phase in PHASES:
                before = compiles.snapshot()
                try:
                    result = phase(ctx)
                except Exception as e:  # the phase boundary: name it, stop, exit 1
                    failed = name
                    print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                    break
                result["compile"] = compiles.since(before)
                results[name] = result
                say(name, result)
        finally:
            ctx.manager.stop()

    mesh = [
        {"executor": e, "id": d.id, "coords": list(getattr(d, "coords", None) or []) or None}
        for e, d in enumerate(ctx.manager.cluster.mesh.devices.reshape(-1))
    ] if ctx.manager is not None else None
    peak = []
    for d in devices:
        stats = d.memory_stats()
        peak.append(stats.get("peak_bytes_in_use") if stats else None)
    if failed is None and n > 1 and platform == "tpu" and not all(peak):
        failed = "spread"
        print(f"FAIL spread: a device's peak HBM stayed zero: {peak}", flush=True)

    summary = {
        "ok": failed is None,
        "source": SOURCE,
        "reduced": reduced,
        "seed": args.seed,
        "mesh": mesh,
        "peak_bytes_in_use": peak,
        "phases": list(results),
        "wall_seconds": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    if failed is not None:
        summary["failed_phase"] = failed
    say("summary", summary)
    # the driver's contract: the last line holds these two keys and no other
    print(json.dumps({"ok": failed is None, "device": device}), flush=True)
    return 0 if failed is None else 1


if __name__ == "__main__":
    sys.exit(main())

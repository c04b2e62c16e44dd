#!/usr/bin/env python
"""Hardware acceptance smoke: every device-resident op vs its oracle, one command.

The reference validates hardware with live-cluster Spark jobs (buildlib/
test.sh); this is the TPU-native equivalent for one chip or one multi-chip
host (or any backend): small-shape oracle drives of the exchange, the Pallas
gather, the distributed sort, the columnar shuffle, the hierarchical route,
the full store -> commit -> exchange -> fetch stack, the relational operators
(GROUP BY + hash join), and the transitive closure — then one
compile-and-oracle attempt, at a non-toy shape, for each Pallas kernel that is
OFF the default path (the scheduled ring, the fused scatter+ring, the fused
ring+combine, the DMA block scatter, the radix sort).  Those five are
TPU-only lowerings: on any other backend they skip by name (tier-1 runs their
interpreter forms in tests/).  Exit 0 = every drive passed or skipped.

Run on the chip (default) or any backend:

    python scripts/tpu_smoke.py              # whatever jax.devices() offers
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/tpu_smoke.py          # the CI form (dense lowerings)

Each drive prints ``ok: <name> [impl=...] (<seconds>)``; failures print the
op's own diagnostics (for a kernel that does not compile, the compiler's).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _drive(name):
    def deco(fn):
        fn._drive_name = name
        return fn
    return deco


@_drive("exchange vs oracle")
def drive_exchange():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import (
        ExchangeSpec, build_exchange, make_mesh, oracle_exchange,
        pack_chunks_slots, unpack_received,
    )

    n = min(4, len(jax.devices()))
    slot = 64
    spec = ExchangeSpec(num_executors=n, send_rows=n * slot, recv_rows=n * slot)
    mesh = make_mesh(n)
    fn = build_exchange(mesh, spec)
    rng = np.random.default_rng(0)
    per_dev = [
        [rng.integers(0, 256, size=int(rng.integers(0, slot * 256)), dtype=np.uint8).tobytes()
         for _ in range(n)]
        for _ in range(n)
    ]
    bufs, sizes = zip(*[
        pack_chunks_slots(chunks, slot, spec.row_bytes) for chunks in per_dev
    ])
    sh = NamedSharding(mesh, P("ex", None))
    recv, rs = fn(
        jax.device_put(np.concatenate(bufs), sh),
        jax.device_put(np.stack(sizes), sh),
    )
    recv_h = np.asarray(recv).reshape(n, -1)
    rs_h = np.asarray(rs)
    # the shared oracle concatenates raw chunks; the wire carries each chunk
    # row-padded, so compare per-sender chunks with padding stripped
    expect = oracle_exchange(per_dev)
    for j in range(n):
        parts = unpack_received(recv_h[j].view(np.uint8).tobytes(), rs_h[j], spec.row_bytes)
        got = b"".join(
            part[: len(chunk)] for part, chunk in
            zip(parts, (per_dev[i][j] for i in range(n)))
        )
        assert got == expect[j], f"receiver {j} diverged from oracle"
    return fn.spec.impl


@_drive("block gather vs oracle")
def drive_gather():
    import jax

    from sparkucx_tpu.ops.pallas_kernels import build_block_gather, pack_plan

    rng = np.random.default_rng(1)
    src = jax.device_put(rng.integers(-100, 100, size=(4096, 128), dtype=np.int32))
    plan = [(0, 512), (1536, 2048), (1024, 100), (3584, 512 * 97)]
    starts, counts, outs, total = pack_plan(plan, 512)
    fn = build_block_gather(len(plan), total)
    out = np.asarray(fn(*(jax.device_put(a) for a in (starts, counts, outs)), src))
    src_h = np.asarray(src)
    for (off, ln), s, c, o in zip(plan, starts, counts, outs):
        assert (out[o : o + c] == src_h[s : s + c]).all(), f"block at {off} diverged"
    return fn.impl


@_drive("distributed sort vs oracle")
def drive_sort():
    import jax

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_distributed_sort

    n = min(4, len(jax.devices()))
    cap = 512
    spec = SortSpec(num_executors=n, capacity=cap,
                    recv_capacity=cap if n == 1 else 2 * cap, width=24)
    rng = np.random.default_rng(2)
    total = n * cap - 13
    keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint64).astype(np.uint32)
    payload = rng.integers(-100, 100, size=(total, 24)).astype(np.int32)
    sk, sp = run_distributed_sort(make_mesh(n), spec, keys, payload)
    ek, ep = oracle_sort(keys, payload)
    assert (sk == ek).all() and (sp == ep).all(), "sort diverged from oracle"
    return spec.resolve_impl().impl


@_drive("columnar shuffle vs oracle")
def drive_columnar():
    import jax

    from sparkucx_tpu.ops.columnar import ColumnarSpec, run_columnar_shuffle
    from sparkucx_tpu.ops.exchange import make_mesh

    n = min(4, len(jax.devices()))
    cap = 256
    spec = ColumnarSpec(num_executors=n, capacity=cap,
                        recv_capacity=cap if n == 1 else 2 * cap, width=8)
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(n * cap, 8)).astype(np.float32)
    owners = rng.integers(0, n, size=n * cap).astype(np.int32)
    mesh = make_mesh(n)
    recv, counts = run_columnar_shuffle(mesh, spec, rows, owners)
    counts_h = np.asarray(counts)
    assert int(counts_h.sum()) == n * cap, "columnar shuffle dropped rows"
    # every destination's shard holds exactly its rows (as a multiset)
    recv_h = np.asarray(recv).reshape(n, -1, 8)
    for j in range(n):
        mine = rows[owners == j]
        got = recv_h[j][: len(mine)]
        assert sorted(map(tuple, got.tolist())) == sorted(map(tuple, mine.tolist())), (
            f"destination {j} row multiset diverged"
        )
    return spec.resolve_impl().impl


@_drive("full store stack (stage→commit→exchange→fetch, incl. device batch fetch)")
def drive_stack():
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
    from sparkucx_tpu.core.operation import OperationStatus
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    conf = TpuShuffleConf(
        staging_capacity_per_executor=1 << 20, num_executors=1,
        keep_device_recv=True,  # so the device-side batch fetch can run
    )
    cluster = TpuShuffleCluster(conf, num_executors=1)
    M, R = 4, 8
    meta = cluster.create_shuffle(0, M, R)
    rng = np.random.default_rng(4)
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
        assert buf.host_view()[: buf.size].tobytes() == expect, f"fetch ({m},{r}) diverged"
    # device-side batch fetch: the Pallas/XLA gather through the transport
    bids = [ShuffleBlockId(0, m, 0) for m in range(M)]
    packed, entries = t.fetch_blocks_device(bids)
    packed_bytes = np.asarray(packed).reshape(-1).view(np.uint8)
    for (row_start, length), bid in zip(entries, bids):
        start = int(row_start) * cluster.row_bytes
        got = packed_bytes[start : start + int(length)].tobytes()
        assert got == oracle[(bid.map_id, bid.reduce_id)], f"device fetch {bid} diverged"
    cluster.remove_shuffle(0)
    return "auto"


@_drive("hierarchical 2-slice route vs oracle")
def drive_hierarchy():
    import jax
    from jax.sharding import Mesh

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.transport.tpu import TpuShuffleCluster

    devs = jax.devices()
    if len(devs) < 4 or len(devs) % 2:
        return "skipped (needs >=4 even devices; single-chip backends exercise the flat route)"
    n = min(8, len(devs) - len(devs) % 2)
    mesh = Mesh(np.array(devs[:n]), ("ex",))
    conf = TpuShuffleConf(
        staging_capacity_per_executor=n * 4096, num_executors=n, num_slices=2
    )
    cluster = TpuShuffleCluster(conf, mesh=mesh)
    meta = cluster.create_shuffle(0, n, n)
    rng = np.random.default_rng(5)
    oracle = {}
    for m in range(n):
        t = cluster.transport(meta.map_owner[m])
        w = t.store.map_writer(0, m)
        for r in range(n):
            payload = rng.integers(0, 256, size=int(rng.integers(1, 300)), dtype=np.uint8).tobytes()
            oracle[(m, r)] = payload
            w.write_partition(r, payload)
        t.commit_block(w.commit().pack())
    cluster.run_exchange(0)
    for (m, r), expect in oracle.items():
        view, ln = cluster.locate_received_block(meta.owner_of_reduce(r), 0, m, r)
        assert view.tobytes() == expect, f"hierarchical block ({m},{r}) diverged"
    cluster.remove_shuffle(0)
    return "two-phase"


@_drive("grouped aggregate + hash join vs oracle")
def drive_relational():
    import jax

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.relational import (
        AggregateSpec,
        oracle_aggregate,
        oracle_join,
        run_grouped_aggregate,
        run_hash_join,
    )

    n = min(4, len(jax.devices()))
    mesh = make_mesh(n)
    rng = np.random.default_rng(21)
    total = 6000
    keys = rng.integers(0, 64, size=total).astype(np.uint32)
    values = rng.integers(-1000, 1000, size=(total, 2)).astype(np.int32)
    spec = AggregateSpec(
        num_executors=n, capacity=-(-total // n), recv_capacity=4 * -(-total // n),
        aggs=("sum", "max"),
    )
    gk, gv, gc = run_grouped_aggregate(mesh, spec, keys, values)
    wk, wv, wc = oracle_aggregate(keys, values, spec.aggs)
    assert np.array_equal(gk, wk) and np.array_equal(gv, wv) and np.array_equal(gc, wc)

    # PK-FK join through the capacity-planning host driver (raises its own
    # precise diagnostics if the device placement diverges from the host plan)
    nb, nprobe = 512, 2048
    bkeys = rng.permutation(nb).astype(np.uint32)
    pkeys = bkeys[rng.integers(0, nb, size=nprobe)]
    bvals = rng.integers(-50, 50, size=(nb, 1)).astype(np.int32)
    pvals = rng.integers(-50, 50, size=(nprobe, 1)).astype(np.int32)
    jk, jb, jp = run_hash_join(mesh, bkeys, bvals, pkeys, pvals)
    got = sorted(zip(jk.tolist(), jb[:, 0].tolist(), jp[:, 0].tolist()))
    wk_, wb, wp = oracle_join(bkeys, bvals, pkeys, pvals)
    want = sorted(zip(wk_.tolist(), wb[:, 0].tolist(), wp[:, 0].tolist()))
    assert got == want, f"join rows diverged ({len(got)} vs {len(want)})"
    return spec.resolve_impl(mesh.devices.reshape(-1)[0].platform).impl


@_drive("transitive closure vs oracle")
def drive_tc():
    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.tc import TcSpec, oracle_tc, run_transitive_closure

    import jax

    n = min(4, len(jax.devices()))
    mesh = make_mesh(n)
    rng = np.random.default_rng(22)
    edges = rng.integers(0, 48, size=(120, 2)).astype(np.uint32)
    want = oracle_tc(edges)
    cap = max(4096 // n, 512)
    spec = TcSpec(num_executors=n, edge_capacity=cap, tc_capacity=cap, join_capacity=4 * cap)
    pairs, rounds = run_transitive_closure(mesh, spec, edges)
    # the driver's contract is ascending-unique — compare directly, no
    # np.unique laundering of a dedup/order regression
    assert np.array_equal(pairs, want), "closure pairs diverged"
    return spec.resolve_impl(mesh.devices.reshape(-1)[0].platform).impl


def _tpu_ring(min_devices: int = 2):
    """(mesh width, skip reason) for the remote-DMA kernels: they are TPU-only
    and need a ring of at least two chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return 0, "skipped (TPU-only remote-DMA kernel; tests/ run its interpreter form)"
    if len(devs) < min_devices:
        return 0, f"skipped (needs >= {min_devices} chips; this host has {len(devs)})"
    return min(4, len(devs)), None


def _ring_inputs(n: int, slot: int, lane: int, seed: int):
    """Seeded slot-layout staging with ragged per-peer sizes, plus the plain
    reference: receiver j holds, sender-major, the used prefix of every
    sender's slot j."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
    data = rng.integers(-100, 100, size=(n * n * slot, lane), dtype=np.int32)
    send_rows = n * slot
    want = [
        np.concatenate([
            data[i * send_rows + j * slot : i * send_rows + j * slot + sizes[i, j]]
            for i in range(n)
        ])
        for j in range(n)
    ]
    return sizes, data, want


def _assert_received(recv, recv_sizes, sizes, want, n: int, what: str) -> None:
    recv_h = np.asarray(recv).reshape(n, -1, recv.shape[-1])
    assert np.array_equal(np.asarray(recv_sizes), sizes.T), f"{what}: recv_sizes diverged"
    for j in range(n):
        assert np.array_equal(recv_h[j][: len(want[j])], want[j]), (
            f"{what}: receiver {j} diverged from the plain reference"
        )


@_drive("scheduled ring exchange (ring_exchange_grid) vs plain reference")
def drive_ring_exchange():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, make_mesh
    from sparkucx_tpu.ops.ici_exchange import DEFAULT_CHUNKS_PER_DEST, build_ici_exchange

    n, skip = _tpu_ring()
    if skip:
        return skip
    slot, lane = 8192, 128  # 4 MiB per peer slot
    spec = ExchangeSpec(num_executors=n, send_rows=n * slot, recv_rows=n * slot, lane=lane)
    mesh = make_mesh(n)
    fn = build_ici_exchange(mesh, spec, chunks_per_dest=DEFAULT_CHUNKS_PER_DEST)
    assert fn.lowering == "dma", f"ring lowered to {fn.lowering!r}, not the DMA kernel"
    sizes, data, want = _ring_inputs(n, slot, lane, seed=31)
    sh = NamedSharding(mesh, P("ex", None))
    recv, rs = fn(jax.device_put(data, sh), jax.device_put(sizes, sh))
    _assert_received(recv, rs, sizes, want, n, "ring exchange")
    return f"{fn.lowering}, n={n}, {fn.schedule.num_steps} supersteps"


@_drive("fused scatter + ring (fused_scatter_ring_grid) vs plain reference")
def drive_fused_scatter_ring():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.exchange import ExchangeSpec, make_mesh
    from sparkucx_tpu.ops.ici_exchange import (
        DEFAULT_CHUNKS_PER_DEST, build_fused_ici_exchange,
    )

    n, skip = _tpu_ring()
    if skip:
        return skip
    slot, lane = 8192, 128
    send_rows = n * slot
    spec = ExchangeSpec(num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane)
    mesh = make_mesh(n)
    fn = build_fused_ici_exchange(
        mesh, spec, n, chunks_per_dest=DEFAULT_CHUNKS_PER_DEST, max_block_rows=slot
    )
    assert fn.lowering == "dma", f"fused ring lowered to {fn.lowering!r}"
    # one block per destination: packed back to back per sender, scattered to
    # the head of each destination slot (the build_block_scatter plan triple)
    sizes, staged, want = _ring_inputs(n, slot, lane, seed=32)
    starts = np.tile(np.arange(n, dtype=np.int32) * slot, (n, 1))
    outs = (np.cumsum(sizes, axis=1) - sizes).astype(np.int32)
    packed = np.zeros_like(staged)
    for i in range(n):
        for j in range(n):
            c = sizes[i, j]
            src = i * send_rows + j * slot
            packed[i * send_rows + outs[i, j] : i * send_rows + outs[i, j] + c] = (
                staged[src : src + c]
            )
    sh = NamedSharding(mesh, P("ex", None))
    recv, rs = fn(
        jax.device_put(starts, sh), jax.device_put(sizes, sh), jax.device_put(outs, sh),
        jax.device_put(packed, sh), jax.device_put(np.zeros_like(staged), sh),
        jax.device_put(sizes, sh),
    )
    _assert_received(recv, rs, sizes, want, n, "fused scatter+ring")
    return f"{fn.lowering}, n={n}"


@_drive("fused ring + combine (ring_combine_grid) vs the scheduled-XLA lowering")
def drive_ring_combine():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.combine import CombineSpec, acc_init
    from sparkucx_tpu.ops.exchange import ExchangeSpec, make_mesh
    from sparkucx_tpu.ops.ici_exchange import (
        DEFAULT_CHUNKS_PER_DEST, build_combine_exchange,
    )

    n, skip = _tpu_ring()
    if skip:
        return skip
    cspec = CombineSpec(num_groups=1024, aggs=("sum", "min", "max", "avg"))
    slot, lane = 4096, cspec.row_width
    send_rows = n * slot
    spec = ExchangeSpec(num_executors=n, send_rows=send_rows, recv_rows=send_rows, lane=lane)
    mesh = make_mesh(n)
    fused, reference = (
        build_combine_exchange(
            mesh, spec, cspec, chunks_per_dest=DEFAULT_CHUNKS_PER_DEST, lowering=low
        )
        for low in ("auto", "xla")
    )
    assert fused.lowering == "dma", f"fused combine lowered to {fused.lowering!r}"
    # seeded partial-aggregate rows [key | values | count] up to each ragged
    # per-peer size; padding rows stay all-zero (count 0)
    rng = np.random.default_rng(33)
    sizes = rng.integers(1, slot + 1, size=(n, n)).astype(np.int32)
    data = np.zeros((n * send_rows, lane), dtype=np.int32)
    for i in range(n):
        for j in range(n):
            c, base = int(sizes[i, j]), i * send_rows + j * slot
            data[base : base + c, 0] = rng.integers(0, cspec.num_groups, size=c)
            data[base : base + c, 1:-1] = rng.integers(-100, 100, size=(c, cspec.width))
            data[base : base + c, -1] = rng.integers(1, 5, size=c)
    av0, ac0 = (np.tile(np.asarray(a), (n, 1)) for a in acc_init(cspec))
    sh = NamedSharding(mesh, P("ex", None))

    def run(fn):
        # fresh uploads per call: the accumulator operands are donated
        out = fn(
            jax.device_put(data, sh), jax.device_put(sizes, sh),
            jax.device_put(av0, sh), jax.device_put(ac0, sh),
        )
        return [np.asarray(x) for x in out]

    for got, want, what in zip(run(fused), run(reference), ("values", "counts", "recv_sizes")):
        assert np.array_equal(got, want), f"fused combine {what} diverged from scheduled XLA"
    return f"{fused.lowering}, n={n}, {cspec.num_groups} groups"


@_drive("DMA block scatter (build_block_scatter impl='dma') vs plain reference")
def drive_scatter_dma():
    import jax

    from sparkucx_tpu.ops.pallas_kernels import build_block_scatter

    if jax.devices()[0].platform != "tpu":
        return "skipped (TPU-only dynamic-size DMA; tests/ run the tiled form in the interpreter)"
    # 64 GroupByTest-sized blocks (1,222 rows: not a multiple of the 8-row
    # tile) scattered into a 64 MiB slot-layout staging
    blocks, rows, out_rows, lane = 64, 1222, 1 << 17, 128
    rng = np.random.default_rng(34)
    packed = rng.integers(-100, 100, size=(blocks * rows, lane), dtype=np.int32)
    starts = (rng.permutation(blocks) * 2048).astype(np.int32)
    counts = np.full(blocks, rows, dtype=np.int32)
    outs = (np.arange(blocks) * rows).astype(np.int32)
    fn = build_block_scatter(blocks, out_rows, impl="dma", max_block_rows=2048)
    dst = np.full((out_rows, lane), 7, dtype=np.int32)
    got = np.asarray(fn(*(jax.device_put(a) for a in (starts, counts, outs, packed, dst))))
    want = dst.copy()
    for s, o in zip(starts, outs):
        want[s : s + rows] = packed[o : o + rows]
    assert np.array_equal(got, want), "scattered staging diverged"
    return fn.impl


@_drive("radix sort (ops/radix.py) vs oracle")
def drive_radix():
    import jax

    from sparkucx_tpu.ops.exchange import make_mesh
    from sparkucx_tpu.ops.sort import SortSpec, oracle_sort, run_distributed_sort

    if jax.devices()[0].platform != "tpu":
        return "skipped (Mosaic kernel; tests/test_radix.py runs it in the interpreter)"
    # 1M TeraSort-shaped rows: uint32 key + 96-byte payload
    total, width = 1 << 20, 24
    spec = SortSpec(num_executors=1, capacity=total, recv_capacity=total, width=width,
                    impl="radix")
    rng = np.random.default_rng(35)
    keys = rng.integers(0, 1 << 32, size=total, dtype=np.uint64).astype(np.uint32)
    payload = rng.integers(-100, 100, size=(total, width)).astype(np.int32)
    sk, sp = run_distributed_sort(make_mesh(1), spec, keys, payload)
    ek, ep = oracle_sort(keys, payload)
    assert (sk == ek).all() and (sp == ep).all(), "radix sort diverged from oracle"
    return spec.impl


DRIVES = [
    drive_exchange, drive_gather, drive_sort, drive_columnar, drive_stack,
    drive_hierarchy, drive_relational, drive_tc,
    # off the default path: one compile-and-oracle attempt each, TPU only
    drive_ring_exchange, drive_fused_scatter_ring, drive_ring_combine,
    drive_scatter_dma, drive_radix,
]


def main() -> int:
    import jax

    from sparkucx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    devs = jax.devices()
    print(f"backend: {devs[0].platform} x {len(devs)} ({devs[0].device_kind})", flush=True)
    failed = 0
    for drive in DRIVES:
        t0 = time.time()
        try:
            impl = drive()
            print(f"ok: {drive._drive_name} [impl={impl}] ({time.time() - t0:.1f}s)", flush=True)
        except Exception as e:
            failed += 1
            print(f"FAIL: {drive._drive_name}: {type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"SMOKE: {failed}/{len(DRIVES)} drives FAILED")
        return 1
    print(f"SMOKE: all {len(DRIVES)} drives passed")  # skipped drives say so in their impl tag
    return 0


if __name__ == "__main__":
    sys.exit(main())

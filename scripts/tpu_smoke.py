#!/usr/bin/env python
"""The scheduled-ring plane's chip evidence: its two Pallas kernels, compiled by
the chip's compiler and compared with a plain reference, one command.

``ops/ici_exchange.py`` (``spark.shuffle.tpu.exchange.impl=pallas``) is off
the default path and no cell of the benchmark runs it, so nothing else shows
that ``pallas_kernels.ring_exchange_grid`` and ``fused_scatter_ring_grid``
still compile under the chip's libtpu and move the right rows over real ICI
links (tier-1 runs their bodies in Pallas' interpreter, which has no tiling
and no remote DMA).  This script is that evidence and nothing more: every
other device op is held to an oracle by tier-1 on the CPU mesh and by the
benchmark's cells on the chip.  It goes when that plane goes (ROADMAP.md
queue 3).

    chiprun --chips 4 -- python3 scripts/tpu_smoke.py

Both drives need a TPU ring of at least two chips; anywhere else they skip by
name.  Each prints ``ok: <name> [impl=...] (<seconds>)``; a failure prints the
compiler's or the comparison's message.  Exit 0 = every drive passed or
skipped.
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


def _tpu_ring(min_devices: int = 2):
    """(mesh width, skip reason) for the remote-DMA kernels: they are TPU-only
    and need a ring of at least two chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return 0, "skipped (TPU-only remote-DMA kernel; tests/test_ici_exchange.py runs its interpreter form)"
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


DRIVES = [drive_ring_exchange, drive_fused_scatter_ring]


def main() -> int:
    import jax

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

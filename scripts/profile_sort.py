#!/usr/bin/env python
"""Sort-lowering head-to-head: the compiled n=1 sort body vs its pieces.

Answers "where does the TeraSort step's time go, and what could beat it" with
one table.  Variants:

* the full jitted ``_sort_body_single`` (what ``bench.py`` measures),
* ``jnp.argsort`` alone, argsort + key gather, argsort + both gathers,
* keys-only ``jnp.sort`` (no index production) and batched argsort
  ([chunks, rows/chunk] — XLA's batched sort costs ~the keys-only sort,
  the basis for any two-level scheme),
* ``sort_key_val`` (what argsort lowers to).

Methodology: best-of-3 chained windows ending in ``block_until_ready``.
Data generated ON DEVICE.  Run on any backend; numbers only mean something on
the chip:

    python scripts/profile_sort.py [-n ROWS] [-w WINDOW]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", "--rows", type=int, default=1 << 21)
    ap.add_argument("-w", "--window", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkucx_tpu.ops.exchange import gather_rows, make_mesh
    from sparkucx_tpu.ops.sort import SortSpec, build_distributed_sort

    N, W = args.rows, args.window
    print(f"backend: {jax.devices()[0].platform}, rows={N}, window={W}", flush=True)

    mesh = make_mesh(1)
    spec = SortSpec(num_executors=1, capacity=N, recv_capacity=N, width=24)
    full = build_distributed_sort(mesh, spec)

    @jax.jit
    def gen():
        k = jax.random.bits(jax.random.key(0), (N,), jnp.uint32)
        p = jax.lax.bitcast_convert_type(
            jax.random.bits(jax.random.key(1), (N, 24), jnp.uint32), jnp.int32
        )
        return k, p

    keys, pay = jax.block_until_ready(gen())
    nv = jax.device_put(np.full(1, N, np.int32))
    readback = jax.jit(lambda x: x.ravel()[:4])

    def timed(name, f, *fargs, rows=N):
        o = f(*fargs)
        jax.block_until_ready(o)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            outs = [f(*fargs) for _ in range(W)]
            jax.block_until_ready(outs)
            np.asarray(readback(jax.tree_util.tree_leaves(outs[-1])[0]))
            best = min(best, (time.perf_counter() - t0) / W)
        print(f"{name:44s} {best*1e3:8.2f} ms  {rows/best/1e6:7.1f} M rows/s", flush=True)
        return best

    timed("full sort body (impl=single)", full, keys, pay, nv)
    timed("argsort u32", jax.jit(lambda k: jnp.argsort(k)), keys)
    timed("argsort + key gather", jax.jit(lambda k: k[jnp.argsort(k)]), keys)

    def body_like(k, p):
        order = jnp.argsort(k)
        return k[order], gather_rows(p, order)

    timed("argsort + key gather + payload gather", jax.jit(body_like), keys, pay)
    timed("sort u32 keys only", jax.jit(lambda k: jnp.sort(k)), keys)
    chunks = 256
    nb = (N // chunks) * chunks  # round down so the variant always runs
    bkeys = keys if nb == N else jax.jit(lambda k: k[:nb])(keys)
    timed(
        f"argsort batched [{chunks},{nb // chunks}]"
        + ("" if nb == N else f" (first {nb} rows)"),
        jax.jit(lambda k: jnp.argsort(k.reshape(chunks, -1), axis=1)),
        bkeys,
        rows=nb,
    )
    timed(
        "sort_key_val (k, iota)",
        jax.jit(lambda k: jax.lax.sort_key_val(k, jnp.arange(N, dtype=jnp.int32))),
        keys,
    )

    # The contender: the Pallas LSD radix sort whose scatter moves
    # key+payload together by segment DMA (ops/radix.py).  Mosaic-only — the interpreter path would measure the
    # emulator, so off-TPU this section just says so.
    if jax.devices()[0].platform == "tpu":
        from sparkucx_tpu.ops.radix import build_radix_sort

        fused = jax.jit(
            lambda k, p: jnp.concatenate(
                [jax.lax.bitcast_convert_type(k, jnp.int32)[:, None], p], axis=1
            )
        )
        rows_fused = jax.block_until_ready(fused(keys, pay))
        try:
            timed(
                "radix LSD 8x4bit, fused 100 B rows (Pallas)",
                build_radix_sort(N, 25), rows_fused,
            )
            rspec = SortSpec(
                num_executors=1, capacity=N, recv_capacity=N, width=24, impl="radix"
            )
            timed("full sort body (impl=radix)", build_distributed_sort(mesh, rspec), keys, pay, nv)
        except Exception as e:  # first hardware run of the kernel: report, don't die
            print(f"radix variant failed: {type(e).__name__}: {e}", flush=True)
    else:
        print("radix variants: skipped (Mosaic kernel; TPU only)", flush=True)


if __name__ == "__main__":
    main()

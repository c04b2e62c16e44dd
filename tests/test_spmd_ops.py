"""Multi-controller device ops: two OS processes (2 CPU devices each) run the
distributed sort as one SPMD program over the 4-device global mesh.

test_spmd.py proves the byte shuffle is multi-controller; this proves the
device-resident *workloads* (ops/sort.py and, by the same construction,
columnar/relational/tc) are too — the jitted step is plain SPMD over a global
mesh, so the only multi-host-specific code is array construction from
process-local shards."""

import os
import socket
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, {root!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    pid = int(sys.argv[1]); coord = sys.argv[2]
    jax.distributed.initialize(coord, num_processes=2, process_id=pid)
    assert len(jax.devices()) == 4, jax.devices()

    from sparkucx_tpu.ops.sort import SortSpec, build_distributed_sort

    N_EXEC, CAP = 4, 512
    mesh = Mesh(np.array(jax.devices()), ("ex",))
    spec = SortSpec(
        num_executors=N_EXEC, capacity=CAP, recv_capacity=2 * CAP, width=2,
        impl="dense",
    )
    fn = build_distributed_sort(mesh, spec)

    # both processes generate the SAME global input; each contributes only its
    # process-local shards
    rng = np.random.default_rng(99)
    keys = rng.integers(0, 1 << 31, size=N_EXEC * CAP, dtype=np.uint32)
    payload = rng.integers(-100, 100, size=(N_EXEC * CAP, 2), dtype=np.int32)
    nv = np.full(N_EXEC, CAP, np.int32)

    key_sh = NamedSharding(mesh, P("ex"))
    row_sh = NamedSharding(mesh, P("ex", None))
    gkeys = jax.make_array_from_process_local_data(key_sh, keys[pid * 2 * CAP : (pid + 1) * 2 * CAP])
    gpay = jax.make_array_from_process_local_data(row_sh, payload[pid * 2 * CAP : (pid + 1) * 2 * CAP])
    gnv = jax.make_array_from_process_local_data(key_sh, nv[pid * 2 : (pid + 1) * 2])

    out_keys, out_pay, counts = fn(gkeys, gpay, gnv)

    from jax.experimental import multihost_utils
    all_counts = np.asarray(multihost_utils.process_allgather(counts, tiled=True))
    assert all_counts.sum() == N_EXEC * CAP, all_counts
    bounds = np.concatenate([[0], np.cumsum(all_counts)])
    oracle_keys = np.sort(keys)

    # each process verifies ITS local output shards against the oracle range
    checked = 0
    for shard in out_keys.addressable_shards:
        j = shard.index[0].start // (2 * CAP)  # global executor of this shard
        got = np.asarray(shard.data)[: all_counts[j]]
        want = oracle_keys[bounds[j] : bounds[j + 1]]
        assert np.array_equal(got, want), f"shard {{j}} keys mismatch"
        checked += 1
    assert checked == 2, checked
    print(f"CHILD_PASS pid={{pid}} shards={{checked}}", flush=True)
    """
)


CHILD_COMBINE = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, {root!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from dataclasses import replace
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    pid = int(sys.argv[1]); coord = sys.argv[2]
    jax.distributed.initialize(coord, num_processes=2, process_id=pid)
    assert len(jax.devices()) == 4, jax.devices()

    from sparkucx_tpu.ops.relational import AggregateSpec, build_grouped_aggregate

    N_EXEC, CAP = 4, 256
    mesh = Mesh(np.array(jax.devices()), ("ex",))
    spec = AggregateSpec(
        num_executors=N_EXEC, capacity=CAP, recv_capacity=CAP,
        aggs=("sum", "min", "max"), partial=True,
        combine="dense", combine_groups=64,
    )
    # both planes must derive the SAME plan/tier in lockstep: the spec is
    # static and identical in every process, the bodies are pure SPMD
    fused = build_grouped_aggregate(mesh, spec)
    unfused = build_grouped_aggregate(mesh, replace(spec, combine="off"))

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 60, size=N_EXEC * CAP).astype(np.uint32)
    vals = rng.integers(-100, 100, size=(N_EXEC * CAP, 3)).astype(np.int32)
    nv = np.full(N_EXEC, CAP, np.int32)

    key_sh = NamedSharding(mesh, P("ex"))
    row_sh = NamedSharding(mesh, P("ex", None))
    lo, hi = pid * 2 * CAP, (pid + 1) * 2 * CAP
    args = (
        jax.make_array_from_process_local_data(key_sh, keys[lo:hi]),
        jax.make_array_from_process_local_data(row_sh, vals[lo:hi]),
        jax.make_array_from_process_local_data(key_sh, nv[pid * 2 : (pid + 1) * 2]),
    )

    from jax.experimental import multihost_utils
    got = [
        np.asarray(multihost_utils.process_allgather(o, tiled=True))
        for o in fused(*args)
    ]
    ref = [
        np.asarray(multihost_utils.process_allgather(o, tiled=True))
        for o in unfused(*args)
    ]
    for a, b in zip(ref, got):
        assert a.tobytes() == b.tobytes(), "fused != unfused over 2 processes"
    assert got[3].sum() == 60, got[3]  # 60 distinct keys across all shards
    print(f"CHILD_PASS pid={{pid}} groups={{int(got[3].sum())}}", flush=True)
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_spmd_sort():
    coord = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=ROOT, env=env,
        )
        for pid in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for pid, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"child {pid} failed:\n{out[-3000:]}"
            assert f"CHILD_PASS pid={pid}" in out, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_two_process_spmd_fused_combine():
    """The compute-in-exchange aggregate over TWO OS PROCESSES: the fused
    ring fold runs as lockstep SPMD collectives (same static spec -> same
    tier in every process) and reproduces the unfused bytes exactly."""
    coord = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    script = CHILD_COMBINE.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=ROOT, env=env,
        )
        for pid in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for pid, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"child {pid} failed:\n{out[-3000:]}"
            assert f"CHILD_PASS pid={pid}" in out, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

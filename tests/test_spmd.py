"""True multi-controller test: two OS processes, each owning CPU devices, run
the collective exchange in lockstep over gloo — the multi-host deployment shape
(one process per TPU host) exercised without TPU hardware.

Covers: jax.distributed bootstrap, driver/executor address exchange for the peer
plane, MapperInfo commit broadcast (AM id 2), the global-mesh collective from
per-process shards, and post-exchange reads vs a deterministic oracle.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = textwrap.dedent(
    """
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, {root!r})
    import numpy as np
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.parallel.bootstrap import ExecutorEndpoint
    from sparkucx_tpu.transport.spmd import SpmdShuffleExecutor

    pid = int(sys.argv[1]); coord = sys.argv[2]; driver_host, driver_port = sys.argv[3].split(":")
    conf = TpuShuffleConf(
        staging_capacity_per_executor=int(os.environ.get("TEST_STAGING", str(1 << 20))),
        num_slices=int(os.environ.get("TEST_NUM_SLICES", "1")),
        host_recv_mode=os.environ.get("TEST_HOST_RECV_MODE", "array"),
        spill_dir=os.environ.get("TEST_SPILL_DIR") or None,
        slot_quota_rows=int(os.environ.get("TEST_SLOT_QUOTA_ROWS", "0")),
        exchange_impl=os.environ.get("TEST_EXCHANGE_IMPL", "stock"),
    )
    ex = SpmdShuffleExecutor(conf, coordinator_address=coord, num_processes=2, process_id=pid)
    assert ex.num_executors == 2, ex.num_executors
    addr = ex.init()
    ep = ExecutorEndpoint((driver_host, int(driver_port)), ex.executor_id, ex.peer)
    ep.register(addr)
    deadline = time.monotonic() + 30
    other = 1 - pid
    while other not in ep.known and time.monotonic() < deadline:
        time.sleep(0.01)
    assert other in ep.known, "peer never introduced"

    M, R = 4, 4
    def payload(m, r):
        rng = np.random.default_rng(100 * m + r)
        return rng.integers(0, 256, size=int(rng.integers(1, 1500)), dtype=np.uint8).tobytes()

    jobs = int(os.environ.get("TEST_JOBS", "1"))
    for sid in range(jobs):  # a later job of the process writes into round buffers the one before gave back
        if sid:
            ex.remove_shuffle(sid - 1)
        early = ex.store.write_stats()
        ex.create_shuffle(sid, M, R)
        for m in range(M):
            if ex.map_owner(m) != ex.executor_id:
                continue
            w = ex.store.map_writer(sid, m)
            for r in range(R):
                w.write_partition(r, payload(m, r))
            ex.commit_map(w)
        rounds = ex.store.num_rounds(sid)
        puts = ex.store.write_stats()["early_round_puts"] - early["early_round_puts"]
        ex.run_exchange(sid)

        checked = 0
        for r in range(R):
            if ex.owner_of_reduce(sid, r) != ex.executor_id:
                continue
            for m in range(M):
                got = ex.read_received_block(sid, m, r)
                assert got == payload(m, r), f"mismatch at map={{m}} reduce={{r}}"
                checked += 1
        assert checked > 0
        if jobs > 1:
            # the completed rounds of a job after the first are on the device
            # before the seal and the exchange takes every one of them
            assert rounds > 2 and puts == (rounds - 1 if sid else 0), (sid, rounds, puts)
            stats = ex.store.write_stats()
            assert stats["early_rounds_dropped"] == 0 and ex.store.take_early_round(sid, 0) is None
    if conf.host_recv_mode == "memmap":
        # the received rounds live on disk, not RAM, and are reclaimed
        shards, _ = ex._recv[sid]
        assert shards and all(isinstance(s, np.memmap) for s in shards)
        spilled = list(ex._recv_spill.get(sid, []))
        assert spilled and all(os.path.exists(p) for p, _ in spilled)
        # the refund is the charged nbytes, not getsize: budget returns to 0
        assert ex._recv_spill_bytes == sum(nb for _, nb in spilled)
        ex.remove_shuffle(sid)
        assert not any(os.path.exists(p) for p, _ in spilled), "spmd spill leaked"
        assert ex._recv_spill_bytes == 0, "spill budget not fully refunded"
    print(f"CHILD_PASS pid={{pid}} checked={{checked}}", flush=True)
    ex.close(); ep.close()
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_spmd_exchange():
    from sparkucx_tpu.parallel.bootstrap import DriverEndpoint

    driver = DriverEndpoint()
    coord = f"127.0.0.1:{_free_port()}"
    driver_addr = f"{driver.address[0]}:{driver.address[1]}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord, driver_addr],
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
        driver.close()


def test_two_process_spmd_exchange_two_slices():
    """Multi-host AND multi-slice: each process is one slice of one chip; the
    superstep routes through the two-phase hierarchy over jax.distributed."""
    from sparkucx_tpu.parallel.bootstrap import DriverEndpoint

    driver = DriverEndpoint()
    coord = f"127.0.0.1:{_free_port()}"
    driver_addr = f"{driver.address[0]}:{driver.address[1]}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["TEST_NUM_SLICES"] = "2"
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord, driver_addr],
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
        driver.close()


def test_two_process_spmd_exchange_quota():
    """Multi-controller + slotQuotaRows: both processes must all-gather the
    same sub-round plan (lockstep collectives) and splice chunked receive
    bytes back to the oracle.  Quota of 1 row with ≤1500-byte payloads (3
    rows at 512 alignment) forces 3 sub-rounds per staging round."""
    from sparkucx_tpu.parallel.bootstrap import DriverEndpoint

    driver = DriverEndpoint()
    coord = f"127.0.0.1:{_free_port()}"
    driver_addr = f"{driver.address[0]}:{driver.address[1]}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["TEST_SLOT_QUOTA_ROWS"] = "1"
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord, driver_addr],
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
        driver.close()


def test_two_process_spmd_exchange_memmap(tmp_path):
    """Multi-controller + host_recv_mode='memmap': each process spills its
    received rounds to read-only disk mappings (the per-host memory budget of
    transport/tpu.py's memmap mode) and reclaims them on remove_shuffle."""
    from sparkucx_tpu.parallel.bootstrap import DriverEndpoint

    driver = DriverEndpoint()
    coord = f"127.0.0.1:{_free_port()}"
    driver_addr = f"{driver.address[0]}:{driver.address[1]}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["TEST_HOST_RECV_MODE"] = "memmap"
    env["TEST_SPILL_DIR"] = str(tmp_path)
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord, driver_addr],
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
        driver.close()


def test_two_process_spmd_later_jobs_take_their_rounds_from_the_device():
    """Multi-controller + a process that runs job after job: from its second
    job on, the store's completed rounds are on the device before the seal
    (``HbmBlockStore.take_early_round``) and the SPMD submit donates them as
    it donates a device-sealed round — same bytes, nothing dropped."""
    from sparkucx_tpu.parallel.bootstrap import DriverEndpoint

    driver = DriverEndpoint()
    coord = f"127.0.0.1:{_free_port()}"
    driver_addr = f"{driver.address[0]}:{driver.address[1]}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["TEST_JOBS"] = "3"
    env["TEST_STAGING"] = "4096"  # two regions of four rows: a job rolls several rounds
    script = CHILD.format(root=ROOT)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid), coord, driver_addr],
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
        driver.close()

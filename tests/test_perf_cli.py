"""The perf CLI (UcxPerfBenchmark analogue: server and client) and the
hardware acceptance smoke on the CI mesh."""

import re
import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.core.block import MemoryBlock, ShuffleBlockId
from sparkucx_tpu.core.operation import OperationStatus
from sparkucx_tpu.perf import benchmark
from sparkucx_tpu.transport.peer import PeerTransport

BLOCKS, SIZE = 4, 64 << 10


def _serve(capsys, argv, entry):
    """Start the server (it loops forever; a daemon thread is enough) on an
    ephemeral port and return the address from the banner it prints once
    every block is registered."""
    threading.Thread(target=entry, args=(argv,), daemon=True).start()
    # generous: on a loaded single-core CI box the server thread can starve
    # behind the suite's subprocesses for several seconds
    deadline = time.monotonic() + 30
    banner = ""
    while time.monotonic() < deadline and "\n" not in banner:
        banner += capsys.readouterr().out
        time.sleep(0.05)
    m = re.search(r"blocks on (\S+:\d+)", banner)
    assert m, f"server did not come up: {banner!r}"
    return m.group(1)


def _read_back(address):
    """Every served block's bytes, fetched by a transport of the test's own."""
    client = PeerTransport(TpuShuffleConf(), executor_id=7)
    client.add_executor(0, address.encode())
    try:
        bufs = [MemoryBlock(np.zeros(SIZE, dtype=np.uint8), size=SIZE) for _ in range(BLOCKS)]
        bids = [ShuffleBlockId(0, 0, i) for i in range(BLOCKS)]
        reqs = client.fetch_blocks_by_block_ids(0, bids, bufs, [None] * BLOCKS)
        while not all(r.completed() for r in reqs):
            client.progress()
            client.wait_for_activity(0.002)
        assert all(r.wait(1).status == OperationStatus.SUCCESS for r in reqs)
        return [b.host_view()[:SIZE].tobytes() for b in bufs]
    finally:
        client.close()


@pytest.mark.parametrize(
    "case", ["synthetic", "file_backed", "two_threads", "main_dispatch"]
)
def test_client_server_roundtrip(case, capsys, tmp_path):
    """The reference's two modes as its README runs them: a server of -n
    blocks of -s bytes (synthetic, or -f file-backed), a client of -t threads
    fetching the whole set -i times, -o in flight."""
    via_main = case == "main_dispatch"
    threads = 2 if case == "two_threads" else 1
    srv = ["server", "-a", "127.0.0.1:0", "-n", str(BLOCKS), "-s", "64k"]
    if case == "file_backed":
        content = np.random.default_rng(5).integers(0, 256, BLOCKS * SIZE, dtype=np.uint8)
        (tmp_path / "blocks.bin").write_bytes(content.tobytes())
        srv += ["-f", str(tmp_path / "blocks.bin")]
    run = lambda argv: benchmark.run_server(benchmark._parse_args(argv))
    address = _serve(capsys, srv, benchmark.main if via_main else run)
    cli = ["client", "-a", address, "-n", str(BLOCKS), "-s", "64k", "-i", "2", "-o", "2",
           "-t", str(threads)]
    if via_main:
        benchmark.main(cli)
    else:
        benchmark.run_client(benchmark._parse_args(cli))
    out = capsys.readouterr().out
    # every thread fetched the whole set in every iteration
    for tid in range(threads):
        got = re.findall(rf"\[thread {tid}\] iter (\d): (\d+) bytes", out)
        assert got == [("0", str(BLOCKS * SIZE)), ("1", str(BLOCKS * SIZE))], out
    blocks = _read_back(address)
    if case == "file_backed":
        # block i is the file's i-th -s-sized segment, byte for byte
        raw = content.tobytes()
        assert blocks == [raw[i * SIZE : (i + 1) * SIZE] for i in range(BLOCKS)]
    else:
        assert len(set(blocks)) == BLOCKS  # BLOCKS distinct synthetic blocks


def test_cli_flags_match_reference():
    # -a/-f/-n/-s/-i/-o/-r/-t (UcxPerfBenchmark.scala:41-59)
    args = benchmark._parse_args(
        ["client", "-a", "h:1", "-f", "f", "-n", "2", "-s", "1k", "-i", "3", "-o", "4", "-r", "5", "-t", "6"]
    )
    assert (args.address, args.file, args.num_blocks) == ("h:1", "f", 2)
    assert (args.iterations, args.outstanding, args.reports, args.threads) == (3, 4, 5, 6)


def test_unknown_mode_refused(capsys):
    """Only the reference's two modes exist; the parser refuses another."""
    with pytest.raises(SystemExit) as e:
        benchmark.main(["superstep", "-s", "64k"])
    assert e.value.code == 2
    assert "invalid choice: 'superstep'" in capsys.readouterr().err


def test_tpu_smoke_script():
    """The scheduled-ring plane's chip check on the CI mesh: its two kernels
    are TPU-only, so both drives must skip by name here, not vanish, and the
    script must exit 0 (as it does on the chip, where they run)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "tpu_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all 2 drives passed" in r.stdout
    drives = [ln for ln in r.stdout.splitlines() if ln.startswith("ok: ")]
    assert len(drives) == 2, r.stdout
    for kernel, line in zip(("ring_exchange_grid", "fused_scatter_ring_grid"), drives):
        assert kernel in line and "[impl=skipped (TPU-only" in line, line

"""Tests for the native arena (C++ via ctypes): build, pinned buffers,
shared-memory cross-process visibility, batch copy."""

import os
import subprocess
import sys

import numpy as np
import pytest

from sparkucx_tpu import native


pytestmark = pytest.mark.skipif(
    not native.native_available(), reason=f"native build unavailable: {native.build_error()}"
)


class TestPinnedBuffer:
    def test_alloc_and_alignment(self):
        with native.PinnedBuffer(1 << 20, alignment=4096) as buf:
            assert buf.array.size == 1 << 20
            assert buf.array.ctypes.data % 4096 == 0
            buf.array[:100] = 7
            assert (buf.array[:100] == 7).all()

    def test_close_idempotent(self):
        buf = native.PinnedBuffer(4096)
        buf.close()
        buf.close()


class TestSharedArena:
    def test_create_write_attach_read(self):
        name = f"/ts_test_{os.getpid()}"
        with native.SharedArena(name, 1 << 16, create=True) as arena:
            arena.array[:256] = np.arange(256, dtype=np.uint8)
            with native.SharedArena(name, 1 << 16, create=False) as attached:
                assert (attached.array[:256] == np.arange(256, dtype=np.uint8)).all()
                attached.array[0] = 99
                assert arena.array[0] == 99

    def test_cross_process_visibility(self):
        name = f"/ts_xproc_{os.getpid()}"
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with native.SharedArena(name, 4096, create=True) as arena:
            arena.array[:5] = [1, 2, 3, 4, 5]
            script = (
                f"import sys; sys.path.insert(0, {root!r});\n"
                "from sparkucx_tpu import native\n"
                f"a = native.SharedArena({name!r}, 4096, create=False)\n"
                "print([int(x) for x in a.array[:5]])\n"
                "a.array[5] = 42\n"
                "a.close()\n"
            )
            out = subprocess.run(
                [sys.executable, "-c", script], capture_output=True, text=True, timeout=60
            )
            assert out.returncode == 0, out.stderr
            assert "[1, 2, 3, 4, 5]" in out.stdout
            assert arena.array[5] == 42

    def test_attach_missing_fails(self):
        with pytest.raises(OSError):
            native.SharedArena("/ts_does_not_exist_xyz", 4096, create=False)

    def test_double_create_fails(self):
        name = f"/ts_dup_{os.getpid()}"
        with native.SharedArena(name, 4096, create=True):
            with pytest.raises(OSError):
                native.SharedArena(name, 4096, create=True)


class TestBatchCopy:
    def test_scattered_segments(self, rng):
        src = rng.integers(0, 256, size=1 << 16, dtype=np.uint8)
        dst = np.zeros(1 << 16, dtype=np.uint8)
        segs = [(0, 1000, 500), (600, 5000, 256), (900, 0, 128)]
        native.batch_copy(dst, src, segs)
        for d, s, l in segs:
            assert (dst[d : d + l] == src[s : s + l]).all()

    def test_large_threaded_copy(self, rng):
        # > 4 MiB total triggers the thread team
        src = rng.integers(0, 256, size=16 << 20, dtype=np.uint8)
        dst = np.zeros(16 << 20, dtype=np.uint8)
        seg_len = 1 << 20
        segs = [(i * seg_len, (15 - i) * seg_len, seg_len) for i in range(16)]
        native.batch_copy(dst, src, segs, max_threads=4)
        for d, s, l in segs:
            assert (dst[d : d + l] == src[s : s + l]).all()

    def test_python_fallback_matches(self, rng, monkeypatch):
        src = rng.integers(0, 256, size=4096, dtype=np.uint8)
        dst_native = np.zeros(4096, dtype=np.uint8)
        dst_py = np.zeros(4096, dtype=np.uint8)
        segs = [(0, 2048, 1024), (2048, 0, 512)]
        native.batch_copy(dst_native, src, segs)
        monkeypatch.setattr(native, "_load", lambda: None)
        native.batch_copy(dst_py, src, segs)
        assert (dst_native == dst_py).all()


def test_version():
    assert native._load().ts_version() == 1


def test_source_change_forces_rebuild(tmp_path, monkeypatch):
    """The library is keyed on arena.cpp's contents, not its mtime: a stale
    .so copied along with the checkout is never loaded for different source."""
    import shutil

    src = tmp_path / "arena.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_DIR", str(tmp_path))

    def fresh_load():
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_built_here", False)
        assert native._load() is not None, native.build_error()
        return native._so_path(), native.built_here()

    first, built = fresh_load()
    assert built and os.path.exists(first)
    # same source again (even with a newer mtime): loaded as is
    os.utime(src)
    assert fresh_load() == (first, False)
    # one changed byte: a different library is built, the stale one removed
    src.write_text(src.read_text() + "\n// changed\n")
    second, built = fresh_load()
    assert built and second != first
    assert os.path.exists(second) and not os.path.exists(first)


class TestShmStore:
    def test_store_with_shm_staging(self):
        from sparkucx_tpu.config import TpuShuffleConf
        from sparkucx_tpu.store.hbm_store import HbmBlockStore

        conf = TpuShuffleConf(
            staging_capacity_per_executor=1 << 18,
            use_shm_staging=True,
            shm_namespace=f"ts_store_{os.getpid()}",
        )
        store = HbmBlockStore(conf, executor_id=3)
        try:
            store.create_shuffle(0, 1, 2)
            w = store.map_writer(0, 0)
            w.write_partition(0, b"shm-staged")
            w.commit()
            assert store.read_block(0, 0, 0) == b"shm-staged"
            # another process attaches the same named arena and sees the bytes
            name = f"/{conf.shm_namespace}_e3_s0"
            with native.SharedArena(name, 4096, create=False) as peer:
                assert bytes(peer.array[:10]) == b"shm-staged"
        finally:
            store.close()
        # unlinked at close: attach must now fail
        with pytest.raises(OSError):
            native.SharedArena(name, 4096, create=False)

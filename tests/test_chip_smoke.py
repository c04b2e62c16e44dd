"""The chip-side entry points, as far as a CPU can show them: chip_smoke.py's
explicit tiny form end to end, its (and bench.py's) refusal to run without a
chip, a failed phase being named, and the compile-cache placement helper.

Every subprocess gets ``JAX_COMPILATION_CACHE_DIR`` under ``tmp_path`` so
tier-1 leaves no cache in the checkout for the chip tool to copy."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, tmp_path, devices=2, timeout=120):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
    }
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True, timeout=timeout,
        cwd=ROOT, env=env,
    )


def test_chip_smoke_tiny_cpu_form(tmp_path):
    """All four phases at GroupByTest width (200 reducers, 25,000-byte
    values) on a two-device CPU mesh, oracle checked in each."""
    r = _run(["chip_smoke.py", "--cpu-tiny"], tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the driver's contract: the last line holds exactly these keys
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    phases = {
        ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1]) for ln in lines[:-1]
    }
    summary = phases["summary"]
    assert summary["ok"] is True and summary["claim"] is None
    assert summary["phases"] == ["main", "reuse", "device", "daemon"]
    assert [m["executor"] for m in summary["mesh"]] == [0, 1]
    records = phases["records"]
    assert records["reducers"] == 200 and records["bytes"] > 200 * 25000
    for name in ("main", "device", "daemon"):
        assert phases[name]["oracle"] == {
            "groups": records["groups"], "records": 4 * 400,
        }, name
        assert phases[name]["lowering"]["exchange"] == ["dense"], name
    assert phases["main"]["rounds"] > 1  # the multi-round engine ran
    assert phases["device"]["rounds"] == 1
    assert phases["device"]["lowering"]["gather"] == ["xla"]
    assert phases["device"]["gathered_blocks"] == records["blocks"]
    # the second shuffle on the warm manager built no executable
    assert phases["reuse"]["compile"]["compiles"] == 0
    assert all(
        phases["main"][k] == 0 for k in ("blocks_retried", "failovers", "fetch_timeouts")
    )
    assert phases["chip_smoke"]["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
    assert os.listdir(tmp_path / "jax_cache")


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_default_form_refuses_without_a_chip(script, tmp_path):
    r = _run([script], tmp_path, devices=1)
    assert r.returncode not in (0, 1), r.stdout + r.stderr[-2000:]
    assert "no chip found" in r.stderr
    assert r.stdout.strip() == ""  # no result line, not even a null one


def test_failed_phase_is_named(tmp_path):
    """A phase made to raise (the repo's own fault point, armed before the
    run) exits non-zero, names the phase, and runs no later phase."""
    code = (
        "import sys, chip_smoke\n"
        "from sparkucx_tpu.testing import faults\n"
        "faults.arm('exchange.submit', faults.fail(RuntimeError('injected')))\n"
        "sys.exit(chip_smoke.main(['--cpu-tiny']))\n"
    )
    r = _run(["-c", code], tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr[-2000:]
    assert "FAIL main: RuntimeError: injected" in r.stdout
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and set(last) == {"ok", "device"}
    summary = json.loads(lines[-2].split(": ", 1)[1])
    assert summary["ok"] is False and summary["failed_phase"] == "main"
    assert summary["phases"] == []


def test_bench_phase_runner_records_and_names_a_failure(monkeypatch):
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)
    monkeypatch.setattr(bench, "FAILED", [])
    monkeypatch.setattr(bench, "RESULT", {})

    def boom():
        raise ValueError("nope")

    bench.phase("gather", boom)
    bench.phase("sort", lambda: bench.RESULT.update(sort_mrows_s=1.0))
    assert bench.FAILED == ["gather"]
    assert bench.RESULT == {"gather_error": "ValueError: nope", "sort_mrows_s": 1.0}


class TestCompileCacheHelper:
    """env var set -> that directory, and nothing else is set; unset -> the
    fixed <checkout>/.jax_cache.  jax.config is stubbed: the helper must not
    re-point the cache of the pytest process itself."""

    @pytest.fixture
    def updates(self, monkeypatch):
        import jax

        seen = {}
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_var_wins_and_directory_is_left_alone(self, updates, monkeypatch, tmp_path):
        from sparkucx_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
        assert enable_compile_cache() == str(tmp_path / "given")
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / "given").exists()  # nothing done to it
        # JAX's 1.0 s default would store none of this program's executables
        assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0}

    def test_unset_uses_the_fixed_checkout_path(self, updates, monkeypatch):
        from sparkucx_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path

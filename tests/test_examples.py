"""Every example in examples/ must run green, as a real subprocess.

The examples are the user-facing walkthroughs (examples/README.md); running
them end-to-end keeps the documented surface honest the same way the
integration gate keeps the daemon protocol honest."""

import importlib.util
import os
import pathlib
import re
import shlex
import subprocess
import sys

import pytest

EXAMPLES_DIR = pathlib.Path(__file__).resolve().parent.parent / "examples"
SCRIPTS = sorted(p.name for p in EXAMPLES_DIR.glob("*.py"))


def test_examples_dir_has_scripts():
    assert len(SCRIPTS) >= 4


def test_readme_lists_every_script():
    readme = (EXAMPLES_DIR / "README.md").read_text()
    for script in SCRIPTS:
        assert script in readme, f"examples/README.md does not mention {script}"


def test_every_command_of_the_readme_names_something_that_exists():
    """README's ``bash`` block is what a new owner runs first: each ``python``
    line's script or ``-m`` module, and each ``bash`` line's script, is there."""
    root = EXAMPLES_DIR.parent
    block = "\n".join(re.findall(r"```bash\n(.*?)```", (root / "README.md").read_text(), re.S))
    commands = [shlex.split(line, comments=True) for line in block.splitlines()]
    commands = [c for c in commands if c and c[0] in ("python", "python3", "bash")]
    assert len(commands) >= 8
    for argv in commands:
        if argv[1] == "-m":
            assert importlib.util.find_spec(argv[2]) is not None, argv
        else:
            assert (root / argv[1]).is_file(), argv


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(EXAMPLES_DIR.parent)] + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    r = subprocess.run(
        [sys.executable, str(EXAMPLES_DIR / script)],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
    )
    assert r.returncode == 0, f"{script} failed:\n{r.stdout}\n{r.stderr}"
    assert "OK" in r.stdout, f"{script} printed no OK checkpoint:\n{r.stdout}"

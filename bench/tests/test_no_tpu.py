"""Without a TPU a run fails and prints no result."""

import os
import subprocess
import sys

import pytest

from bench import harness


def test_harness_refuses_the_cpu(tmp_path):
    from bench.tests import tiny

    root = tiny.make_root(tmp_path)
    with pytest.raises(harness.NoChip):
        harness.run("gpt2.offload", 1, 1.0, False, t_start=0.0, root=root)


def test_command_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "gpt2-1b.offload", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_command_fails_outside_a_checkout(tmp_path):
    """A directory with only BENCHMARK.json and bench/ gives no result."""
    import shutil

    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-1b.offload",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

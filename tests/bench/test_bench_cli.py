"""The command refuses to run where it cannot measure."""

import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "offline.day2000",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    r = _run(harness.ROOT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_exits_nonzero_beside_no_program(tmp_path):
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "nope",
         "--seed", "1", "--seconds", "1"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0 and r.stdout == ""

"""The command fails, printing no result, where it must."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "clf-pnode",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

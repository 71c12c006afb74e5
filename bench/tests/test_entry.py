"""``bench/run.py`` refuses to measure without a TPU or without the
program, and prints no result then."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "p5.fdk", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    r = run(ROOT)
    assert r.returncode != 0 and r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run(tmp_path)
    assert r.returncode != 0 and r.stdout == ""
    assert "no repro package" in r.stderr

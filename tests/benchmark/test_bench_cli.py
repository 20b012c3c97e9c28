"""The command refuses to measure on anything but a TPU, and to run where
the program is not."""

import os
import shutil
import subprocess
import sys

from benchmarks.lib import manifest as mf

CELL = mf.load()["workloads"][0]["name"]


def _run(root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _bare_copy(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_no_tpu_is_a_nonzero_exit_and_no_result(tmp_path):
    """From a copy of the checkout, so that nothing is written into this
    one: JAX finds only the CPU, so the kernel sweep's child refuses and
    the parent returns its code before it has touched JAX."""
    root = _bare_copy(tmp_path)
    shutil.copytree(os.path.join(mf.ROOT, "horovod_tpu"),
                    os.path.join(root, "horovod_tpu"),
                    ignore=shutil.ignore_patterns("__pycache__", "build"))
    done = _run(root)
    assert done.returncode == 2, done.stderr[-2000:]
    assert "needs 1 TPU chip" in done.stderr
    assert '"correct"' not in done.stdout
    assert not os.path.exists(os.path.join(root, ".compile_cache",
                                           f"presweep.{CELL}.done"))


def test_only_the_benchmark_files_is_a_nonzero_exit_and_no_result(tmp_path):
    done = _run(_bare_copy(tmp_path))
    assert done.returncode != 0
    assert '"correct"' not in done.stdout

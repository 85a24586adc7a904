"""``run.py`` where it must not produce a result: no TPU, and a directory
that holds the benchmark and nothing of the program."""

import os
import shutil
import subprocess
import sys

from benchmark.harness import manifest as mf


def run_cell(cwd, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    for cell in mf.load_manifest()["workloads"]:
        done = run_cell(mf.ROOT, cell["name"])
        assert done.returncode != 0
        assert done.stdout == ""
        assert "needs" in done.stderr and "TPU" in done.stderr


def test_unknown_cell_no_result():
    done = run_cell(mf.ROOT, "no-such.cell")
    assert done.returncode != 0 and done.stdout == ""


def test_benchmark_alone_no_result(tmp_path):
    """Only ``BENCHMARK.json`` and the files under ``paths``: the program is
    not there, so there is nothing to measure."""
    man = mf.load_manifest()
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(mf.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = run_cell(tmp_path, man["workloads"][0]["name"])
    assert done.returncode != 0 and done.stdout == ""
    assert "ModuleNotFoundError" in done.stderr

"""The command finds no chip here and fails; it prints no result line."""

import os
import shutil
import subprocess
import sys

import pytest

from chipbench_testlib import BENCH

ROOT = os.path.dirname(BENCH)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_chip_no_result():
    proc = _run(ROOT, "--workload", "internvl2-2b.conv", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_unknown_workload_no_result():
    proc = _run(ROOT, "--workload", "nothing.here", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    _no_result(proc)
    assert proc.returncode == 2


@pytest.mark.parametrize("trace", ["0", "1"])
def test_benchmark_alone_without_the_program_fails(tmp_path, trace):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--workload", "internvl2-2b.conv", "--seed", "1",
                "--seconds", "1", "--trace", trace)
    _no_result(proc)
    assert "no program under test" in proc.stderr

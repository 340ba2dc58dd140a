"""Peaks are looked up by the device kind JAX reports, and the command
refuses to measure anything but an accelerator: on a CPU, and in a
directory that holds only the benchmark, it exits non-zero and prints
no result."""

import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny
from harness import device as D


def test_known_kind_has_published_peaks():
    p = D.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError):
        D.peaks("TPU v9 imaginary")


def test_cpu_is_not_an_accelerator():
    with pytest.raises(D.NoAccelerator):
        D.accelerators(1)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.chat",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_a_cpu():
    r = _run(bench_tiny.ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

"""The benchmark refuses to measure without a TPU: no result, non-zero exit,
from the repository and from a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm3-4b-l4.stl2x2048",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _assert_refused(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_cpu_is_refused():
    p = _run(ROOT)
    _assert_refused(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _assert_refused(_run(tmp_path))


def test_peaks_are_keyed_by_device_kind():
    from bench.harness import device

    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(device.NoChip):
        device.peaks_for("cpu")

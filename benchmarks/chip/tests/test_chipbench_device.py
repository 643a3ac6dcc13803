"""The measurement path fails, with no result, off a TPU and outside a
full checkout."""
import os
import shutil
import subprocess
import sys
import types

import pytest

import chipbench_tiny
from benchmarks.chip import harness


def _dev(platform):
    return types.SimpleNamespace(platform=platform, device_kind=platform)


def test_check_devices_refuses_a_cpu_first_device():
    with pytest.raises(harness.DeviceError, match="cpu"):
        harness.check_devices([_dev("cpu"), _dev("tpu")], 1)


def test_check_devices_refuses_too_few_chips():
    with pytest.raises(harness.DeviceError, match="asks for 4"):
        harness.check_devices([_dev("tpu")] * 2, 4)
    assert len(harness.check_devices([_dev("tpu")] * 4, 1)) == 1


def test_a_device_kind_without_peaks_is_an_error():
    with pytest.raises(harness.DeviceError, match="peaks"):
        harness.load_peaks("TPU v99")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "olmo-1b.cure", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_on_the_cpu_with_no_result():
    p = _run(chipbench_tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cpu" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(chipbench_tiny.ROOT, "BENCHMARK.json"),
                tmp_path)
    shutil.copytree(chipbench_tiny.CHIP,
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

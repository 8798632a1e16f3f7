"""JAX collective correctness on 8 forced host devices.

Runs in a subprocess because --xla_force_host_platform_device_count must be
set before jax initializes, and the rest of the suite must see 1 device.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_collectives_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev_driver.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-OK" in proc.stdout


def test_optcc_allreduce_is_bit_exact_on_8_devices():
    """OptCC's subring adds in the ring's order, bit for bit, at every
    straggler position of p = 8 and of p = 4 (tests/multidev_driver.py's
    `bitexact` mode)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev_driver.py"),
         "bitexact"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "BITEXACT-OK" in proc.stdout


@pytest.mark.slow
def test_elastic_node_loss_rescale():
    """Train on 8 virtual devices, lose half at step 4, continue on 4."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "qwen3-1.7b", "--smoke", "--steps", "8", "--lose-node-at", "4",
         "--seq-len", "32", "--log-every", "2"],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "NODE LOSS - resumed on 4 devices" in proc.stdout
    assert "done" in proc.stdout


@pytest.mark.slow
def test_failure_injection_path():
    """The driver detects the injected NIC loss, re-plans with OptCC,
    and recovers to psum on repair - full paper loop in one run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "qwen3-1.7b", "--smoke", "--steps", "9", "--fail-at", "3",
         "--repair-at", "6", "--seq-len", "32", "--log-every", "3"],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DEGRADED" in proc.stdout and "optcc-single" in proc.stdout
    assert "REPAIRED; back to native psum" in proc.stdout
    # each switch reports its new step's trace / lower / compile split
    assert proc.stdout.count("new step built in") == 2

"""Distributed-runtime integration tests (8 host devices via subprocess)."""
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).parent / "host_mesh_checks.py"

CHECKS = [
    "sharded_train_step_matches_single_device",
    "sharded_init_matches_device_put",
    "checkpoint_roundtrip",
    "crash_resume_bitwise",
    "elastic_reshard",
    "reshard_roundtrip",
    "grad_compression_convergence",
    "straggler_watchdog",
    "runahead_loader",
]


@pytest.mark.parametrize("check", CHECKS)
def test_host_mesh(check):
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), check],
        capture_output=True, text=True, timeout=600,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(SCRIPT.parents[1] / "src"),
             "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert proc.returncode == 0, (
        f"{check} failed:\nSTDOUT:\n{proc.stdout[-3000:]}\n"
        f"STDERR:\n{proc.stderr[-3000:]}")

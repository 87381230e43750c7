"""Slow integration test: one production-mesh dry-run cell compiles.

The full 10x4x2 grid runs via ``python -m repro.launch.dryrun --all
--mesh both``; this test pins the machinery in CI.
Runs in a subprocess so the 512 placeholder devices never leak into the main
pytest process.
"""
import pathlib
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_one_dryrun_cell_compiles():
    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-1.5b", "--shape", "decode_32k", "--mesh", "pod"],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": str(root / "src"), "JAX_PLATFORMS": "cpu",
             "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd=str(root),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "OK" in proc.stdout

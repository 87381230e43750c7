"""chip_smoke.py off the chip: it refuses a CPU backend, and its serve phase
and input generators work at a reduced size (the chip run itself is made
with ``python3 chip_smoke.py`` on a TPU)."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    sys.path.remove(str(ROOT))


def test_refuses_a_cpu_backend_before_any_work():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=str(ROOT))
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout and "[serve]" not in proc.stdout


def test_serve_phase_at_smoke_size(smoke):
    from examples import serve_lm
    from repro.configs import registry
    cfg = registry.smoke("qwen2-1.5b")
    params = serve_lm.init_params(cfg, smoke.SEED)
    smoke.serve_phase(cfg, params, slots=3, max_len=128, page_size=16,
                      prefill_chunk=32, n_requests=6, new_tokens=4,
                      prompt_len=(8, 80))


def test_greedy_prompts_share_lengths(smoke):
    lens = smoke.prompt_lengths(12, 64, 1024, seed=0)
    assert len(lens) == 12 and all(64 <= n <= 1024 for n in lens)
    assert len(set(lens[0::2])) <= smoke.GREEDY_LENGTHS


def test_zipf_indices_are_skewed_rows(smoke):
    idx = smoke.zipf_indices(1000, (4096,), 1.2, seed=0)
    assert idx.dtype == np.int32 and idx.min() >= 0 and idx.max() < 1000
    counts = np.bincount(idx, minlength=1000)
    assert counts.max() > 20 * np.median(counts[counts > 0])

"""BENCHMARK.json against the contract's shape and the files under bench/."""
from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, loadgen

BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_every_name_resolves_to_its_files():
    for c in BENCH["configs"]:
        cfg = harness.load_config(harness.REPO / c["file"])
        assert cfg["name"] == c["name"] and c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(cfg)
        for key in harness.MODULE_KEYS:
            assert cfg[key].startswith("bench/")
        arch, ref = harness.arch(cfg), harness.reference(cfg)
        assert all(callable(getattr(arch, f)) for f in (
            "program_config", "shapes", "decode_flops", "decode_bytes"))
        assert ref.BLOCK > 0 and callable(ref.logit_stats)
    for w in BENCH["workloads"]:
        assert NAME.fullmatch(w["name"])
        assert (harness.BENCH / "configs" / f"{w['config']}.json").is_file()
        mix = json.loads((harness.BENCH / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert hasattr(importlib.import_module(
            f"bench.arrivals.{mix['arrivals']}"), "run")
        assert (harness.BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.fullmatch(m["name"])
        assert callable(harness.reader(m["name"]))


def test_metrics_and_cells_fit_together():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in cells:
        assert harness.cell_metrics(BENCH, w, trace=False)[1:]
        assert harness.cell_metrics(BENCH, w, trace=True)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_request_fits_its_cell():
    """No request of any cell's traffic needs more than the cell's
    ``max_len``: none would be refused for its length."""
    for w in BENCH["workloads"]:
        cell = harness.resolve(w["name"], BENCH)
        vocab, max_len = cell.cfg["vocab_size"], cell.cfg["serve"]["max_len"]
        if cell.mix["arrivals"] == "serve_open":
            specs = loadgen.open_loop(cell.mix, vocab, 3, 51.0)
        else:
            first, stream = loadgen.closed_loop(cell.mix, vocab, 3)
            specs = first + stream
        assert max(len(s.prompt) + s.max_new for s in specs) <= max_len


@pytest.mark.parametrize("tool,args", [
    ("run.py", ["--seed", "1", "--seconds", "1", "--trace", "0"]),
    ("calibrate.py", ["--seeds", "1", "--seconds", "1"]),
    ("sweep.py", ["--rates", "1", "--seconds", "1"])])
def test_run_refuses_a_cpu_backend(tool, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, f"bench/{tool}", "--workload", cell, *args],
        cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""

"""BENCHMARK.json against the contract's shape and the files under bench/."""
from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys

from bench import harness

BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_every_name_resolves_to_its_files():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(cfg)
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


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=harness.REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr and "TPU" in p.stderr
    assert p.stdout.strip() == ""

"""A whole run of the harness on the CPU at a small size, past the look for
a chip: sound, and with the timed path broken underneath."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.tests.common import run_tiny


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.open", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    ("tiny.closed", {"setup_s"})])
def test_sound_run_is_correct(workload, metrics):
    line = run_tiny(seed=2**31 + 11, workload=workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == metrics
    assert line["attempted"] > 0 and line["failed"] == 0


def _broken(kind):
    def hook(eng):
        s, vocab = eng.steps, eng.cfg.vocab_size

        def decode(*a):
            old = jax.tree.map(jnp.copy, a[-1])
            tok, lg, cache = s.decode(*a)
            if kind == "token":            # a token altered where produced
                return (tok + 1) % vocab, lg, cache
            return tok, lg, old            # the step returns its state unchanged

        def prefill(*a):
            tok, lg, cache = s.prefill(*a)
            return (tok + 1) % vocab, lg, cache

        eng.steps = dataclasses.replace(
            s, decode=decode if kind in ("token", "state") else s.decode,
            prefill=prefill if kind == "first_token" else s.prefill)
    return hook


@pytest.mark.parametrize("kind", ["token", "state", "first_token"])
def test_broken_timed_path_is_not_correct(kind):
    line = run_tiny(seed=5, hook=_broken(kind))
    assert not line["correct"], line["checks"]
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["max"]


def test_traced_run_records_host_spans(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path))
    line = run_tiny(seed=7, trace=True)
    assert line["correct"]
    # no TPU planes in a CPU trace: device readers find nothing to read
    assert set(line["metrics"]) <= {"queue_wait_ms_p95"}
    assert line["device"]["window_s"] > 0
    from jax.profiler import ProfileData
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for ln in p.lines
             for ev in ln.events}
    assert {"bench.window", "bench.engine_step", "bench.call.decode",
            "bench.call.prefill"} <= names

"""A whole run of the harness on the CPU at a small size, past the look for
a chip: sound, and with the timed path broken underneath."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, serving
from bench.tests.common import CPU, DATA, run_tiny, tiny_benchmark


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.open", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    ("tiny.closed", {"total_tok_s", "setup_s"})])
def test_sound_run_is_correct(workload, metrics):
    line = run_tiny(seed=2**31 + 11, workload=workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == metrics
    assert line["attempted"] > 0 and line["failed"] == 0


def test_closed_cell_reports_the_tokens_sampled_in_its_window(monkeypatch):
    """``total_tok_s`` against the engine's own acceptances and prefill
    calls: every token it accepted inside the window, and every prompt
    token of the prefill chunks it ran between the window's open and its
    close, over the window's seconds."""
    stamps, dues, events = [], [], []

    def hook(eng):
        accept, submit = eng._accept_token, eng.submit
        prefill = eng.steps.prefill

        def accepted(req, token, logits, now):
            stamps.append(now)
            return accept(req, token, logits, now)

        def submitted(prompt, **kw):
            if kw.get("arrival") is not None:
                dues.append(kw["arrival"])
            return submit(prompt, **kw)

        def prefilled(*a):
            events.append(int(a[2]))        # the chunk's valid tokens
            return prefill(*a)

        eng._accept_token, eng.submit = accepted, submitted
        eng.steps = dataclasses.replace(eng.steps, prefill=prefilled)

    count = serving.Loop._prompt_tokens

    def marked(loop):                   # the window's open, then its close
        events.append(None)
        return count(loop)

    monkeypatch.setattr(serving.Loop, "_prompt_tokens", marked)
    seconds = 3.0
    line = run_tiny(seed=2**31 + 29, seconds=seconds, hook=hook,
                    workload="tiny.closed")
    # the clients' first requests are due at the warm-up's start
    w0 = dues[0] + json.loads((DATA / "traffic" / "tiny_closed.json")
                              .read_text())["warmup_s"]
    n = sum(1 for t in stamps if w0 <= t < w0 + seconds)
    opened, closed = [i for i, e in enumerate(events) if e is None]
    prompt = sum(events[opened + 1:closed])
    assert n > 0 and prompt > 0
    assert line["metrics"]["total_tok_s"]["value"] == pytest.approx(
        (n + prompt) / seconds, rel=1e-12)


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


def test_short_window_serves_on_past_its_close_for_the_check():
    """When the window's close leaves the check fewer greedy tokens than
    ``min_tokens``, the engine serves on past the close, submitting
    nothing, and the check compares what it served; the record, and so
    every metric, ends at the close."""
    seed, seconds = 2**31 + 31, 1.0
    cell = harness.resolve("tiny.closed", tiny_benchmark(), DATA)
    cell.limits = {**cell.limits, "min_tokens": 10**6}   # never reached
    out = harness.window(cell, seed, seconds, False, t_start=time.time())
    line = harness.result(cell, CPU, out, seed, False)
    at_close = sum(len(r["tokens"]) for r in out.record["requests"]
                   if r["temperature"] == 0.0)
    served = sum(len(s) for _, s in out.served)
    assert line["load"]["served_past_close_s"] > 0
    assert served > at_close
    assert line["checks"]["tokens_compared"]["value"] == served
    assert not line["correct"]          # 10**6 tokens were never served

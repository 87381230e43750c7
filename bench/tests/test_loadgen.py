"""The traffic generator: every seed sends the same set of sizes."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, loadgen
from bench.tests.common import DATA


def mix(name):
    return json.loads((harness.BENCH / "traffic" / f"{name}.json").read_text())


def sizes(specs):
    return sorted((len(s.prompt), s.max_new, s.temperature) for s in specs)


def test_open_loop_same_work_every_seed():
    m = mix("chat")
    a = loadgen.open_loop(m, 1000, 1, 30.0)
    b = loadgen.open_loop(m, 1000, 2**31 + 5, 30.0)
    assert sizes(a) == sizes(b)
    assert [s.due for s in a] != [s.due for s in b]
    win = [s for s in a if s.due >= 0]
    assert len(win) == round(m["rate_rps"] * 30)
    assert sum(s.temperature == 0 for s in win) == round(len(win) * m["greedy_share"])
    assert all(-m["warmup_s"] <= s.due < 30.0 for s in a)
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    p, o = m["prompt"], m["output"]
    arrived = a[len(loadgen.under_way(m, 1000, np.random.default_rng(1))):]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in arrived)
    assert all(o["min"] <= s.max_new <= o["max"] for s in arrived)


def test_warm_up_starts_with_little_law_requests_under_way():
    m = mix("chat")
    a = loadgen.under_way(m, 1000, np.random.default_rng(3))
    b = loadgen.under_way(m, 1000, np.random.default_rng(2**31 + 9))
    assert len(a) == round(m["rate_rps"] * m["life_s"]) > 0
    assert sizes(a) == sizes(b)
    assert all(s.due == -m["warmup_s"] for s in a)
    p, o = m["prompt"], m["output"]
    # each holds its prompt and the tokens served so far, and has a budget
    # left: together no more than a request of the mix can have
    assert all(1 <= s.max_new <= o["max"] for s in a)
    assert all(len(s.prompt) + s.max_new <= p["max"] + o["max"] for s in a)


@pytest.mark.parametrize("path", [DATA / "traffic" / "tiny_closed.json",
                                  harness.BENCH / "traffic" / "docs.json"],
                         ids=lambda p: p.stem)
def test_closed_loop_same_work_every_seed(path):
    m = json.loads(path.read_text())
    fa, sa = loadgen.closed_loop(m, 1000, 4)
    fb, sb = loadgen.closed_loop(m, 1000, 2**31 + 7)
    assert len(fa) == m["clients"] and len(sa) == m["stream"]
    assert sizes(fa) == sizes(fb) and sizes(sa) == sizes(sb)
    assert [len(s.prompt) for s in sa] != [len(s.prompt) for s in sb]
    assert all(s.due == -m["warmup_s"] for s in fa)
    p = m["prompt"]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in sa)

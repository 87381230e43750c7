"""The control: the reference in fp8 put in the program's place must come
out as not correct, through the same comparison and limits, where the
program, as served in bfloat16, comes out correct.  A small size on the
CPU, with a fixed sample of greedy requests run to completion (no timed
window, so the sample does not depend on the machine's speed)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import check, serving, weights
from bench.tests.common import DATA

CFG = json.loads((DATA / "configs" / "tiny.json").read_text())
LIMITS = json.loads((DATA / "limits" / "tiny.open.json").read_text())


def served(seed: int) -> list:
    from repro.serve import ServeEngine
    geo = CFG["serve"]
    eng = ServeEngine(serving.model_config(CFG), weights.make(CFG, seed),
                      slots=geo["slots"], max_len=geo["max_len"],
                      page_size=geo["page_size"],
                      prefill_chunk=geo["prefill_chunk"])
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, CFG["vocab_size"], n), max_new_tokens=48)
            for n in (20, 45, 70, 100, 33, 150, 64, 90)]
    eng.run()
    return [(np.asarray(r.prompt), np.asarray(r.out_tokens)) for r in reqs]


@pytest.mark.parametrize("seed", [2, 3, 4])
def test_control_fails_where_program_passes(seed):
    reqs = served(seed)
    program = check.run(CFG, seed, reqs, 0, LIMITS)
    control = check.control(CFG, seed, reqs, LIMITS)
    assert check.passed(program), program
    assert not check.passed(control), control

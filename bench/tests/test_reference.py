"""The plain reference against the program's paged serve steps, on the CPU.

The program is run in float32 here, so prefill in chunks and then decode
through the paged cache must give the reference's logits to rounding; the
same program in bfloat16, as served, must not, so the tolerance would catch
a lower-precision path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serving, weights
from bench.reference import dense_decoder
from bench.tests.common import INTERNLM_LIKE, QWEN_LIKE

TOL = 2e-4          # float32 paths differ in summation order only
PROMPT, DECODE, CHUNK, PAGE = 70, 6, 32, 16


def program_logits(cfg: dict, seq: np.ndarray, dtype: str) -> np.ndarray:
    """Logits the serve steps give at positions ends of chunks and decode
    steps: prefill ``seq[:PROMPT]`` in chunks, then feed the rest."""
    from repro.launch.steps import build_serve_engine_steps
    mcfg = dataclasses.replace(serving.model_config(cfg), dtype=dtype)
    params = weights.make(cfg, 3)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if dtype == "float32" else a, params)
    steps = build_serve_engine_steps(mcfg, slots=2, max_len=512,
                                     page_size=PAGE, sampling=False,
                                     return_logits=True)
    cache = steps.init_cache()
    table = np.zeros((2, 512 // PAGE), np.int32)
    table[1] = np.arange(1, 512 // PAGE + 1)       # the request sits in slot 1
    cache["page_table"] = jnp.asarray(table)
    out, key = [], np.zeros(2, np.uint32)
    for s in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - s)
        chunk = np.zeros(CHUNK, np.int32)
        chunk[:n] = seq[s:s + n]
        _, lg, cache = steps.prefill(params, chunk, np.int32(n), np.int32(1),
                                     np.float32(0), key, cache)
        out.append(np.asarray(lg))
    for i in range(DECODE):
        tok = np.array([0, seq[PROMPT + i]], np.int32)
        _, lg, cache = steps.decode(params, tok, np.array([False, True]),
                                    np.zeros(2, np.float32),
                                    np.zeros((2, 2), np.uint32), cache)
        out.append(np.asarray(lg)[1])
    return np.stack(out)


def positions() -> list:
    ends = [min(s + CHUNK, PROMPT) - 1 for s in range(0, PROMPT, CHUNK)]
    return ends + [PROMPT + i for i in range(DECODE)]


@pytest.fixture(scope="module", params=[QWEN_LIKE, INTERNLM_LIKE],
                ids=lambda c: c["name"])
def case(request):
    cfg = request.param
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                            dense_decoder.BLOCK)
    ref = dense_decoder.logits(weights.make(cfg, 3), seq, cfg)
    return cfg, seq, ref[positions()]


def test_paged_prefill_and_decode_match_reference(case):
    cfg, seq, ref = case
    got = program_logits(cfg, seq, "float32")
    assert np.max(np.abs(got - ref)) <= TOL * np.max(np.abs(ref))


def test_bfloat16_program_is_caught(case):
    cfg, seq, ref = case
    got = program_logits(cfg, seq, "bfloat16")
    assert np.max(np.abs(got - ref)) > TOL * np.max(np.abs(ref))


def test_lower_precision_references_are_caught(case):
    cfg, seq, ref = case
    w = weights.make(cfg, 3)
    for quant in ("bfloat16", "float8_e4m3fn"):
        low = dense_decoder.logits(w, seq, cfg, quant)[positions()]
        assert np.max(np.abs(low - ref)) > TOL * np.max(np.abs(ref)), quant

"""Serve steps over a layer pattern of two entries (host CPU).

Both serve programs carry one stacked cache per pattern entry through the
layer scan and index it at each group.  Driven here step by step, with a
slot joining and leaving mid-run: the paged and the dense cache give
bitwise-equal tokens and logits at every step, each step hands back a
cache of the structure, shapes and dtypes it was given, and each slot's
last logits follow the training forward over what the slot was fed (a
layer reading or writing another group's cache would miss it by far).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.steps import build_serve_engine_steps
from repro.models import api

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

SLOTS, MAX_LEN, PAGE, CHUNK = 3, 32, 4, 4
PPS = MAX_LEN // PAGE
N_PAGES = 1 + 4 * PPS            # the null page and four slots' worth

# two groups of two entries that differ: attention+MLP, then attention+MoE
PATTERN = dict(period=2, n_layers=4, family="moe", n_experts=4, top_k=2,
               moe_positions=(1,))


def _layout(cache):
    return jax.tree.map(lambda a: (a.shape, a.dtype), cache)


class _Run:
    """One backend's steps, with the host's lengths and page table."""

    def __init__(self, cfg, params, backend):
        self.steps = build_serve_engine_steps(
            cfg, slots=SLOTS, max_len=MAX_LEN, backend=backend,
            page_size=PAGE, n_pages=N_PAGES, return_logits=True)
        self.params = params
        self.cache = self.steps.init_cache()
        self.layout = _layout(self.cache)
        self.paged = backend == "paged"
        self.lengths = np.zeros((SLOTS,), np.int32)
        self.table = np.zeros((SLOTS, PPS), np.int32)
        self.fed = [[] for _ in range(SLOTS)]     # tokens each slot holds
        self.last = [None] * SLOTS                # and its latest logits
        self.outputs = []                         # (tokens, logits) a step

    def give_pages(self, slot, pages):
        self.table[slot] = pages
        self.lengths[slot] = 0
        self.fed[slot] = []

    def _sync(self):
        # jnp.array copies: the host arrays change while a step may run
        self.cache["lengths"] = jnp.array(self.lengths)
        if self.paged:
            self.cache["page_table"] = jnp.array(self.table)

    def prefill(self, slot, prompt):
        """Prefill ``prompt`` chunk by chunk; returns the sampled token."""
        for i in range(0, len(prompt), CHUNK):
            part = prompt[i:i + CHUNK]
            toks = np.zeros((CHUNK,), np.int32)
            toks[:len(part)] = part
            self._sync()
            tok, logits, self.cache = self.steps.prefill(
                self.params, jnp.array(toks), np.int32(len(part)),
                np.int32(slot), np.float32(0.0), np.zeros((2,), np.uint32),
                self.cache)
            assert _layout(self.cache) == self.layout
            self.lengths[slot] += len(part)
            self.fed[slot] += part
            self.last[slot] = np.asarray(logits)
            self.outputs.append((int(tok), self.last[slot]))
        return int(tok)

    def decode(self, tokens, active):
        """One decode step; returns every slot's sampled token."""
        self._sync()
        tok, logits, self.cache = self.steps.decode(
            self.params, jnp.array(tokens, jnp.int32), jnp.array(active),
            np.zeros((SLOTS,), np.float32), np.zeros((SLOTS, 2), np.uint32),
            self.cache)
        assert _layout(self.cache) == self.layout
        tok, logits = np.asarray(tok), np.asarray(logits)
        self.lengths += active
        for slot in np.flatnonzero(active):
            self.fed[slot].append(int(tokens[slot]))
            self.last[slot] = logits[slot]
        self.outputs.append((tok[active].tolist(), logits[active]))
        return tok


def _drive(run):
    """Slots 0 and 1 prefill and decode; slot 2 joins; slot 0 leaves and a
    new sequence takes it over on other pages."""
    rng = np.random.default_rng(7)
    pages = 1 + rng.permutation(N_PAGES - 1).reshape(4, PPS)  # scattered
    run.give_pages(0, pages[0])
    run.give_pages(1, pages[1])
    tokens = np.array([run.prefill(0, [5, 6, 7, 8, 9, 10]),
                       run.prefill(1, [20, 21, 22]), 0])
    active = np.array([True, True, False])

    def decode_steps(n):
        nonlocal tokens
        for _ in range(n):
            tokens = np.where(active, run.decode(tokens, active), 0)

    decode_steps(3)
    run.give_pages(2, pages[2])
    tokens[2], active[2] = run.prefill(2, [40, 41, 42, 43, 44]), True
    decode_steps(2)
    active[0] = False                                      # slot 0 leaves
    decode_steps(2)
    run.give_pages(0, pages[3])                            # and is reused
    tokens[0], active[0] = run.prefill(0, [60, 61]), True
    decode_steps(3)


@pytest.fixture(scope="module")
def driven():
    """Both backends driven through the same steps."""
    cfg = dataclasses.replace(registry.smoke("qwen2-1.5b"), **PATTERN)
    assert [s.ffn for s in cfg.pattern()] == ["mlp", "moe"]
    assert cfg.n_groups == 2
    ok, why = api.serve_supported(cfg)
    assert ok, why
    params = api.init_params(jax.random.key(3), cfg)
    runs = {b: _Run(cfg, params, b) for b in ("paged", "dense")}
    for run in runs.values():
        _drive(run)
    return cfg, params, runs


def test_paged_matches_dense_over_two_entry_pattern(driven):
    _, _, runs = driven
    paged, dense = runs["paged"].outputs, runs["dense"].outputs
    assert len(paged) == len(dense) == 16
    for (tok_p, lg_p), (tok_d, lg_d) in zip(paged, dense):
        assert tok_p == tok_d
        assert lg_p.dtype == np.float32 and np.array_equal(lg_p, lg_d), \
            np.abs(lg_p - lg_d).max()


def test_served_logits_follow_the_training_forward(driven):
    """Relative L2 gap between each slot's last served logits and
    ``api.prefill`` over its fed tokens: bf16 both, rounded differently."""
    cfg, params, runs = driven
    run = runs["paged"]
    for slot in range(SLOTS):
        ref = np.asarray(api.prefill(
            params, {"tokens": np.asarray([run.fed[slot]], np.int32)},
            cfg))[0]
        gap = np.linalg.norm(run.last[slot] - ref) / np.linalg.norm(ref)
        assert gap < 0.15, (slot, gap)

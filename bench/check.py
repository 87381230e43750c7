"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the greedy requests that were served tokens is drawn from the seed, the
longest always in it, until it holds ``min_tokens`` served tokens.  A
request still running at the close is compared on the tokens it was served
(in the long-document cell most requests outlast the window).  The
reference (``bench/reference``) runs once over each prompt followed by its
served tokens, at the cell's ``max_len`` (one compiled shape), and the
numbers compared are the widest gap by which a served token's logit lies
below the reference's best logit at that position (``logit_gap``) and,
where the cell's limits name it, the mean of those gaps (``mean_gap``).  A
greedy engine that serves what the model says reads near 0 (rounding in
bfloat16 can pick a near-tied runner-up); a wrong token reads about the
spread of the logits.

The control (:func:`control`) puts the reference in the program's place
in the nearest precision below the configuration's (bfloat16 -> fp8): at
each position of the same prompts and tokens it reads the gap of the token
that the fp8 forward puts first.
"""
from __future__ import annotations

import numpy as np

from bench import weights
from bench.reference import dense_decoder


def sample(served, seed: int, min_tokens: int) -> list:
    """``served``: [(prompt, served)]; the longest first, then others in
    an order drawn from the seed, until ``min_tokens`` served tokens."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    order = [longest] + [int(i) for i in np.random.default_rng(seed)
                         .permutation(len(served)) if i != longest]
    out, n = [], 0
    for i in order:
        if n >= min_tokens:
            break
        out.append(served[i])
        n += len(served[i][1])
    return out


def _inputs(prompt, served, length: int):
    toks = np.concatenate([prompt, served[:-1]])
    if len(toks) > length:
        raise ValueError(f"{len(toks)} tokens > reference length {length}")
    x = np.zeros(length, np.int32)
    x[:len(toks)] = toks
    tgt = np.full(length, -1, np.int32)
    tgt[len(prompt) - 1:len(toks)] = served
    return x, tgt


def _length(max_len: int) -> int:
    b = dense_decoder.BLOCK
    return -(-max_len // b) * b


def gaps(cfg: dict, seed: int, reqs, max_len: int,
         quant: str | None = None) -> np.ndarray:
    """At every compared position, the gap by which the token lies below
    the reference's best logit.  Without ``quant`` the token is the one
    served; with it, the one that the reference computed in ``quant``
    puts first (the control)."""
    w = weights.make(cfg, seed)
    out = []
    for prompt, served in reqs:
        if np.any((served < 0) | (served >= cfg["vocab_size"])):
            return np.array([np.inf])
        x, tgt = _inputs(prompt, served, _length(max_len))
        sel = tgt >= 0
        if quant is not None:
            _, _, top = dense_decoder.logit_stats(w, x, tgt, cfg, quant)
            tgt = np.where(sel, top, -1)
        mx, at, _ = dense_decoder.logit_stats(w, x, tgt, cfg)
        out.append(mx[sel] - at[sel])
    return np.concatenate(out) if out else np.array([np.inf])


def numbers(g: np.ndarray, n_tokens: int, short: int, limits: dict) -> dict:
    """The numbers compared, each beside its limit: the widest gap
    (``logit_gap``), the mean gap (``mean_gap``) where the limits name
    it, the tokens compared and the finished requests short of their
    budget."""
    out = {"logit_gap": {"value": float(np.max(g)),
                         "max": limits["logit_gap"]}}
    if "mean_gap" in limits:
        out["mean_gap"] = {"value": float(np.mean(g)),
                           "max": limits["mean_gap"]}
    out["tokens_compared"] = {"value": n_tokens, "min": limits["min_tokens"]}
    out["short_requests"] = {"value": short, "max": 0}
    return out


def run(cfg: dict, seed: int, served, short: int, limits: dict) -> dict:
    """The program's numbers, each beside its limit."""
    reqs = sample(served, seed, limits["min_tokens"])
    g = gaps(cfg, seed, reqs, cfg["serve"]["max_len"])
    return numbers(g, sum(len(s) for _, s in reqs), short, limits)


def control(cfg: dict, seed: int, served, limits: dict,
            quant: str = "float8_e4m3fn") -> dict:
    """The control's numbers on the program's sample, beside the same
    limits: the reference in ``quant`` put in the program's place."""
    reqs = sample(served, seed, limits["min_tokens"])
    g = gaps(cfg, seed, reqs, cfg["serve"]["max_len"], quant)
    return numbers(g, sum(len(s) for _, s in reqs), 0, limits)


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        v = c["value"]
        ok &= bool(np.isfinite(v))
        ok &= v <= c.get("max", v) and v >= c.get("min", v)
    return bool(ok)

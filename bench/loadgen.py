"""The one traffic generator: reads a mix's parameters and the seed.

A mix is a JSON file under ``bench/traffic/``.  Lengths are drawn as
stratified quantiles of their distribution, ``(i + 0.5) / n`` for
``i < n``, paired by a fixed draw, and the seed only orders them, so every
seed sends the same set of requests in another order: the work in a window
does not change with the seed, only its order and the prompt tokens do.  Inter-arrival gaps of
an open loop are stratified exponential quantiles, permuted the same way
and scaled to fill their span exactly.  The warm-up and the window are
drawn as two such sets, so the window's set is fixed as well.

A stream that has run for a while has requests under way, and a warm-up
that starts from an empty engine would need a whole request's life to
reach them.  So the warm-up starts with ``rate_rps * life_s`` requests
(Little's law) already under way, as a steady stream leaves them: drawn
from a larger stratified set with odds in proportion to their answer's
length (a long request is more often under way), each at a stratified
point of its answer.  Its prompt holds the tokens it has been served so
far and its budget what is left, so the engine holds their keys and values
as it would.  That set too is the same for every seed.

Keys of a mix:
  arrivals        a module of ``bench/arrivals`` (``serve_open``,
                  ``serve_closed``)
  rate_rps        requests per second (open loop)
  clients         clients of a closed loop, each with one request at a time
  stream          requests a closed loop's clients draw from, in all
  warmup_s        seconds of the same traffic before the window opens
  life_s          a request's mean time in the system at ``rate_rps``
                  (measured): sets how many are under way at the warm-up's
                  start; 0 starts from an empty engine
  prompt, output  {"median", "sigma", "min", "max"}: lognormal lengths
  greedy_share    share of requests decoded greedily (temperature 0)
  temperature     temperature of the others
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Spec:
    due: float            # seconds after the window opens (< 0: warm-up)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    temperature: float
    seed: int


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths, clipped to [min, max], ascending."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def _sizes(mix: dict, n: int):
    """The set of n (prompt, output, greedy): stratified lengths paired,
    and greedy ones chosen, by a fixed draw, the same for every seed."""
    fixed = np.random.default_rng(0)
    prompts = quantile_lengths(mix["prompt"], n)
    outs = fixed.permutation(quantile_lengths(mix["output"], n))
    greedy = fixed.permutation(np.arange(n) < round(n * mix["greedy_share"]))
    return prompts, outs, greedy


def _emit(mix, vocab, rng, dues, prompts, outs, greedy) -> list[Spec]:
    """The seed orders the set and draws the tokens."""
    order = rng.permutation(len(prompts))
    return [Spec(float(dues[j]),
                 rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                 int(outs[i]),
                 0.0 if greedy[i] else float(mix["temperature"]),
                 int(rng.integers(0, 2**31)))
            for j, i in enumerate(order)]


def _specs(mix: dict, n: int, vocab: int, rng, dues) -> list[Spec]:
    return _emit(mix, vocab, rng, dues, *_sizes(mix, n))


def under_way(mix: dict, vocab: int, rng, n: int | None = None) -> list[Spec]:
    """The requests under way when the warm-up starts, all due then: n, or
    by Little's law ``rate_rps * life_s``."""
    if n is None:
        n = round(mix["rate_rps"] * mix.get("life_s", 0))
    if n == 0:
        return []
    prompts, outs, greedy = _sizes(mix, 8 * n)
    fixed = np.random.default_rng(1)
    pick = fixed.choice(8 * n, n, replace=False, p=outs / outs.sum())
    done = np.floor(fixed.permutation((np.arange(n) + 0.5) / n)
                    * outs[pick]).astype(int)
    return _emit(mix, vocab, rng, np.full(n, -float(mix["warmup_s"])),
                 prompts[pick] + done, outs[pick] - done, greedy[pick])


def _gaps(n: int, span: float, rng) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    g = rng.permutation(-np.log1p(-u))
    return g * (span / g.sum())


def open_loop(mix: dict, vocab: int, seed: int, seconds: float) -> list[Spec]:
    """Requests due in [-warmup_s, seconds), sorted by due time."""
    rng = np.random.default_rng(seed)
    out = under_way(mix, vocab, rng)
    for start, span in ((-mix["warmup_s"], mix["warmup_s"]), (0.0, seconds)):
        n = max(1, round(mix["rate_rps"] * span))
        dues = start + np.concatenate([[0.0], np.cumsum(_gaps(n, span, rng))[:-1]])
        out += _specs(mix, n, vocab, rng, dues)
    return out


def closed_loop(mix: dict, vocab: int, seed: int):
    """(the ``clients`` requests under way at the warm-up's start, the
    stream of ``stream`` requests the clients draw from next)."""
    rng = np.random.default_rng(seed)
    first = under_way(mix, vocab, rng, mix["clients"])
    n = mix["stream"]
    return first, _specs(mix, n, vocab, rng, np.zeros(n))

"""Seeded random weights of a configuration, made on the device.

The benchmark, not the program, makes the weights: one jitted call from the
seed, in the types and the layout of the program's parameter tree, as the
configuration's architecture module gives them (``shapes``).  The plain
reference calls the same function with the same seed, so it takes nothing
that the program has made.

Every leaf is drawn from its own key, ``fold_in(key(seed), leaf number)``,
so a leaf does not depend on the others.  Matrices and biases are normal
with standard deviation ``STD``; norm scales are ``1 + NORM_STD * normal``,
so the check also covers the scale and bias paths.  The query and key
matrices are drawn wider, with variance ``SCORE_STD / hidden_size``, so
that an attention score over normed inputs has a standard deviation of
about ``SCORE_STD``: attention then picks out a few positions, as a
trained model's does, and a key or value lost from the cache changes the
tokens served (at ``STD`` attention is all but uniform over a long
context, and a cache that is never written reads much like one that is).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import harness

STD = 0.02
NORM_STD = 0.1
SCORE_STD = 2.0


def _nest(flat: dict) -> dict:
    """Paths to a tree; ``groups/<i>`` become the tuple ``groups``, one
    entry for each position of the layer pattern."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    groups = tree["groups"]
    tree["groups"] = tuple(groups[str(i)] for i in range(len(groups)))
    return tree


def make(cfg: dict, seed: int) -> dict:
    """The parameter tree for ``seed``, on the default device, one jit."""
    spec = harness.arch(cfg).shapes(cfg)

    def build(key):
        flat = {}
        for i, (path, (shape, dtype)) in enumerate(spec.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            std = STD
            if path.endswith(("attn/wq", "attn/wk")):
                std = (SCORE_STD / cfg["hidden_size"]) ** 0.5
            x = 1.0 + NORM_STD * z if path.endswith("scale") else std * z
            flat[path] = x.astype(dtype)
        return _nest(flat)

    return jax.jit(build)(jax.random.key(seed))

"""Seeded random weights of a dense GQA decoder, made on the device.

The benchmark, not the program, makes the weights: one jitted call from the
seed, in the type they are served in (matrices bfloat16, norm scales
float32), laid out as the program's parameter tree.  The plain reference
calls the same function with the same seed, so it takes nothing that the
program has made.

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

STD = 0.02
NORM_STD = 0.1
SCORE_STD = 2.0


def shapes(cfg: dict) -> dict:
    """{path: (shape, dtype)} of the parameter tree, in a fixed order.

    Paths follow the program's tree: ``groups/0/...`` leaves are stacked
    over the layers on their first axis."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    n = cfg["num_hidden_layers"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"embed": ((v, d), bf), "final_norm/scale": ((d,), f32)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), bf)
    g = "groups/0/"
    out.update({
        g + "mixer_norm/scale": ((n, d), f32),
        g + "attn/wq": ((n, d, hq * dh), bf),
        g + "attn/wk": ((n, d, hkv * dh), bf),
        g + "attn/wv": ((n, d, hkv * dh), bf),
        g + "attn/wo": ((n, hq * dh, d), bf),
        g + "ffn_norm/scale": ((n, d), f32),
        g + "mlp/wi_gate": ((n, d, f), bf),
        g + "mlp/wi_up": ((n, d, f), bf),
        g + "mlp/wo": ((n, f, d), bf),
    })
    if cfg["qkv_bias"]:
        out.update({g + "attn/bq": ((n, hq * dh), bf),
                    g + "attn/bk": ((n, hkv * dh), bf),
                    g + "attn/bv": ((n, hkv * dh), bf)})
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    tree["groups"] = (tree["groups"]["0"],)
    return tree


def make(cfg: dict, seed: int) -> dict:
    """The parameter tree for ``seed``, on the default device, one jit."""
    spec = shapes(cfg)

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

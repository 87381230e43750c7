"""The dense GQA decoder (Qwen2, InternLM2) as the benchmark sees it.

A configuration file names its architecture module under ``"arch"``; the
harness loads it by path (``bench.harness.arch``).  Such a module provides:

  program_config(cfg)        the program's ``ModelConfig``
  shapes(cfg)                {path: (shape, dtype)} of the parameter tree,
                             in the order ``bench.weights.make`` draws it
  decode_flops(cfg, lengths) operations of one decode step
  decode_bytes(cfg, lengths) bytes it needs

``cfg`` is the configuration file's dict: the published ``config.json``
keys and the serve geometry.

Counts are for what the algorithm needs and not for what an implementation
happens to do: a decode step reads every weight once and the keys and
values of the live tokens of its active slots, not the padded pool.
Multiply-adds count two operations.  Norms, rotary embedding, softmax and
sampling are left out of the operations (they are well under 1% of them at
these widths); the bytes count only weights and the key/value cache, which
are all but a few kilobytes of a step's traffic.
"""
from __future__ import annotations

import jax.numpy as jnp

BF16 = 2   # bytes per element of the served weights and cache


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a published configuration file."""
    from repro.models.types import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype="bfloat16", source=cfg["source"])


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


def _dims(cfg: dict):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, hq, cfg["num_key_value_heads"], d // hq


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products (biases and norms excluded)."""
    d, hq, hkv, dh = _dims(cfg)
    attn = d * hq * dh * 2 + d * hkv * dh * 2          # wq, wo; wk, wv
    return attn + 3 * d * cfg["intermediate_size"]    # gate, up, down


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight the step reads once: the layers' matrices,
    biases and norm scales (float32), the final norm and the output head.
    The input embedding of an untied model is read a few rows at a time and
    is not counted."""
    d, hq, hkv, dh = _dims(cfg)
    n = cfg["num_hidden_layers"]
    bias = (hq + 2 * hkv) * dh * BF16 if cfg["qkv_bias"] else 0
    per_layer = layer_matmul_params(cfg) * BF16 + bias + 2 * d * 4
    return n * per_layer + d * 4 + head_params(cfg) * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    """Key and value bytes of one token over all layers."""
    _, _, hkv, dh = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * hkv * dh * BF16


def _attn_flops(cfg: dict, n_keys: int) -> int:
    """One query row against ``n_keys`` keys, all layers: q.k and p.v."""
    _, hq, _, dh = _dims(cfg)
    return cfg["num_hidden_layers"] * 4 * hq * dh * n_keys


def decode_flops(cfg: dict, lengths) -> int:
    """Decode step of the active slots, ``lengths`` = each one's tokens
    already cached; the new token attends to those and to itself."""
    per_tok = 2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + head_params(cfg))
    return sum(per_tok + _attn_flops(cfg, n + 1) for n in lengths)


def decode_bytes(cfg: dict, lengths) -> int:
    """Every weight once, the live keys and values read, the new ones
    written."""
    kv = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + sum((n + 1) * kv for n in lengths)

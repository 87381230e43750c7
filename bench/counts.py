"""Operations and needed bytes of one decode step, from shapes and live
lengths.

Counted from the published configuration keys, for what the algorithm needs
and not for what an implementation happens to do: a decode step reads every
weight once and the keys and values of the live tokens of its active slots,
not the padded pool.  Multiply-adds count two operations.  Norms, rotary
embedding, softmax and sampling are left out of the operations (they are
well under 1% of them at these widths); the bytes count only weights and
the key/value cache, which are all but a few kilobytes of a step's traffic.
"""
from __future__ import annotations

BF16 = 2   # bytes per element of the served weights and cache


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

"""Plain float32 GQA decoder: the reference that decides ``correct``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the published description (Qwen2, InternLM2: pre-norm
RMSNorm, rotary embedding on half-split channels with the configuration's
``rope_theta``, grouped-query attention with query head ``h`` reading
key/value head ``h // (heads / kv_heads)``, optional bias on q, k and v,
SwiGLU feed-forward, tied or untied output head).  It imports nothing from
the program: the configuration is the published ``config.json`` keys and
the weights come from :func:`bench.weights.make`.

One sequence at a time, layer by layer (a scan over the stacked layers)
and in blocks of query rows, so it fits beside nothing else on one chip at
the cells' lengths.  It returns per-position statistics, never the
``[L, vocab]`` logits.

``quant`` computes the same forward in a lower precision, for the control:
every operand of every matrix product (weights, activations, attention
scores and probabilities) is rounded to ``bfloat16``, or to
``float8_e4m3fn`` with a scale per tensor (weights) or per row
(activations) set from its largest magnitude, as an fp8 inference path
would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512          # query rows per attention / logits block
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def _round(x, quant, axis):
    """x rounded to ``quant`` and back to float32 (None: unchanged)."""
    if quant is None:
        return x
    if quant == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "float8_e4m3fn":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return q * scale
    raise ValueError(f"unknown precision {quant!r}")


def _mm(a, w, quant):
    """a [..., K] @ w [K, N]: activation rows and the whole weight rounded."""
    return _round(a, quant, -1) @ _round(w, quant, None)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [L, H, Dh]; rotate the two halves of the channels."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, quant):
    """Causal GQA: q [L, Hq, Dh], k/v [L, Hkv, Dh] -> [L, Hq, Dh]."""
    n, hq, dh = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(_round(k, quant, -1), rep, axis=1)   # head h -> h // rep
    v = jnp.repeat(_round(v, quant, -1), rep, axis=1)
    q = _round(q, quant, -1)
    cols = jnp.arange(n)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(dh)
        rows = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = _round(jax.nn.softmax(s, axis=-1), quant, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(n // BLOCK))
    return out.reshape(n, hq, dh)


def _layer(cfg, quant, x, w):
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    eps = cfg["rms_norm_eps"]
    n = x.shape[0]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = w["attn"]
    h = _rms(x, w["mixer_norm"]["scale"], eps)
    q, k, v = _mm(h, a["wq"], quant), _mm(h, a["wk"], quant), \
        _mm(h, a["wv"], quant)
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(n)
    q = _rope(q.reshape(n, hq, dh), pos, cfg["rope_theta"])
    k = _rope(k.reshape(n, hkv, dh), pos, cfg["rope_theta"])
    y = _attention(q, k, v.reshape(n, hkv, dh), quant)
    x = x + _mm(y.reshape(n, hq * dh), a["wo"], quant)
    m = w["mlp"]
    h = _rms(x, w["ffn_norm"]["scale"], eps)
    g = _mm(h, m["wi_gate"], quant)
    x = x + _mm(jax.nn.silu(g) * _mm(h, m["wi_up"], quant), m["wo"], quant)
    return x, None


def _hidden(weights, tokens, cfg, quant):
    """Final-normed hidden states [L, D] and the output head [D, V]."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, cfg, quant), x,
                        weights["groups"][0])
    x = _rms(x, weights["final_norm"]["scale"], cfg["rms_norm_eps"])
    head = (weights["embed"].T if cfg["tie_word_embeddings"]
            else weights["lm_head"]).astype(jnp.float32)
    return x, head


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _stats(weights, tokens, targets, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        x, head = _hidden(weights, tokens, dict(cfg_items), quant)

        def block(i):
            xb = jax.lax.dynamic_slice_in_dim(x, i * BLOCK, BLOCK, 0)
            tb = jax.lax.dynamic_slice_in_dim(targets, i * BLOCK, BLOCK, 0)
            lg = _mm(xb, head, quant)
            tgt = jnp.take_along_axis(lg, jnp.maximum(tb, 0)[:, None], 1)[:, 0]
            return lg.max(-1), tgt, jnp.argmax(lg, -1).astype(jnp.int32)

        mx, tgt, top = jax.lax.map(block, jnp.arange(x.shape[0] // BLOCK))
    return mx.reshape(-1), tgt.reshape(-1), top.reshape(-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(weights, tokens, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        x, head = _hidden(weights, tokens, dict(cfg_items), quant)
        return _mm(x, head, quant)


_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "vocab_size",
         "rms_norm_eps", "rope_theta", "tie_word_embeddings", "qkv_bias")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS)


def logit_stats(weights, tokens, targets, cfg: dict, quant=None):
    """Per position p of ``tokens`` [L] (L a multiple of ``BLOCK``): the
    largest logit of the next-token distribution, the logit of
    ``targets[p]`` (any id where ``targets[p] < 0``) and the arg-max id.
    Returns three numpy arrays of length L."""
    out = _stats(weights, jnp.asarray(tokens, jnp.int32),
                 jnp.asarray(targets, jnp.int32), _items(cfg), quant)
    return tuple(np.asarray(a) for a in out)


def logits(weights, tokens, cfg: dict, quant=None) -> np.ndarray:
    """All next-token logits [L, V] (L a multiple of ``BLOCK``), for tests
    at small sizes."""
    return np.asarray(_logits(weights, jnp.asarray(tokens, jnp.int32),
                              _items(cfg), quant))

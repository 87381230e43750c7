"""Jit'd wrapper: GQA expansion + Pallas flash attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import flash_attention as k
from . import ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "impl"))
def attention(q, kv_k, kv_v, *, causal: bool = True, window=None,
              q_block: int = 128, kv_block: int = 128,
              impl: str = "pallas"):
    """q: [B,Hq,S,D]; kv: [B,Hkv,S,D] (expanded here when Hkv < Hq)."""
    hq, hkv = q.shape[1], kv_k.shape[1]
    if hkv != hq:
        kv_k = jnp.repeat(kv_k, hq // hkv, axis=1)
        kv_v = jnp.repeat(kv_v, hq // hkv, axis=1)
    if impl == "reference":
        return ref.attention_ref(q, kv_k, kv_v, causal=causal, window=window)
    return k.flash_attention(q, kv_k, kv_v, causal=causal, window=window,
                             q_block=q_block, kv_block=kv_block,
                             interpret=interpret_mode())

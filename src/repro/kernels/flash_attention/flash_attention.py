"""Blocked (FlashAttention-style) causal/SWA attention, Pallas TPU.

Grid ``(B*H, num_q_blocks, num_kv_blocks)``: the kv dimension is innermost,
with the running max / denominator / accumulator held in VMEM scratch across
kv steps (initialized at kj==0, finalized into the output block at the last
kv step).  Q/K/V blocks are staged HBM->VMEM by the pipeline emitter with
MXU-aligned block shapes.  Sliding-window (SWA) masking is fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  causal: bool, window: int | None, q_block: int,
                  kv_block: int, n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0]                                     # [qb, D]
    k = k_ref[0]                                     # [kb, D]
    d = q.shape[-1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (1.0 / (d ** 0.5))

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 0)
    k_pos = kj * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                     (q_block, kv_block), 1)
    mask = jnp.ones((q_block, kv_block), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _():
        l = jnp.maximum(l_sc[...], 1e-20)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool) -> jax.Array:
    """q,k,v: [B,H,S,D] -> [B,H,S,D]."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0
    n_q, n_kv = sq // q_block, sk // kv_block
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda g, qi, kj: (g, qi, 0)),
            pl.BlockSpec((1, kv_block, d), lambda g, qi, kj: (g, kj, 0)),
            pl.BlockSpec((1, kv_block, d), lambda g, qi, kj: (g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d), lambda g, qi, kj: (g, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)

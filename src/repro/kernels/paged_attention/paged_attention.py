"""Paged decode attention, Pallas TPU.

The block-table indirection (vLLM-style paged KV) is textbook irregular
memory access: the page id for grid step (b, j) comes from a scalar-
prefetched ``page_table``, so the K/V page fetches are *precise prefetches*
driven by the pipeline emitter — the serving-side instance of the paper's
runahead idea.

Grid ``(B, pages_per_seq)``, page dimension innermost; running softmax
state ``[H, 1, 1]`` lives in VMEM scratch across pages; invalid tail
positions are masked with the scalar-prefetched ``lengths``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, page: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # the query keeps a length-1 row axis so both dots are batched over
    # heads with a non-empty free dim on each side (Mosaic's matmul form)
    q = q_ref[0].astype(jnp.float32)                     # [H, 1, D]
    k = k_ref[0].astype(jnp.float32)                     # [page, H, D]
    d = q.shape[-1]
    s = jnp.einsum("hqd,phd->hqp", q, k) * (1.0 / (d ** 0.5))

    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = pos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_sc[...]                                   # [H, 1, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jnp.einsum(
        "hqp,phd->hqd", p, v_ref[0].astype(jnp.float32))
    m_sc[...] = m_new

    @pl.when(j == n_pages - 1)
    def _():
        l = jnp.maximum(l_sc[...], 1e-20)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    interpret: bool):
    """q: [B,H,D]; pages: [n_pages_pool, page, H, D]; page_table:
    [B, pages_per_seq]; lengths: [B] -> [B,H,D]."""
    b, h, d = q.shape
    page = k_pages.shape[1]
    pages_per_seq = page_table.shape[1]
    kernel = functools.partial(_paged_kernel, page=page,
                               n_pages=pages_per_seq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_table, lengths
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, h, 1, d), lambda bb, j, pt, ln: (bb, 0, 0, 0)),
            pl.BlockSpec((1, page, h, d),
                         lambda bb, j, pt, ln: (pt[bb, j], 0, 0, 0)),
            pl.BlockSpec((1, page, h, d),
                         lambda bb, j, pt, ln: (pt[bb, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, 1, d),
                               lambda bb, j, pt, ln: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q[:, :, None, :], k_pages, v_pages)
    return out[:, :, 0, :]

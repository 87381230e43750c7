"""MoE token dispatch / combine, Pallas TPU.

The routing table is the irregular index stream of the MoE family (DESIGN.md
§3): *dispatch* scatters token rows into expert-capacity slots, *combine*
gathers the top-k expert outputs back per token.  Both run as per-token grids
with the big buffers in ``pl.ANY`` (HBM) and rows moved by explicit DMA with
a runahead window (``depth`` in-flight copies), exactly like the
gather_runahead kernel — MoE dispatch *is* a gather/scatter.

Dropped tokens (slot == -1) are redirected to a trash slot appended past the
real capacity and sliced off by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(slot_ref, x_ref, o_ref, sem, *, n_tokens: int,
                     n_slots: int):
    t = pl.program_id(0)
    dest = slot_ref[t]
    dest = jnp.where(dest >= 0, dest, n_slots)   # trash slot
    copy = pltpu.make_async_copy(x_ref.at[t], o_ref.at[dest], sem)
    copy.start()
    copy.wait()


def dispatch(x: jax.Array, slot: jax.Array, n_slots: int, *,
             interpret: bool) -> jax.Array:
    """x: [T,D]; slot: [T] in [0,n_slots) or -1 -> [n_slots, D]."""
    t, d = x.shape
    kernel = functools.partial(_dispatch_kernel, n_tokens=t, n_slots=n_slots)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots + 1, d), x.dtype),
        interpret=interpret,
    )(slot, x)
    return out[:n_slots]


def _combine_kernel(slot_ref, w_ref, ye_ref, o_ref, scratch, sems, *,
                    fanin: int, depth: int, n_tokens: int):
    t = pl.program_id(0)

    def start(tok, slot_idx):
        for kk in range(fanin):
            src = slot_ref[tok, kk]
            src = jnp.where(src >= 0, src, 0)
            pltpu.make_async_copy(
                ye_ref.at[src], scratch.at[slot_idx, kk], sems.at[slot_idx, kk]
            ).start()

    @pl.when(t == 0)
    def _():
        for j in range(depth):
            if j < n_tokens:
                start(j, j % depth)

    s = t % depth
    for kk in range(fanin):
        src = slot_ref[t, kk]
        src = jnp.where(src >= 0, src, 0)
        pltpu.make_async_copy(
            ye_ref.at[src], scratch.at[s, kk], sems.at[s, kk]
        ).wait()
    w = w_ref[t, :].astype(jnp.float32)
    ok = (slot_ref[t, :] >= 0).astype(jnp.float32)
    acc = jnp.sum(scratch[s].astype(jnp.float32) * (w * ok)[:, None], axis=0)
    o_ref[...] = acc[None].astype(o_ref.dtype)

    @pl.when(t + depth < n_tokens)
    def _():
        start(t + depth, s)


def combine(ye: jax.Array, slot: jax.Array, weights: jax.Array, *,
            depth: int = 2, interpret: bool) -> jax.Array:
    """ye: [n_slots,D]; slot,weights: [T,K] -> [T,D]."""
    t, fanin = slot.shape
    d = ye.shape[1]
    depth = min(depth, t)
    kernel = functools.partial(_combine_kernel, fanin=fanin, depth=depth,
                               n_tokens=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, d), lambda i, s_ref, w_ref: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, fanin, d), ye.dtype),
            pltpu.SemaphoreType.DMA((depth, fanin)),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), ye.dtype),
        interpret=interpret,
    )(slot, weights, ye)

"""Jit'd public wrappers for the runahead gather kernels."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from . import gather_runahead as k
from . import ref


@functools.partial(jax.jit, static_argnames=("impl", "block_rows", "depth"))
def gather(table, idx, *, impl: str = "runahead", block_rows: int = 8,
           depth: int = 2):
    """out[i] = table[idx[i]] with runahead prefetch.

    impl: "runahead" (explicit multi-buffered DMA; ``depth`` = in-flight
    fetches, the MSHR analogue) or "reference" (jnp oracle).
    """
    if impl == "reference":
        return ref.gather_ref(table, idx)
    return k.runahead_gather(table, idx, block_rows=block_rows, depth=depth,
                             interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("depth",))
def gather_bag(table, idx, weights, *, depth: int = 2):
    """Listing-1 aggregation: out[s] = sum_k w[s,k] * table[idx[s,k]]."""
    return k.gather_bag(table, idx, weights, depth=depth,
                        interpret=interpret_mode())

"""Jit'd wrappers for MoE dispatch/combine kernels."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from . import moe_dispatch as k
from . import ref


@functools.partial(jax.jit, static_argnames=("n_slots", "impl"))
def dispatch(x, slot, *, n_slots: int, impl: str = "pallas"):
    if impl == "reference":
        return ref.dispatch_ref(x, slot, n_slots)
    return k.dispatch(x, slot, n_slots, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("depth", "impl"))
def combine(ye, slot, weights, *, depth: int = 2, impl: str = "pallas"):
    if impl == "reference":
        return ref.combine_ref(ye, slot, weights)
    return k.combine(ye, slot, weights, depth=depth, interpret=interpret_mode())

"""Jit'd wrapper for paged decode attention."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from . import paged_attention as k
from . import ref


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    impl: str = "pallas"):
    if impl == "reference":
        return ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                       lengths)
    return k.paged_attention(q, k_pages, v_pages, page_table, lengths,
                             interpret=interpret_mode())

"""Jit'd wrapper for the SSD scan kernel."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from . import ref
from . import ssd_scan as k


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(xh, dt, a_log, b_mat, c_mat, d_skip, *, chunk: int = 64,
        impl: str = "pallas"):
    if impl == "reference":
        y, _ = ref.ssd_ref(xh, dt, a_log, b_mat, c_mat, d_skip)
        return y
    return k.ssd_scan(xh, dt, a_log, b_mat, c_mat, d_skip, chunk=chunk,
                      interpret=interpret_mode())

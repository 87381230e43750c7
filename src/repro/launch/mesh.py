"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings from the
    ``in_shardings``/constraints (JAX 0.9 defaults to Explicit axes, under
    which an un-annotated gather such as the embedding lookup is refused)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (gradient all-reduce over DCI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4, *, pod: int | None = None):
    """Small mesh over the process's devices: virtual CPU devices in the
    integration tests, the chips of one TPU host in ``chip_smoke.py``."""
    n = len(jax.devices())
    need = data * model * (pod or 1)
    assert n >= need, f"need {need} devices, have {n}"
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))

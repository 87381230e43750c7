"""Pallas TPU kernels for the memory-bound hot spots (runahead gather,
paged / flash attention, MoE dispatch, SSD scan).

Each ``ops.py`` wrapper compiles its kernel through Mosaic for the TPU and
runs it in the Pallas interpreter only on the CPU backend (tests and CPU
examples); :func:`interpret_mode` is the one place that decides.
"""
import jax


def interpret_mode() -> bool:
    """True when Pallas kernels must run interpreted: the CPU backend."""
    return jax.default_backend() == "cpu"

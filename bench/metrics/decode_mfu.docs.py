"""Decode step's operations (matrix products of the active slots, attention
over live context) at the bf16 peak over its device time, %."""
from bench.readers import decode_mfu as read  # noqa: F401

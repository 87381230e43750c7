"""Decode step's share of its roofline, %: needed bytes (weights once, live
keys and values) at the HBM peak over its device time; memory bounds it."""
from bench.readers import decode_roofline as read  # noqa: F401

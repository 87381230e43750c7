"""Seconds from process start to the window's start: weights, compile (or cache
reads), warm-up traffic."""
from bench.readers import setup_s as read  # noqa: F401

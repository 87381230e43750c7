"""Prompt tokens prefilled and tokens sampled inside the window, of every
request, over the window's seconds."""
from bench.readers import total_tok_s as read  # noqa: F401

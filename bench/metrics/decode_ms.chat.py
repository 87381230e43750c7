"""Mean device milliseconds of one decode step (program jit_decode_fn) in the
trace."""
from bench.readers import decode_ms as read  # noqa: F401

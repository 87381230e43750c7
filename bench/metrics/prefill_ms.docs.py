"""Mean device milliseconds of one prefill chunk (program jit_prefill_fn) in
the trace."""
from bench.readers import prefill_ms as read  # noqa: F401

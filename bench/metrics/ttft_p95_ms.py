"""95th percentile (nearest rank) of time to first token over all requests due
in the window, from each one's due time; one with no first token by the
window's end counts to the end, as does one the engine pushed back."""
from bench.readers import ttft_p95_ms as read  # noqa: F401
